"""The graph cell's serve-and-check path on the CPU at scale 10 with 8
clients: a sound run comes out correct; the control (the reference one
precision lower, in the program's place) and each fault that
``test_cell_cpu.py`` plants under the timed path come out not correct."""
import json

import pytest

import harness
from repro.launch import serve as _serve
from test_cell_cpu import FAULTS

SEED = 2 ** 31 + 23456
SMALL = dict(scale=10, n_vertices=1 << 10, edge_capacity=1 << 14,
             insert_pool=1 << 10)


def run(seed=SEED):
    _cell, cfg, mix = harness.load_cell("graph-conn-r90")
    cfg = dict(cfg, **SMALL)
    served = harness.serve(cfg, dict(mix, clients=8), seed=seed,
                           seconds=0.5, ramp_s=0.2)
    return cfg, served


@pytest.fixture(scope="module")
def sound():
    return run()


def test_sound_run_is_correct(sound):
    cfg, served = sound
    checks = harness.check(cfg, served)
    assert harness.passed(checks), checks
    assert served["window"].ops > 0
    assert served["window_compile_events"] == 0
    e2e = harness.end_to_end(served)
    assert e2e["ops_per_s"][0] > 0 and e2e["op_p95_ms"][0] > 0


def test_control_is_not_correct(sound):
    cfg, served = sound
    checks = harness.control(cfg, served)
    assert not harness.passed(checks), checks
    assert checks["answers_wrong"][0] > 0


def test_config_is_the_scale_it_names():
    cfg = json.loads((harness.HERE / "configs" / "graph-kron19.json")
                     .read_text())
    assert cfg["n_vertices"] == 1 << cfg["scale"]
    assert cfg["edge_capacity"] >= cfg["edgefactor"] << cfg["scale"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(_serve.StructureExecutor, "__call__", FAULTS[fault])
    cfg, served = run(seed=SEED + 1)
    checks = harness.check(cfg, served)
    assert not harness.passed(checks), checks
