"""The harness's serve-and-check path on the CPU, at the tests' small
size (``tiny.py``): sound runs come out correct; the control (the
reference one precision lower, in the program's place) and each fault
planted under the timed path come out not correct."""
import numpy as np
import pytest

import harness
import tiny
from repro.launch import serve as _serve

CELLS = ["map-ycsb-a", "pq-5050", "map-ycsb-c"]
SEED = 2 ** 31 + 12345


def run(name, seed=SEED):
    cfg, mix = tiny.cell(name)
    served = harness.serve(cfg, mix, seed=seed, seconds=0.5, ramp_s=0.2)
    return cfg, served


@pytest.fixture(scope="module", params=CELLS)
def sound(request):
    return (request.param,) + run(request.param)


def test_sound_run_is_correct(sound):
    name, cfg, served = sound
    checks = harness.check(cfg, served)
    assert harness.passed(checks), checks
    assert served["window"].ops > 0
    assert served["window_compile_events"] == 0
    e2e = harness.end_to_end(served)
    assert e2e["ops_per_s"][0] > 0 and e2e["op_p95_ms"][0] > 0


def test_control_is_not_correct(sound):
    name, cfg, served = sound
    checks = harness.control(cfg, served)
    assert not harness.passed(checks), checks
    assert checks["answers_wrong"][0] > 0


# -- faults under the timed path: the executor's call is replaced ----------
ORIGINAL = _serve.StructureExecutor.__call__


def state_unchanged(self, reqs):
    """Every pass answers, then the structure's state is put back."""
    snap = self.ds.snapshot()
    out = ORIGINAL(self, reqs)
    self.ds.restore(snap)
    return out


def half_left_out(self, reqs):
    """Only the first half of each batch reaches the structure; the rest
    get the answer of the half's last operation."""
    k = max(1, len(reqs) // 2)
    out = ORIGINAL(self, reqs[:k])
    return list(out) + [out[-1]] * (len(reqs) - k)


def answer_altered(self, reqs):
    """The first answer of each batch is changed where it is produced."""
    out = list(ORIGINAL(self, reqs))
    a = out[0]
    out[0] = (not a) if isinstance(a, bool) else (
        -1.0 if a is None else float(np.float32(a) + 1))
    return out


FAULTS = {"state_unchanged": state_unchanged,
          "half_left_out": half_left_out,
          "answer_altered": answer_altered}
# a cell with no updates has no state for a step to leave unchanged
CASES = [(c, f) for c in CELLS for f in FAULTS
         if not (c == "map-ycsb-c" and f == "state_unchanged")]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_is_not_correct(monkeypatch, name, fault):
    monkeypatch.setattr(_serve.StructureExecutor, "__call__", FAULTS[fault])
    cfg, served = run(name, seed=SEED + 1)
    checks = harness.check(cfg, served)
    assert not harness.passed(checks), checks
