"""``BENCHMARK.json`` against the files the harness finds by name."""
import json
import re

import pytest

import harness
import readers

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_files():
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert all(NAME.match(n) for n in names), names
    for c in BENCH["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert (harness.HERE / "structures" /
                f"{cfg['structure']}.py").exists()
    for w in BENCH["workloads"]:
        assert (harness.HERE / "traffic" / f"{w['traffic']}.json").exists()
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_reader_agrees(m):
    r = readers.load(m["name"])
    assert (r.layer, r.source, r.moves, r.unit) == (
        m["layer"], m["source"], m["moves"], m["unit"])
    e2e = {e["name"] for e in BENCH["end_to_end"]}
    assert m["moves"] in e2e
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(m["workloads"]) <= cells


def test_every_cell_reports_enough():
    for w in BENCH["workloads"]:
        e2e = [e for e in BENCH["end_to_end"]
               if w["name"] in e.get("workloads", [w["name"]])]
        assert "setup_s" in {e["name"] for e in e2e} and len(e2e) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in BENCH["per_layer"])


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = BENCH["run_seconds"]
    runs = 2 + 14 * 24
    assert 1 <= rs <= 51
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_bounds():
    for e in BENCH["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25
