"""The reading of the program's own spans and stamps (``programspans``),
on synthetic records and on a tiny cell served on the CPU with the
program's tracer on (``stages.traced_serve``)."""
import math

import pytest

import programspans as ps
import stages
import tiny

MS = 1_000_000


def program():
    """Two passes in the window [100, 300) ms and one after it."""
    return {
        "batches": [(100 * MS, 110 * MS, 4, 8 * MS, 20 * MS),
                    (200 * MS, 206 * MS, 2, 2 * MS, 6 * MS),
                    (300 * MS, 301 * MS, 1, 0, 0)],
        "spans": [("map.prep", 90 * MS, 91 * MS, MS),      # before any
                  ("scheduler.wait_batch", 95 * MS, 100 * MS, 0),
                  ("map.prep", 100 * MS, 102 * MS, MS),
                  ("map.dispatch", 102 * MS, 103 * MS, MS),
                  ("map.fetch", 103 * MS, 109 * MS, 0),
                  ("map.convert", 109 * MS, 110 * MS, MS // 2),
                  ("scheduler.resolve", 110 * MS, 111 * MS, MS // 2),
                  ("scheduler.wait_batch", 111 * MS, 200 * MS, 0),
                  ("map.prep", 200 * MS, 201 * MS, MS),
                  ("map.prep", 201 * MS, 202 * MS, MS),
                  ("map.fetch", 202 * MS, 206 * MS, 0),
                  ("scheduler.resolve", 206 * MS, 207 * MS, MS),
                  ("map.prep", 300 * MS, 301 * MS, MS)],
        "anchors": [(10, 50), (1000, 1004), (2000, 2100)]}


def test_window_passes_and_stage_means():
    passes = ps.window_passes(program(), 100 * MS, 300 * MS)
    assert [p.n for p in passes] == [4, 2]
    assert passes[0].wall == {"map.prep": 2 * MS, "map.dispatch": MS,
                              "map.fetch": 6 * MS, "map.convert": MS,
                              "scheduler.resolve": MS}
    assert passes[1].wall["map.prep"] == 2 * MS
    # (8 + 2) ms over 6 ops; (20 + 6) ms over 6 ops
    assert ps.pending_ms(passes) == pytest.approx(10 / 6)
    assert ps.handoff_ms(passes) == pytest.approx(26 / 6)
    assert ps.stage_ms(passes, "prep") == pytest.approx(2.0)
    assert ps.stage_ms(passes, "dispatch") == pytest.approx(0.5)
    assert ps.stage_ms(passes, "fetch") == pytest.approx(5.0)
    assert ps.stage_ms(passes, "resolve") == pytest.approx(1.5)
    # wall 2 + 1 + 1 + 2 + 1 = 7 ms over prep, convert and resolve;
    # CPU 1 + 0.5 + 0.5 + 2 + 1 = 5 ms
    assert ps.offcpu_share(passes) == pytest.approx(200 / 7)


def test_every_reading_is_none_without_passes():
    empty = {"batches": [], "spans": [], "anchors": []}
    for passes in (ps.window_passes(empty, 0, 1e12),
                   ps.window_passes(program(), 400 * MS, 500 * MS)):
        assert passes == []
        assert ps.pending_ms(passes) is None
        assert ps.handoff_ms(passes) is None
        for stage in ps.STAGES:
            assert ps.stage_ms(passes, stage) is None
        assert ps.offcpu_share(passes) is None
    assert ps.clock_offset([], {}) is None
    assert ps.idle_waiting_share(empty, [], (5, 5), 0.0) is None


def test_clock_offset_takes_the_narrowest_bracket():
    prog = program()
    # trace starts at program time + 7000 (+ up to the bracket)
    starts = {0: 10 + 7030, 1: 1000 + 7001, 2: 2000 + 7090, 9: 5}
    assert ps.clock_offset(prog["anchors"], starts) == (7001, 4)


def test_idle_waiting_share_on_a_known_offset():
    prog = program()
    off = 5_000 * MS              # trace = program + 5 s
    window = (off + 100 * MS, off + 300 * MS)
    # device idle 150-250 ms (program time): wait_batch covers 150-200
    gaps = [(off + 150 * MS, off + 250 * MS)]
    assert ps.idle_waiting_share(prog, gaps, window, off) == \
        pytest.approx(25.0)
    # the same gaps read with no offset fall outside every span
    assert ps.idle_waiting_share(prog, gaps, window, 0.0) == 0.0
    idle = ps.idle_by_span(prog, gaps, off)
    assert idle["scheduler.wait_batch"] == pytest.approx(0.05)
    assert idle["map.prep"] == pytest.approx(0.002)
    assert idle["map.fetch"] == pytest.approx(0.004)
    assert idle["scheduler.resolve"] == pytest.approx(0.001)
    assert idle["step_fn, outside map spans"] == pytest.approx(0.0)
    assert idle["none"] == pytest.approx(0.1 - 0.057)
    assert sum(idle.values()) == pytest.approx(0.1)


def test_covered():
    spans = [(0, 10), (5, 15), (20, 30)]
    assert ps.covered([(8, 22), (25, 40)], spans) == 7 + 2 + 5
    assert ps.covered([(15, 20)], spans) == 0


@pytest.mark.parametrize("name", ["map-ycsb-a", "map-ycsb-c"])
def test_tiny_cell_reads_every_stage(tmp_path, name):
    cfg, mix = tiny.cell(name)
    run = stages.traced_serve(cfg, mix, seed=2 ** 31 + 77, seconds=0.6,
                              tdir=str(tmp_path), ramp_s=0.2)
    out = stages.report(run)
    assert out["anchors"]["in_trace"] > 0
    assert out["anchors"]["bracket_ns"] < 100_000
    assert out["passes"] > 0
    for k, v in out["metrics"].items():
        assert v is not None and math.isfinite(v) and v >= 0, k
    chk = out["stage_check"]
    assert chk["map_spans_ms_per_pass"] < chk["step_ms_per_pass"]
    assert chk["op_pending_handoff_step_resolve_ms"] < \
        chk["op_mean_latency_ms"]
