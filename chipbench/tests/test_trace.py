"""The trace reduction, on synthetic events and on a recorded v5e trace
(``data/v5e.json.gz``, recorded by ``record_trace.py``)."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

import tracereduce as tr

DATA = Path(__file__).resolve().parent / "data"


def test_union_clip_complement():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 3), (5, 8), (9, 12)], 2, 10) == [(2, 3), (5, 8),
                                                          (9, 10)]
    assert tr.complement([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5),
                                                      (8, 10)]
    assert tr.complement([(0, 10)], 0, 10) == []


def test_gap_takes_the_innermost_span_over_most_of_it():
    spans = [("executor.pass", 0, 100), ("executor.read", 10, 60),
             ("fetch", 40, 60), ("client.publish", 0, 200),
             ("combiner.order", 150, 170)]
    gaps = [(12, 30), (35, 55), (60, 90), (140, 180), (200, 230)]
    got = [lab for _s, _e, lab in tr.label_gaps(gaps, spans)]
    # 35-55: read 5, fetch 15; 140-180: publish 30, order 20 (publish is
    # outermost but covers more of the gap); 200-230: nothing
    assert got == ["executor.read", "fetch", "executor.pass",
                   "client.publish", "none"]


def test_reduce_synthetic():
    ms = 1e6
    t = tr.Trace(
        spans=[("window", 10 * ms, 110 * ms),
               ("executor.pass", 0, 200 * ms),
               ("fetch", 60 * ms, 70 * ms)],
        ops={"/device:TPU:0": [(0, 20 * ms), (15 * ms, 30 * ms),
                               (50 * ms, 60 * ms), (100 * ms, 120 * ms)]},
        programs={"/device:TPU:0": [("jit_a", 0, 30 * ms),
                                    ("jit_b", 50 * ms, 60 * ms),
                                    ("jit_a", 100 * ms, 120 * ms)]})
    s = tr.reduce(t)
    assert s.window_s == pytest.approx(0.1)
    # busy inside [10, 110]: 10-30, 50-60, 100-110 ms
    assert s.busy_s == pytest.approx(0.04)
    assert s.idle_share == pytest.approx(0.6)
    # programs that start in the window count whole
    assert s.program_s == {"jit_b": pytest.approx(0.01),
                           "jit_a": pytest.approx(0.02)}
    assert s.program_n == {"jit_b": 1, "jit_a": 1}
    assert [(g0 / ms, g1 / ms, lab) for g0, g1, lab in s.gaps] == [
        (30, 50, "executor.pass"), (60, 100, "executor.pass")]
    s.gaps = tr.label_gaps([(60 * ms, 68 * ms)], t.spans)
    assert s.idle_by_label() == {"fetch": pytest.approx(0.008)}


def test_program_name():
    assert tr.program_name("jit__apply_impl(1234)") == "jit__apply_impl"
    assert tr.program_name("jit_add") == "jit_add"


XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 6000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 8000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit__apply_impl(42)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 9000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "cb:window" } }
  event_metadata { key: 2 value { id: 2 name: "shard_args" } }
  event_metadata { key: 3 value { id: 3 name: "cb:fetch" } } }
"""


def test_load_reads_device_lines_and_harness_spans(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    t = tr.load(str(path))
    assert t.ops == {"/device:TPU:0": [(1000, 6000), (3000, 9000)]}
    assert t.programs == {"/device:TPU:0": [("jit__apply_impl", 1000,
                                             9000)]}
    assert t.spans == [("window", 0, 20000), ("fetch", 9000, 19000)]
    s = tr.reduce(t)
    assert s.busy_s == pytest.approx(8e-6)
    assert [lab for _a, _b, lab in s.gaps] == ["none", "fetch"]


def test_recorded_v5e_trace():
    d = json.loads(gzip.open(DATA / "v5e.json.gz", "rt").read())
    want = d["pinned"]
    t = tr.Trace(
        spans=[tuple(x) for x in d["trace"]["spans"]],
        ops={k: [tuple(x) for x in v] for k, v in d["trace"]["ops"].items()},
        programs={k: [tuple(x) for x in v]
                  for k, v in d["trace"]["programs"].items()})
    assert list(t.ops) == ["/device:TPU:0"]
    s = tr.reduce(t)
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert s.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    # busy again, by marking 10 ns bins of the window
    w0, w1 = s.window
    bins = np.zeros(int((w1 - w0) / 10) + 1, bool)
    for a, b in t.ops["/device:TPU:0"]:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            bins[int(round((a - w0) / 10)):int(round((b - w0) / 10))] = True
    assert bins.sum() * 1e-8 == pytest.approx(s.busy_s, rel=1e-3)
    assert 0 < s.busy_s < s.window_s
    assert s.program_n == want["program_n"]
    assert s.program_s == pytest.approx(want["program_s"], rel=1e-9)
    assert len(s.gaps) == want["gaps"]
    idle = s.idle_by_label()
    assert idle == pytest.approx(want["idle_by_label"], rel=1e-9)
    # the labelled gaps tile the idle part of the window exactly
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s,
                                               rel=1e-9)
