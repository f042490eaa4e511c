"""The in-program tracer (``repro.core.tracing``, DESIGN.md §19): off it
records nothing; on, its spans nest and keep every thread's records; the
scheduler's stage stamps add up over a served map; and its clock anchor
lands in a profiler trace."""
import glob
import os
import threading
import time

import numpy as np
import pytest

from repro.core import substrate
from repro.core.tracing import ANCHOR, TRACER, Tracer
from repro.launch.serve import StructureExecutor
from repro.serving import PCScheduler


@pytest.fixture
def stop_tracer():
    """The module-level tracer is off again after the test."""
    try:
        yield
    finally:
        TRACER.stop()


def _spin(ns: int) -> None:
    t = time.perf_counter_ns()
    while time.perf_counter_ns() - t < ns:
        pass


def test_off_returns_one_shared_noop_and_records_nothing():
    tr = Tracer()
    assert not tr.on and not TRACER.on
    assert tr.span("a") is tr.span("b") is TRACER.span("c")
    with tr.span("a"):
        pass
    assert tr.stamp() == 0
    tr.batch(1, 2, 3, 4, 5)
    tr.anchor()
    tr.start()
    assert tr.stop() == {"spans": [], "batches": [], "anchors": []}


def test_spans_nest_and_every_thread_comes_back():
    tr = Tracer()
    tr.start()
    with tr.span("outer"):
        with tr.span("inner"):
            _spin(200_000)
        time.sleep(0.002)              # wall without CPU

    def worker():
        for _ in range(3):
            with tr.span("worker"):
                _spin(50_000)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not any(t.is_alive() for t in threads)
    rec = tr.stop()
    with tr.span("late"):               # off again
        pass
    spans = rec["spans"]
    names = [s[0] for s in spans]
    assert names.count("worker") == 12 and "late" not in names
    assert [s[1] for s in spans] == sorted(s[1] for s in spans)
    (_, o0, o1, ocpu), = [s for s in spans if s[0] == "outer"]
    (_, i0, i1, _), = [s for s in spans if s[0] == "inner"]
    assert o0 <= i0 <= i1 <= o1
    assert (o1 - o0) - ocpu >= 1_000_000     # the sleep is off the CPU
    for _name, t0, t1, cpu in spans:
        assert 0 <= cpu <= t1 - t0


def test_restart_drops_the_last_session():
    tr = Tracer()
    tr.start()
    with tr.span("first"):
        pass
    tr.stop()
    tr.start()
    with tr.span("second"):
        pass
    assert [s[0] for s in tr.stop()["spans"]] == ["second"]


def _map_executor():
    keys = np.arange(1, 201, dtype=np.float32)
    return StructureExecutor(substrate.get("map"), capacity=256, c_max=16,
                             n_shards=4, key_range=(0.0, 201.0),
                             items=list(zip(keys.tolist(),
                                            (keys * 2).tolist())))


def test_stage_stamps_add_up_over_a_served_map(stop_tracer):
    ex = _map_executor()
    ex([{"method": "assign", "input": (1.0, 3.0)},
        {"method": "lookup", "input": 2.0}])       # compile outside the count
    ids_by_pass = []

    def step(reqs):
        ids_by_pass.append([r["id"] for r in reqs])
        return ex(reqs)

    lat = {}
    n_clients, per_client = 6, 25
    sch = PCScheduler(step, max_batch=16, n_shards=2, pq_capacity=1024,
                      tier="eliminate")
    TRACER.start()

    def client(c):
        rng = np.random.default_rng(c)
        for i in range(per_client):
            key = float(rng.integers(1, 201))
            req = ({"method": "lookup", "input": key} if i % 2 else
                   {"method": "assign", "input": (key, float(c))})
            req["id"] = (c, i)
            t0 = time.perf_counter_ns()
            sch.submit_async(req).result(timeout=60)
            lat[(c, i)] = time.perf_counter_ns() - t0

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads)
    sch.close()
    rec = TRACER.stop()

    batches = rec["batches"]
    assert len(batches) == len(sch.batches) == len(ids_by_pass)
    assert sum(b[2] for b in batches) == n_clients * per_client == len(lat)
    assert [b[2] for b in batches] == sch.batches
    for (t_start, t_end, n, pending, handoff), ids in zip(batches,
                                                          ids_by_pass):
        assert n == len(ids)
        assert t_end >= t_start and pending >= 0 and handoff >= 0
        assert pending + handoff <= sum(lat[i] for i in ids)
    names = [s[0] for s in rec["spans"]]
    assert names.count("scheduler.resolve") == len(batches)
    for name in ("scheduler.wait_batch", "map.prep", "map.dispatch",
                 "map.fetch", "map.convert"):
        assert name in names
    for _name, t0, t1, cpu in rec["spans"]:
        assert t1 >= t0 and 0 <= cpu <= t1 - t0
    # every map span falls inside some pass's step_fn call
    for name, t0, t1, _cpu in rec["spans"]:
        if name.startswith("map."):
            assert any(s <= t0 and t1 <= e for s, e, *_ in batches)


def test_anchor_lands_in_a_cpu_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    tr = Tracer()
    tr.start()
    tr.anchor()                         # before the session: not recorded
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(8):
            tr.anchor()
    finally:
        jax.profiler.stop_trace()
    anchors = tr.stop()["anchors"]
    assert len(anchors) == 9
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    starts = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == ANCHOR:
                    starts[dict(e.stats)["n"]] = e.start_ns
    assert sorted(starts) == list(range(1, 9))
    # offset = trace start - `before`, within the bracket `after - before`
    pairs = [(starts[n] - anchors[n][0], anchors[n][1] - anchors[n][0])
             for n in starts]
    best, width = min(pairs, key=lambda p: p[1])
    assert width < 100_000
    for off, w in pairs:
        assert abs(off - best) <= w + width
