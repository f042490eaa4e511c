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

from gated_step import GatedStep, publish_together, wait_waiting


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
    tr.count("a")
    tr.gauge("b", 7)
    tr.anchor()
    tr.start()
    assert tr.stop() == {"spans": [], "batches": [], "anchors": [],
                         "counters": {}}


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


def test_topup_counter_matches_topped_up(stop_tracer):
    """``combiner.topup`` counts what ``PCScheduler.topped_up`` counts,
    and an entry that joins a waiting batch keeps the stamp of the
    ordering pass that chose it."""
    step = GatedStep()
    TRACER.start()
    sch = PCScheduler(step, max_batch=4)
    f0 = sch.submit_async(0)
    assert step.entered.wait(10)
    futs = publish_together(sch, [1, 2])
    wait_waiting(sch, 2)
    futs += publish_together(sch, [3, 4, 5])
    assert wait_waiting(sch, 5) == [[1, 2, 3, 4], [5]]
    with sch._cond:
        stamps = [e.t_chosen for e in sch._waiting[0]]
    assert 0 < stamps[0] == stamps[1] < stamps[2] == stamps[3]
    step.release()
    assert [f.result(timeout=10) for f in [f0] + futs] == [0, 2, 4, 6, 8,
                                                           10]
    sch.close()
    rec = TRACER.stop()
    assert rec["counters"]["combiner.topup"] == sch.topped_up == 2
    assert [b[2] for b in rec["batches"]] == sch.batches == [1, 4, 1]


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


# ---------------------------------------------------------------------------
# the graph's spans and counters
# ---------------------------------------------------------------------------
GRAPH_SPANS = ("graph.prep", "graph.dispatch", "graph.fetch",
               "graph.convert")


def _graph_executor(edges, n):
    return StructureExecutor(substrate.get("graph"), n=n,
                             edge_capacity=4096, c_max=16, n_shards=2,
                             edges=edges)


def _kron(scale, seed):
    from repro.core.seq_graph import canonical_edges, kronecker_edges

    n = 1 << scale
    return canonical_edges(kronecker_edges(
        scale, 16 * n, np.random.default_rng(seed)), n), n


def _mixed_batch(rng, distinct, n, k=12):
    reqs = []
    for i in range(k):
        r = rng.random()
        if r < 0.25:
            e = tuple(int(x) for x in distinct[rng.integers(len(distinct))])
            reqs.append({"method": "delete", "input": e})
        elif r < 0.45:
            reqs.append({"method": "insert", "input": tuple(
                int(x) for x in rng.integers(0, n, 2))})
        else:
            reqs.append({"method": "connected", "input": tuple(
                int(x) for x in rng.integers(0, n, 2))})
    return reqs


def test_graph_spans_come_once_a_pass(stop_tracer):
    """Each pass of updates and reads makes one span of each graph stage
    per call into the graph: prep and dispatch for the update and for
    the read, the read's one fetch, and its conversion."""
    distinct, n = _kron(7, 3)
    ex = _graph_executor(distinct, n)
    rng = np.random.default_rng(4)
    ex(_mixed_batch(rng, distinct, n))         # compile outside the count
    windows = []
    TRACER.start()
    for _ in range(10):
        reqs = _mixed_batch(rng, distinct, n)
        t0 = time.perf_counter_ns()
        ex(reqs)
        windows.append((t0, time.perf_counter_ns(),
                        any(r["method"] != "connected" for r in reqs)))
    rec = TRACER.stop()
    for t0, t1, has_update in windows:
        names = [s[0] for s in rec["spans"] if t0 <= s[1] and s[2] <= t1]
        calls = 2 if has_update else 1
        assert names.count("graph.prep") == calls
        assert names.count("graph.dispatch") == calls
        assert names.count("graph.fetch") == 1
        assert names.count("graph.convert") >= 1
    assert {s[0] for s in rec["spans"]} == set(GRAPH_SPANS)


def test_graph_counters_match_the_device(stop_tracer):
    """The host counters that ride each read's fetch add up to the
    device's own refresh counts; the lean reads are the rest."""
    distinct, n = _kron(8, 5)
    ex = _graph_executor(distinct, n)
    before = ex.ds.refresh_counts()
    TRACER.start()
    rng = np.random.default_rng(6)
    n_reads = 0
    for i in range(40):
        reqs = (_mixed_batch(rng, distinct, n) if i % 3 else
                [{"method": "connected", "input": (1, 2)}])
        n_reads += any(r["method"] == "connected" for r in reqs)
        ex(reqs)
    rec = TRACER.stop()
    after = ex.ds.refresh_counts()
    c = rec["counters"]
    for b in ("keep", "merge", "repair", "scratch"):
        assert c.get(f"graph.refresh.{b}", 0) == after[b] - before[b], b
    assert c.get("graph.repair.overflow", 0) == (after["overflow"]
                                                 - before["overflow"])
    fused = sum(after[b] - before[b]
                for b in ("keep", "merge", "repair", "scratch"))
    assert fused + c["graph.refresh.lean"] == n_reads
    assert after["scratch"] == 1 and after["repair"] > 0


def test_graph_step_counts_match_numpy_counts(stop_tracer):
    """The scratch fixpoint counts the largest hop distance from a
    component's least vertex, plus the step that changes nothing; a
    repair counts the levels of the forest below its cut, plus the one
    that finds nothing."""
    from repro.core.device_graph import DeviceGraph

    distinct, n = _kron(8, 7)
    adj = [[] for _ in range(n)]
    for u, v in distinct.tolist():
        adj[u].append(v)
        adj[v].append(u)
    depth = np.full(n, -1)
    for r in range(n):
        if depth[r] >= 0:
            continue
        depth[r], front, d = 0, [r], 0
        while front:
            d += 1
            nxt = sorted({w for x in front for w in adj[x] if depth[w] < 0})
            for w in nxt:
                depth[w] = d
            front = nxt
    TRACER.start()
    g = DeviceGraph(n, edge_capacity=4096, c_max=16, edges=distinct)
    g.connected(0, 1)
    assert TRACER.stop()["counters"]["graph.refresh.steps"] == \
        int(depth.max()) + 1
    # a path 0-1-...-9: cutting (4, 5) leaves 5..9, four levels below 5
    TRACER.start()
    p = DeviceGraph(10, edge_capacity=64, c_max=4,
                    edges=[(i, i + 1) for i in range(9)])
    p.connected(0, 9)
    p.delete(4, 5)
    assert p.connected(0, 9) is False
    c = TRACER.stop()["counters"]
    assert c["graph.refresh.repair"] == 1
    assert c["graph.refresh.steps"] == 5


def test_graph_pass_makes_one_host_fetch(monkeypatch):
    """Updates and reads of one pass still cost one blocking fetch, the
    refresh counters included."""
    import repro.core.device_graph as dg

    distinct, n = _kron(7, 9)
    ex = _graph_executor(distinct, n)
    rng = np.random.default_rng(10)
    ex(_mixed_batch(rng, distinct, n))
    fetches = []
    real = dg._host_fetch
    monkeypatch.setattr(dg, "_host_fetch",
                        lambda t: fetches.append(1) or real(t))
    for _ in range(5):
        fetches.clear()
        reqs = _mixed_batch(rng, distinct, n)
        reqs.append({"method": "delete", "input": tuple(
            int(x) for x in distinct[0])})
        reqs.append({"method": "connected", "input": (0, 1)})
        ex(reqs)
        assert fetches == [1]
