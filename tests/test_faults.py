"""Fault-tolerance layer tests (ISSUE 7; DESIGN.md §15).

Covers the deterministic fault harness itself (plan determinism, the
breaker state machine, the transactional dispatch guard), bit-identical
snapshot/restore on all three device structures — including the fused
``mixed_rounds`` megapass, where one failed dispatch must rewind BOTH
the update state and the pending read results (ISSUE 9) — combiner
lease takeover, the scheduler supervisor's exactly-once recovery, and
the close() vs in-flight-device-step race (regression: slow fake
step_fn).

The whole module is marked ``faults`` so the dedicated CI fault-injection
job selects it with ``-m faults``; it stays in tier-1 too (not slow).
"""
import threading
import time
from collections import Counter

import numpy as np
import pytest

from repro.core.batched_map import ShardedMap
from repro.core.combining import (TIER_DEVICE, TIER_HOST, ParallelCombiner,
                                  Request, Status, TierRouter)
from repro.core.device_graph import DeviceGraph
from repro.core.faults import (CircuitBreaker, CombinerLeaseExpired,
                               DispatchGuard, FaultPlan,
                               InjectedCombinerKill, InjectedDispatchError,
                               make_guard)
from repro.core.pc_pq import pc_sharded_priority_queue
from repro.core.sharded_pq import ShardedBatchedPQ
from repro.serving.scheduler import PCScheduler

from gated_step import GatedStep, publish_together, wait_waiting

pytestmark = pytest.mark.faults

_NOSLEEP = dict(sleep=lambda s: None)


# ---------------------------------------------------------------------------
# FaultPlan: determinism + one-shot semantics
# ---------------------------------------------------------------------------
def _dispatch_schedule(plan, n=200):
    out = []
    for _ in range(n):
        try:
            plan.maybe_fail_dispatch("t")
            out.append(False)
        except InjectedDispatchError:
            out.append(True)
    return out

def test_fault_plan_dispatch_schedule_deterministic():
    a = _dispatch_schedule(FaultPlan(7, dispatch_fail_rate=0.3))
    b = _dispatch_schedule(FaultPlan(7, dispatch_fail_rate=0.3))
    assert a == b and any(a) and not all(a)
    c = _dispatch_schedule(FaultPlan(8, dispatch_fail_rate=0.3))
    assert c != a                      # seed actually matters

def test_fault_plan_dispatch_cap():
    plan = FaultPlan(0, dispatch_fail_rate=1.0, max_dispatch_failures=3)
    assert sum(_dispatch_schedule(plan, 50)) == 3

def test_fault_plan_kill_and_spike_fire_once():
    naps = []
    plan = FaultPlan(0, kill_combiner_at_pass=3, latency_spike_passes=(2,),
                     latency_spike_s=0.5, sleep=naps.append)
    plan.on_combiner_pass(1)
    plan.on_combiner_pass(2)           # spike, no kill yet
    assert naps == [0.5]
    with pytest.raises(InjectedCombinerKill):
        plan.on_combiner_pass(3)
    # both are one-shot: a restarted combiner passing the same indices
    # again must not re-fire
    plan.on_combiner_pass(2)
    plan.on_combiner_pass(3)
    assert naps == [0.5]
    snap = plan.counters.snapshot()
    assert snap["combiner_kills"] == 1 and snap["latency_spikes"] == 1
    assert plan.counters.faults_injected == 2

def test_standard_plan_matches_issue_acceptance():
    plan = FaultPlan.standard(0)
    assert plan.kill_combiner_at_pass == 3
    assert plan.dispatch_fail_rate == pytest.approx(0.10)
    assert plan.latency_spike_passes == frozenset({5})


# ---------------------------------------------------------------------------
# CircuitBreaker state machine (fake clock)
# ---------------------------------------------------------------------------
def test_circuit_breaker_lifecycle():
    now = [0.0]
    b = CircuitBreaker(failure_threshold=3, cooldown_s=1.0,
                       clock=lambda: now[0])
    assert b.state == "closed" and b.allows()
    b.record_failure(); b.record_failure()
    assert b.state == "closed"         # below threshold
    b.record_failure()
    assert b.state == "open" and not b.allows()
    now[0] = 0.5
    assert not b.allows()              # cooldown not elapsed
    now[0] = 1.5
    assert b.state == "half_open"
    assert b.allows()                  # exactly one probe...
    assert not b.allows()              # ...re-armed while it is in flight
    b.record_success()
    assert b.state == "closed" and b.allows()
    # a failed probe re-opens and restarts the cooldown
    b.record_failure(); b.record_failure(); b.record_failure()
    now[0] = 3.0
    assert b.allows()                  # the probe
    b.record_failure()
    assert b.state == "open" and not b.allows()


# ---------------------------------------------------------------------------
# DispatchGuard: transactional semantics
# ---------------------------------------------------------------------------
def test_guard_retries_until_plan_relents():
    plan = FaultPlan(0, dispatch_fail_rate=1.0, max_dispatch_failures=2)
    g = DispatchGuard(plan, **_NOSLEEP)
    state = {"x": 0}
    restored = []
    out = g.run(lambda: state.__setitem__("x", state["x"] + 1) or "ok",
                snapshot=lambda: dict(state),
                restore=lambda s: (restored.append(1),
                                   state.update(s)),
                site="t")
    assert out == "ok"
    # two injected failures -> two restores -> third attempt commits
    assert len(restored) == 2 and state["x"] == 1
    snap = plan.counters.snapshot()
    assert snap["retries"] == 2 and snap["restores"] == 2

def test_guard_exhausts_retries_and_restores():
    plan = FaultPlan(0, dispatch_fail_rate=1.0)
    g = DispatchGuard(plan, max_retries=2, **_NOSLEEP)
    state = {"x": 0}
    with pytest.raises(InjectedDispatchError):
        g.run(lambda: state.__setitem__("x", state["x"] + 1),
              snapshot=lambda: dict(state), restore=state.update)
    assert state == {"x": 0}           # final failure also restored

def test_guard_value_error_is_not_retried():
    calls = []

    def thunk():
        calls.append(1)
        raise ValueError("refused")

    g = DispatchGuard(FaultPlan(0), **_NOSLEEP)
    state = {"x": 1}
    with pytest.raises(ValueError):
        g.run(thunk, snapshot=lambda: dict(state), restore=state.update)
    assert calls == [1]                # exactly one attempt

def test_guard_feeds_breaker():
    now = [0.0]
    b = CircuitBreaker(failure_threshold=2, cooldown_s=10.0,
                       clock=lambda: now[0])
    plan = FaultPlan(0, dispatch_fail_rate=1.0)
    g = DispatchGuard(plan, breaker=b, max_retries=3, **_NOSLEEP)
    with pytest.raises(InjectedDispatchError):
        g.run(lambda: None, snapshot=lambda: None, restore=lambda s: None)
    assert b.state == "open"

def test_make_guard_convention():
    plan = FaultPlan(0)
    ready = DispatchGuard(plan)
    assert make_guard(plan, ready) is ready
    assert make_guard(None, None) is None
    assert make_guard(plan, False) is None
    assert make_guard(None, True) is not None       # fault-free overhead row
    assert make_guard(plan, None).plan is plan      # plan => guarded


# ---------------------------------------------------------------------------
# Bit-identical restore per structure (permanent injected failure)
# ---------------------------------------------------------------------------
def _flaky_guard():
    """Guard whose plan can be flipped hot: rate 0 -> healthy, 1 -> always
    fails (and with max_retries=1 the op surfaces the injected error)."""
    plan = FaultPlan(0, dispatch_fail_rate=0.0)
    return plan, DispatchGuard(plan, max_retries=1, **_NOSLEEP)

def test_pq_restore_bit_identical():
    plan, guard = _flaky_guard()
    pq = ShardedBatchedPQ(64, c_max=4, n_shards=2, guard=guard)
    pq.apply(0, [5.0, 1.0, 9.0, 3.0])
    a0 = np.asarray(pq.state.a).copy()
    s0 = np.asarray(pq.state.size).copy()
    v0 = pq.values()
    plan.dispatch_fail_rate = 1.0
    with pytest.raises(InjectedDispatchError):
        pq.apply(2, [0.5])
    np.testing.assert_array_equal(np.asarray(pq.state.a), a0)
    np.testing.assert_array_equal(np.asarray(pq.state.size), s0)
    assert pq.values() == v0
    plan.dispatch_fail_rate = 0.0      # mirrors intact: op replays cleanly
    assert pq.apply(2, [0.5]) == [1.0, 3.0]
    assert pq.values() == [0.5, 5.0, 9.0]

def test_map_restore_bit_identical():
    plan, guard = _flaky_guard()
    m = ShardedMap(64, c_max=4, n_shards=2, key_range=(0.0, 100.0),
                   guard=guard)
    m.update_batch(["insert"] * 3, [(10.0, 1.0), (20.0, 2.0), (30.0, 3.0)])
    keys0 = np.asarray(m.state.keys).copy()
    vals0 = np.asarray(m.state.vals).copy()
    items0 = m.items()
    plan.dispatch_fail_rate = 1.0
    with pytest.raises(InjectedDispatchError):
        m.update_batch(["insert", "delete"], [(40.0, 4.0), 10.0])
    np.testing.assert_array_equal(np.asarray(m.state.keys), keys0)
    np.testing.assert_array_equal(np.asarray(m.state.vals), vals0)
    assert m.items() == items0
    plan.dispatch_fail_rate = 0.0
    assert m.update_batch(["insert", "delete"],
                          [(40.0, 4.0), 10.0]) == [True, True]
    assert m.read_batch(["lookup", "lookup"], [40.0, 10.0]) == [4.0, None]

def test_graph_restore_bit_identical():
    plan, guard = _flaky_guard()
    g = DeviceGraph(16, edge_capacity=64, c_max=4, n_shards=2, guard=guard)
    assert g.insert(0, 1) and g.insert(1, 2)
    edges0 = g.edges()
    snap0 = [np.asarray(a).copy() for a in g.state]
    plan.dispatch_fail_rate = 1.0
    with pytest.raises(InjectedDispatchError):
        g.insert(2, 3)
    plan.dispatch_fail_rate = 0.0
    for a, b in zip(g.state, snap0):
        np.testing.assert_array_equal(np.asarray(a), b)
    assert g.edges() == edges0
    # mirrors (_outstanding_ins / _maybe_stale / _n_edges) rewound with
    # the state: reads and replays behave as if the fault never happened
    assert g.connected(0, 2) and not g.connected(0, 3)
    assert g.insert(2, 3) and g.connected(0, 3)

def test_map_megapass_restore_bit_identical():
    """ISSUE 9: a failed MEGAPASS dispatch — R update+read rounds fused
    into ONE scan — restores the update state AND the pending read
    results bit-identically; after the plan heals, the SAME megapass
    replays cleanly and the reads observe their whole epoch."""
    plan, guard = _flaky_guard()
    m = ShardedMap(64, c_max=4, n_shards=2, key_range=(0.0, 100.0),
                   guard=guard)
    m.update_batch(["insert"] * 2, [(10.0, 1.0), (20.0, 2.0)])
    keys0 = np.asarray(m.state.keys).copy()
    vals0 = np.asarray(m.state.vals).copy()
    items0 = m.items()
    rounds = [("update", ["insert", "delete"], [(30.0, 3.0), 10.0]),
              ("read", ["lookup", "lookup", "lookup"],
               [30.0, 10.0, 20.0])]
    plan.dispatch_fail_rate = 1.0
    with pytest.raises(InjectedDispatchError):
        m.mixed_rounds(rounds)
    np.testing.assert_array_equal(np.asarray(m.state.keys), keys0)
    np.testing.assert_array_equal(np.asarray(m.state.vals), vals0)
    assert m.items() == items0
    plan.dispatch_fail_rate = 0.0      # mirrors intact: replay is clean
    hs = m.mixed_rounds(rounds)
    assert hs[0].result() == [True, True]
    assert hs[1].result() == [3.0, None, 2.0]
    assert m.items() == [(20.0, 2.0), (30.0, 3.0)]


def test_pq_megapass_restore_bit_identical():
    plan, guard = _flaky_guard()
    pq = ShardedBatchedPQ(64, c_max=4, n_shards=2, guard=guard)
    pq.update_batch(["insert"] * 3, [5.0, 1.0, 9.0])
    a0 = np.asarray(pq.state.a).copy()
    s0 = np.asarray(pq.state.size).copy()
    v0 = pq.values()
    rounds = [("update", ["insert", "extract_min"], [0.5, None]),
              ("read", ["peek_min", "peek_min"], [None, None])]
    plan.dispatch_fail_rate = 1.0
    with pytest.raises(InjectedDispatchError):
        pq.mixed_rounds(rounds)
    np.testing.assert_array_equal(np.asarray(pq.state.a), a0)
    np.testing.assert_array_equal(np.asarray(pq.state.size), s0)
    assert pq.values() == v0
    plan.dispatch_fail_rate = 0.0
    hs = pq.mixed_rounds(rounds)
    # structure-level in-round contract: extracts before inserts (host
    # elimination is an ENGINE concern), so the extract takes 1.0 and
    # the later peek round observes the freshly inserted 0.5
    assert hs[0].result()[1] == 1.0
    assert hs[1].result() == [0.5, 0.5]
    assert pq.values() == [0.5, 5.0, 9.0]


def test_graph_guarded_read_pass_restores():
    plan, guard = _flaky_guard()
    g = DeviceGraph(16, edge_capacity=64, c_max=4, n_shards=2, guard=guard)
    g.insert(0, 1)
    plan.dispatch_fail_rate = 1.0
    with pytest.raises(InjectedDispatchError):
        g.connected(0, 1)              # fused read_pass donates state too
    plan.dispatch_fail_rate = 0.0
    assert g.connected(0, 1)


# ---------------------------------------------------------------------------
# Combiner lease takeover (tentpole part 2)
# ---------------------------------------------------------------------------
def test_lease_takeover_serves_survivors_exactly_once():
    plan = FaultPlan(0, kill_combiner_at_pass=4)
    eng = pc_sharded_priority_queue(128, c_max=8, n_shards=2,
                                    fault_plan=plan, lease_timeout=0.5)
    # warm the jit caches single-threaded (passes 1-2): the lease timeout
    # must exceed the worst-case combining pass, and the first pass pays
    # compilation — an unwarmed cache would make a LIVE combiner look
    # dead and trigger a spurious takeover mid-dispatch
    eng.execute("insert", 1.0)
    assert eng.execute("extract_min") == 1.0
    T = 4
    start = threading.Barrier(T)
    failed, ok = [], []

    def worker(i):
        start.wait()
        for j in range(3):
            v = float(10 * (i + 1) + j)
            try:
                eng.execute("insert", v)
                ok.append(v)
            except InjectedCombinerKill:
                failed.append(v)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(T)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    # the killed combiner's own request fails; everything else is served
    assert len(failed) <= 1
    assert plan.counters.combiner_kills == 1
    assert eng.takeovers >= 1 and plan.counters.takeovers >= 1
    drained = []
    while True:
        v = eng.execute("extract_min")
        if v is None:
            break
        drained.append(v)
    # exactly-once: the queue holds each survivor once, victims never
    assert Counter(drained) == Counter(ok)

def test_wait_while_client_mode_is_bounded():
    eng = ParallelCombiner(lambda e, rs: None, lambda e, r: None,
                           lease_timeout=0.05)
    r = Request(status=Status.STARTED)
    t0 = time.monotonic()
    with pytest.raises(CombinerLeaseExpired):
        eng.wait_while(r, Status.STARTED)
    assert time.monotonic() - t0 < 5.0

def test_wait_while_returns_when_status_moves():
    eng = ParallelCombiner(lambda e, rs: None, lambda e, r: None,
                           lease_timeout=5.0)
    r = Request(status=Status.STARTED)
    threading.Timer(0.05, lambda: setattr(r, "status",
                                          Status.FINISHED)).start()
    eng.wait_while(r, Status.STARTED)
    assert r.status == Status.FINISHED

def test_record_drops_cost_retries_never_ops():
    plan = FaultPlan(3, drop_record_rate=0.6)
    eng = pc_sharded_priority_queue(64, c_max=4, n_shards=2,
                                    fault_plan=plan)
    for v in (4.0, 2.0, 8.0):
        eng.execute("insert", v)
    assert [eng.execute("extract_min") for _ in range(4)] \
        == [2.0, 4.0, 8.0, None]
    assert plan.counters.record_drops >= 1


# ---------------------------------------------------------------------------
# Router degradation
# ---------------------------------------------------------------------------
def test_router_degrades_to_host_and_probes_back():
    now = [0.0]
    b = CircuitBreaker(failure_threshold=1, cooldown_s=1.0,
                       clock=lambda: now[0])
    r = TierRouter("t", (TIER_HOST, TIER_DEVICE), force=TIER_DEVICE)
    r.attach_breaker(TIER_DEVICE, b)
    assert r.choose(8) == TIER_DEVICE
    b.record_failure()
    assert r.choose(8) == TIER_HOST    # breaker veto beats force
    assert r.breaker_state() == {TIER_DEVICE: "open"}
    now[0] = 2.0
    assert r.choose(8) == TIER_DEVICE  # half-open probe flows back
    b.record_success()
    assert r.choose(8) == TIER_DEVICE


# ---------------------------------------------------------------------------
# Scheduler: supervisor recovery + close()/in-flight race (satellite b)
# ---------------------------------------------------------------------------
def test_scheduler_supervisor_recovers_exactly_once():
    plan = FaultPlan(0, kill_combiner_at_pass=2)
    served = []

    def step(xs):
        served.extend(xs)
        return [x * 2 for x in xs]

    with PCScheduler(step, max_batch=4, n_shards=2,
                     fault_plan=plan) as s:
        futs = [s.submit_async(i, deadline=float(i % 5))
                for i in range(24)]
        outs = [f.result(timeout=30) for f in futs]
    assert outs == [i * 2 for i in range(24)]
    assert Counter(served) == Counter(range(24))   # zero lost, zero dup
    assert s.takeovers >= 1
    assert s.fault_counters()["combiner_kills"] == 1

def test_scheduler_takeover_with_a_waiting_batch_serves_each_once():
    """The combiner dies while a batch waits for the device: the waiting
    batch is served by the device thread, the killed pass's requests are
    replayed, and every request is applied exactly once."""
    plan = FaultPlan(0, kill_combiner_at_pass=3)
    step = GatedStep()
    s = PCScheduler(step, max_batch=4, n_shards=2, fault_plan=plan)
    f0 = s.submit_async(0)
    assert step.entered.wait(10)            # pass 1 holds the device
    futs = publish_together(s, [1, 2, 3])   # pass 2: the waiting batch
    assert wait_waiting(s, 3) == [[1, 2, 3]]
    futs += publish_together(s, [4, 5, 6])  # pass 3 dies; pass 4 replays
    assert wait_waiting(s, 6) == [[1, 2, 3, 4], [5, 6]]
    step.release()
    assert [f.result(timeout=30) for f in [f0] + futs] == [
        2 * i for i in range(7)]
    s.close()
    served = [x for rows in step.received for x in rows]
    assert Counter(served) == Counter(range(7))   # zero lost, zero dup
    assert step.received == [[0], [1, 2, 3, 4], [5, 6]]
    assert s.takeovers == 1 and s.fault_counters()["combiner_kills"] == 1
    assert s.topped_up == 1


def test_scheduler_guarded_pq_survives_dispatch_faults():
    plan = FaultPlan(1, dispatch_fail_rate=0.9, max_dispatch_failures=6)
    with PCScheduler(lambda xs: [x + 1 for x in xs], max_batch=4,
                     n_shards=2, tier="device", fault_plan=plan) as s:
        futs = [s.submit_async(i, deadline=float((i * 7) % 5))
                for i in range(30)]
        outs = [f.result(timeout=60) for f in futs]
    assert outs == [i + 1 for i in range(30)]
    c = s.fault_counters()
    assert c["dispatch_failures"] >= 1 and c["restores"] >= 1
    assert "breaker_state" in c

def test_scheduler_close_waits_for_inflight_step():
    """Regression (ISSUE 7 satellite b): close() while a slow device step
    is mid-flight must let the step finish and resolve its future with
    the RESULT — not sweep it into the doomed-futures RuntimeError."""
    release = threading.Event()
    entered = threading.Event()

    def slow_step(xs):
        entered.set()
        release.wait(timeout=10)
        return [x + 1 for x in xs]

    s = PCScheduler(slow_step, max_batch=8, n_shards=2)
    f = s.submit_async(41, deadline=0.0)
    assert entered.wait(timeout=10)
    closer = threading.Thread(target=s.close)
    closer.start()
    time.sleep(0.05)                   # close() is now waiting on workers
    release.set()
    closer.join(timeout=10)
    assert not closer.is_alive()
    assert f.result(timeout=1) == 42

def test_scheduler_submit_after_close_raises():
    s = PCScheduler(lambda xs: xs, n_shards=2)
    s.close()
    with pytest.raises(RuntimeError):
        s.submit_async(1)


# ---------------------------------------------------------------------------
# Deterministic fault-mode differential fuzz (satellite c; the hypothesis
# state-machine variants live in test_differential.py behind the fuzz
# marker — these loops need no extra dependency and run in tier-1)
# ---------------------------------------------------------------------------
def _fuzz_vs_spec_oracle(name, ds, rng, iters, **drive_kw):
    from conformance import run_differential
    from repro.core import substrate

    substrate.load_builtins()
    spec = substrate.get(name)
    run_differential(ds, spec.make_host(ds), spec, rng, iters, **drive_kw)

def test_faulty_pq_oracle_equivalent(rng):
    plan = FaultPlan(2, dispatch_fail_rate=0.2)
    pq = ShardedBatchedPQ(512, c_max=8, n_shards=2, fault_plan=plan,
                          guard=DispatchGuard(plan, **_NOSLEEP))
    _fuzz_vs_spec_oracle("pq", pq, rng, 40)
    assert plan.counters.dispatch_failures >= 1
    assert plan.counters.restores == plan.counters.retries

def test_faulty_map_oracle_equivalent(rng):
    plan = FaultPlan(3, dispatch_fail_rate=0.2)
    m = ShardedMap(128, c_max=8, n_shards=4, key_range=(0.0, 100.0),
                   fault_plan=plan, guard=DispatchGuard(plan, **_NOSLEEP))
    _fuzz_vs_spec_oracle("map", m, rng, 30)
    assert plan.counters.dispatch_failures >= 1

def test_faulty_graph_oracle_equivalent(rng):
    plan = FaultPlan(4, dispatch_fail_rate=0.2)
    g = DeviceGraph(24, edge_capacity=256, c_max=8, n_shards=2,
                    fault_plan=plan, guard=DispatchGuard(plan, **_NOSLEEP))
    _fuzz_vs_spec_oracle("graph", g, rng, 40, ctx={"n": 24})
    assert plan.counters.dispatch_failures >= 1
