"""Data pipeline, checkpointing, serving scheduler, watchdog."""
import json
import os
import shutil
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, latest_step, \
    load_checkpoint, save_checkpoint
from repro.data import make_pipeline
from repro.launch.train import StragglerWatchdog
from repro.serving import PCScheduler, SerialScheduler

from gated_step import GatedStep, publish_together, wait_waiting


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_pipeline_deterministic_and_stateless():
    p = make_pipeline(500, 64, 8, seed=1)
    b1 = p.global_batch(7)
    b2 = p.global_batch(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = p.global_batch(8)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_pipeline_host_sharding_covers_global():
    full = make_pipeline(500, 32, 12, seed=2)
    shards = [make_pipeline(500, 32, 12, seed=2, n_hosts=3, host_id=h)
              for h in range(3)]
    g = full.global_batch(3)
    got = np.concatenate([s[3]["tokens"] for s in shards], axis=0)
    np.testing.assert_array_equal(g["tokens"], got)


def test_pipeline_elastic_resharding_identical_stream():
    """Changing host count must not change the global token stream."""
    a = make_pipeline(500, 32, 8, seed=3, n_hosts=2, host_id=0)
    b = make_pipeline(500, 32, 8, seed=3, n_hosts=4, host_id=0)
    ga = np.concatenate(
        [make_pipeline(500, 32, 8, seed=3, n_hosts=2, host_id=h)[5]["tokens"]
         for h in range(2)])
    gb = np.concatenate(
        [make_pipeline(500, 32, 8, seed=3, n_hosts=4, host_id=h)[5]["tokens"]
         for h in range(4)])
    np.testing.assert_array_equal(ga, gb)


def test_pipeline_labels_shifted_and_masked():
    p = make_pipeline(100, 32, 2, seed=0)
    b = p.global_batch(0)
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])
    assert b["mask"].min() >= 0 and b["mask"].max() <= 1
    assert (b["mask"][b["labels"] == 0] == 0).all()


def test_pipeline_prefetch(tmp_path):
    p = make_pipeline(100, 16, 2, seed=0)
    it = p.prefetch(4, depth=2)
    first = next(it)
    np.testing.assert_array_equal(first["tokens"], p[4]["tokens"])
    second = next(it)
    np.testing.assert_array_equal(second["tokens"], p[5]["tokens"])


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------
def _tree():
    return {"w": jnp.arange(12, dtype=jnp.bfloat16).reshape(3, 4),
            "o": {"m": jnp.ones((5,), jnp.float32),
                  "step": jnp.int32(7)}}


def test_checkpoint_roundtrip_bf16(tmp_path):
    tree = _tree()
    save_checkpoint(str(tmp_path), 3, tree, extra={"k": 1})
    restored, extra = load_checkpoint(
        str(tmp_path), 3, jax.tree.map(jnp.zeros_like, tree))
    assert extra == {"k": 1}
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        assert np.asarray(a).dtype == np.asarray(b).dtype


def test_checkpoint_atomicity_partial_write_ignored(tmp_path):
    tree = _tree()
    save_checkpoint(str(tmp_path), 1, tree)
    # simulate a crash mid-write: a bare tmp dir and a corrupt final dir
    os.makedirs(tmp_path / "step_0000000002.tmp")
    os.makedirs(tmp_path / "step_0000000003")
    (tmp_path / "step_0000000003" / "manifest.json").write_text("{broken")
    assert latest_step(str(tmp_path)) == 1
    cm = CheckpointManager(str(tmp_path))
    assert not (tmp_path / "step_0000000002.tmp").exists()  # GC'd


def test_checkpoint_manager_keep_k_and_async(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    tree = _tree()
    for s in (1, 2, 3, 4):
        cm.save(s, tree, blocking=False)
    cm.wait()
    steps = sorted(int(d[5:]) for d in os.listdir(tmp_path)
                   if d.startswith("step_"))
    assert steps == [3, 4]
    got = cm.restore_latest(jax.tree.map(jnp.zeros_like, tree))
    assert got[0] == 4


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"w": jnp.zeros((2, 2))})
    with pytest.raises(ValueError):
        load_checkpoint(str(tmp_path), 1, {"w": jnp.zeros((3, 3))})


# ---------------------------------------------------------------------------
# serving scheduler
# ---------------------------------------------------------------------------
def test_pc_scheduler_combines_and_is_correct():
    calls = []

    def step_fn(rows):
        calls.append(len(rows))
        time.sleep(0.001)
        return [r * 10 for r in rows]

    sch = PCScheduler(step_fn, max_batch=8)
    outs = {}

    def sess(tid):
        outs[tid] = [sch.submit(tid * 100 + i, deadline=i)
                     for i in range(15)]

    ts = [threading.Thread(target=sess, args=(t,)) for t in range(5)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    for tid, res in outs.items():
        assert res == [(tid * 100 + i) * 10 for i in range(15)]
    assert max(calls) > 1                  # combining actually happened
    assert sum(calls) == 75


def test_pc_scheduler_respects_max_batch():
    def step_fn(rows):
        assert len(rows) <= 4
        return rows

    sch = PCScheduler(step_fn, max_batch=4)

    def sess(tid):
        for i in range(10):
            sch.submit(i)

    ts = [threading.Thread(target=sess, args=(t,)) for t in range(8)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert all(b <= 4 for b in sch.batches)


def test_pq_ordering_prefers_earlier_deadlines():
    """When more requests are pending than fit, the PQ picks the smallest
    deadlines first."""
    order = []
    gate = threading.Event()

    def step_fn(rows):
        order.extend(rows)
        time.sleep(0.005)
        return rows

    sch = PCScheduler(step_fn, max_batch=2, use_pq=True)
    # 6 concurrent sessions with distinct deadlines
    def sess(tid):
        gate.wait()
        sch.submit(tid, deadline=float(tid))

    ts = [threading.Thread(target=sess, args=(t,)) for t in range(6)]
    [t.start() for t in ts]
    gate.set()
    [t.join() for t in ts]
    assert sorted(order) == list(range(6))


def test_submit_async_returns_futures_and_combines():
    """submit_async is non-blocking, returns futures; flooding the
    scheduler with concurrent async submissions yields mean_batch > 1."""
    from concurrent.futures import Future

    def step_fn(rows):
        time.sleep(0.002)              # device step in flight
        return [r * 10 for r in rows]

    sch = PCScheduler(step_fn, max_batch=8)
    gate = threading.Event()
    futs = {}

    def sess(tid):
        gate.wait()
        futs[tid] = [sch.submit_async(tid * 100 + i, deadline=float(i))
                     for i in range(10)]

    ts = [threading.Thread(target=sess, args=(t,)) for t in range(4)]
    [t.start() for t in ts]
    gate.set()
    [t.join() for t in ts]
    for tid, fs in futs.items():
        assert all(isinstance(f, Future) for f in fs)
        assert [f.result(timeout=30) for f in fs] == [
            (tid * 100 + i) * 10 for i in range(10)]
    assert sum(sch.batches) == 40
    assert sch.mean_batch > 1           # combining under concurrent load
    sch.close()


def test_async_scheduler_drains_on_close():
    sch = PCScheduler(lambda rows: [r + 1 for r in rows], max_batch=4)
    fs = [sch.submit_async(i) for i in range(13)]
    sch.close()                         # must serve everything first
    assert [f.result(timeout=5) for f in fs] == [i + 1 for i in range(13)]
    with pytest.raises(RuntimeError):
        sch.submit_async(0)


def test_waiting_batch_is_topped_up_to_max_batch():
    """Requests chosen while a batch waits join it up to max_batch; the
    rest open the next batch."""
    step = GatedStep()
    sch = PCScheduler(step, max_batch=4)
    f0 = sch.submit_async(0)
    assert step.entered.wait(10)            # pass 1 holds the device
    futs = publish_together(sch, [1, 2])
    assert wait_waiting(sch, 2) == [[1, 2]]
    futs += publish_together(sch, [3, 4, 5])
    assert wait_waiting(sch, 5) == [[1, 2, 3, 4], [5]]
    step.release()
    assert [f.result(timeout=10) for f in [f0] + futs] == [0, 2, 4, 6, 8,
                                                           10]
    sch.close()
    assert step.snapshots == [[0], [1, 2, 3, 4], [5]]
    assert sch.batches == [1, 4, 1] and sch.passes == 3
    assert sch.topped_up == 2               # 3 and 4 joined [1, 2]


def test_batch_taken_by_the_device_never_changes():
    """What a step_fn call received stays as it was: requests chosen
    while it runs wait for the next batch instead of joining it."""
    step = GatedStep()
    sch = PCScheduler(step, max_batch=8)
    f0 = sch.submit_async(0)
    assert step.entered.wait(10)
    futs = publish_together(sch, [1, 2, 3])
    assert wait_waiting(sch, 3) == [[1, 2, 3]]
    step.release()
    assert [f.result(timeout=10) for f in [f0] + futs] == [0, 2, 4, 6]
    sch.close()
    assert step.received == step.snapshots == [[0], [1, 2, 3]]


@pytest.mark.parametrize("use_pq", [True, False])
def test_batches_add_up_to_the_operations_served(use_pq):
    """Closed loop: each batch is recorded once, ≤ max_batch, in the
    order the step_fn saw them, and together they hold every operation."""
    sizes = []

    def step_fn(rows):
        sizes.append(len(rows))
        return [r + 1 for r in rows]

    sch = PCScheduler(step_fn, max_batch=8, use_pq=use_pq)
    n_sess, per_sess = 3 * (os.cpu_count() or 4), 20
    answers = {}

    def sess(tid):
        answers[tid] = [sch.submit(tid * 1000 + i, deadline=float(i))
                        for i in range(per_sess)]

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)             # interleave the threads hard
    try:
        ts = [threading.Thread(target=sess, args=(t,))
              for t in range(n_sess)]
        [t.start() for t in ts]
        [t.join(60) for t in ts]
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in ts)
    sch.close()
    assert answers == {t: [t * 1000 + i + 1 for i in range(per_sess)]
                       for t in range(n_sess)}
    assert sum(sch.batches) == n_sess * per_sess
    assert sch.batches == sizes and sch.passes == len(sizes)
    assert all(1 <= b <= 8 for b in sch.batches)
    assert sch.topped_up <= n_sess * per_sess


def test_close_serves_the_waiting_batch():
    """close() lets the device thread serve every waiting batch before
    its sweep fails whatever is left."""
    step = GatedStep()
    sch = PCScheduler(step, max_batch=4)
    f0 = sch.submit_async(0)
    assert step.entered.wait(10)
    futs = publish_together(sch, [1, 2, 3])
    assert wait_waiting(sch, 3) == [[1, 2, 3]]
    closer = threading.Thread(target=sch.close)
    closer.start()
    with sch._ready:                        # close() stopped the device
        assert sch._ready.wait_for(lambda: sch._device_stop, 10)
    step.release()
    closer.join(10)
    assert not closer.is_alive()
    assert [f.result(timeout=1) for f in [f0] + futs] == [0, 2, 4, 6]
    assert sch.batches == [1, 3] and not sch._waiting


def test_waiting_batches_keep_the_backpressure():
    """The combiner orders only while at most one batch waits: keys it
    has not chosen stay in the deadline PQ, where a later, more urgent
    request still overtakes them."""
    step = GatedStep()
    sch = PCScheduler(step, max_batch=2, rounds_cap=1, n_shards=2,
                      pq_capacity=1024)
    order, waiting_at_order = sch._order, []

    def watched(new):
        with sch._cond:
            waiting_at_order.append(len(sch._waiting))
        return order(new)

    sch._order = watched
    f0 = sch.submit_async(0)
    assert step.entered.wait(10)
    futs = publish_together(sch, [1, 2])
    assert wait_waiting(sch, 2) == [[1, 2]]
    # budget 2: 3 and 4 are chosen, 5..7 go to the PQ and stay there
    futs += publish_together(sch, [3, 4, 5, 6, 7])
    assert wait_waiting(sch, 4) == [[1, 2], [3, 4]]
    futs += publish_together(sch, [0.5])
    step.release()
    assert [f.result(timeout=30) for f in [f0] + futs] == [
        0, 2, 4, 6, 8, 10, 12, 14, 1.0]
    sch.close()
    assert step.received == [[0], [1, 2], [3, 4], [0.5, 5], [6, 7]]
    assert max(waiting_at_order) <= 1


def test_async_scheduler_propagates_step_errors():
    def boom(rows):
        raise ValueError("step failed")

    sch = PCScheduler(boom, max_batch=4, use_pq=False)
    f = sch.submit_async(1)
    with pytest.raises(ValueError, match="step failed"):
        f.result(timeout=5)
    sch.close()


def test_persistent_pq_table_no_reinsert_churn():
    """Unchosen requests stay in the device PQ across passes: total PQ
    insert traffic equals the number of requests, not O(pending·passes)."""
    inserted = []

    sch = PCScheduler(lambda rows: (time.sleep(0.002), rows)[1],
                      max_batch=2)
    orig_apply = sch._pq.apply
    orig_rounds = sch._pq.apply_rounds_async

    def counting_apply(extracts, inserts):
        inserted.extend(inserts)
        return orig_apply(extracts, inserts)

    def counting_rounds(rounds):
        for _ne, ins in rounds:
            inserted.extend(ins)
        return orig_rounds(rounds)

    sch._pq.apply = counting_apply
    sch._pq.apply_rounds_async = counting_rounds
    gate = threading.Event()

    def sess(tid):
        gate.wait()
        sch.submit(tid, deadline=float(tid))

    ts = [threading.Thread(target=sess, args=(t,)) for t in range(8)]
    [t.start() for t in ts]
    gate.set()
    [t.join() for t in ts]
    sch.close()
    # each key is published at most once (never re-inserted on later
    # passes); single-request passes may bypass the device PQ entirely
    assert len(inserted) <= 8
    assert len(inserted) == len(set(inserted))


def test_extreme_deadlines_round_trip_through_device_pq():
    """Subnormal deadlines (flushed to 0 on device) and ±inf deadlines
    (clamped to the finite f32 range) must still resolve to their
    requests instead of killing the combiner with a table miss."""
    sch = PCScheduler(lambda rows: [r * 2 for r in rows], max_batch=4)
    deadlines = [1e-39, 0.0, float("inf"), 5.0, float("-inf")]
    futs = [sch.submit_async(i, deadline=d)
            for i, d in enumerate(deadlines)]
    assert [f.result(timeout=10) for f in futs] == [0, 2, 4, 6, 8]
    sch.close()


def test_short_step_fn_output_fails_tail_futures():
    """A step_fn that returns fewer outputs than requests must error the
    stranded futures instead of hanging them forever."""
    sch = PCScheduler(lambda rows: rows[:-1], max_batch=4, use_pq=False)
    f = sch.submit_async(7)
    with pytest.raises(RuntimeError, match="0 outputs for a batch of 1"):
        f.result(timeout=10)
    sch.close()


def test_cancelled_future_does_not_poison_batch():
    """Cancelling one request must not steal the results of the others
    batched with it."""
    release = threading.Event()

    def step_fn(rows):
        release.wait(10)
        return [r * 10 for r in rows]

    sch = PCScheduler(step_fn, max_batch=4, use_pq=False, pipeline=False)
    f1 = sch.submit_async(1)
    f2 = sch.submit_async(2)
    f3 = sch.submit_async(3)
    assert f2.cancel() or f2.done()     # cancel while pending/queued
    release.set()
    assert f1.result(timeout=10) == 10
    assert f3.result(timeout=10) == 30  # unaffected by f2's cancellation
    sch.close()


def test_nonfinite_init_values_rejected():
    from repro.core import BatchedPriorityQueue, ShardedBatchedPQ
    for cls, kw in ((ShardedBatchedPQ, dict(n_shards=2)),
                    (BatchedPriorityQueue, {})):
        with pytest.raises(ValueError):
            cls(256, c_max=4, values=[1.0, float("inf")], **kw)


def test_nan_deadline_rejected_at_submit():
    sch = PCScheduler(lambda rows: rows, max_batch=4)
    with pytest.raises(ValueError, match="NaN"):
        sch.submit_async(1, deadline=float("nan"))
    assert sch.submit(5, deadline=0.0) == 5     # scheduler unharmed
    sch.close()


def test_ordering_failure_fails_futures_not_silence():
    """An exception on the ordering path must surface on the futures and
    leave the scheduler alive for later requests."""
    started = threading.Event()

    def slow(rows):
        started.set()
        time.sleep(0.15)
        return rows

    sch = PCScheduler(slow, max_batch=4, pipeline=False, rounds_cap=1)

    def boom(rounds):
        raise RuntimeError("device fell over")

    orig_pq = sch._pq
    f0 = sch.submit_async(0, deadline=0.0)   # single → eliminated, no PQ
    assert started.wait(10)
    sch._pq.apply_rounds_async = boom
    # six requests accumulate while the inline step sleeps → the next
    # pass overflows the elimination budget (rounds_cap·max_batch = 4)
    # and must publish the leftovers through the (broken) device PQ
    futs = [sch.submit_async(i, deadline=float(i)) for i in range(1, 7)]
    for f in futs:
        with pytest.raises(RuntimeError, match="device fell over"):
            f.result(timeout=10)
    assert f0.result(timeout=10) == 0
    assert sch._pq is not orig_pq          # PQ rebuilt after the abort
    assert sch.submit(21, deadline=0.0) == 21   # still serving
    sch.close()


def test_serial_scheduler_baseline():
    sch = SerialScheduler(lambda rows: [r + 1 for r in rows])
    assert sch.submit(41) == 42
    assert all(b == 1 for b in sch.batches)


# ---------------------------------------------------------------------------
# straggler watchdog
# ---------------------------------------------------------------------------
def test_watchdog_flags_outliers():
    wd = StragglerWatchdog(factor=3.0, warmup=3)
    for _ in range(10):
        assert not wd.check(0.1)
    assert wd.check(1.0)
    assert not wd.check(0.11)


# ---------------------------------------------------------------------------
# scheduler shutdown semantics (ISSUE 5 satellite)
# ---------------------------------------------------------------------------
def test_close_races_late_submitter_no_hang():
    """A submit racing close() either raises RuntimeError immediately or
    returns a future that RESOLVES (served or failed) — no caller may
    hang on a dead combiner loop, and every post-close submit raises."""
    for trial in range(3):
        sch = PCScheduler(lambda rows: [r + 1 for r in rows], max_batch=4)
        accepted, rejected = [], []
        stop = threading.Event()

        def late_submitter():
            i = 0
            while not stop.is_set() and i < 500:
                try:
                    accepted.append((i, sch.submit_async(i)))
                except RuntimeError:
                    rejected.append(i)
                i += 1

        t = threading.Thread(target=late_submitter)
        t.start()
        time.sleep(0.005 * (trial + 1))
        sch.close()
        stop.set()
        t.join(10)
        assert not t.is_alive()
        for i, f in accepted:
            try:
                assert f.result(timeout=5) == i + 1   # served on drain
            except RuntimeError:
                pass            # failed with the shutdown exception: fine
        with pytest.raises(RuntimeError):
            sch.submit_async(0)
        with pytest.raises(RuntimeError):
            sch.submit(0)


def test_submit_onto_dead_combiner_raises_not_enqueues():
    """If the combiner thread is gone, submit must raise immediately —
    enqueueing would strand the future forever."""
    sch = PCScheduler(lambda rows: rows, max_batch=4)
    sch.close()
    sch._closed = False          # simulate a dead loop without close()
    with pytest.raises(RuntimeError, match="closed"):
        sch.submit_async(1)


def test_close_fails_unserved_requests_instead_of_hanging():
    """Requests the workers can no longer serve are drained WITH an
    exception at close() — the caller gets RuntimeError, not a hang."""
    from repro.serving.scheduler import BatchRequest, _Entry
    from concurrent.futures import Future

    sch = PCScheduler(lambda rows: rows, max_batch=4, use_pq=False)
    sch.close()
    # a request stranded after the workers stopped (dead-loop scenario)
    ent = _Entry(BatchRequest(inputs=1), Future())
    sch._pending.append(ent)
    sch.close()                  # second close sweeps, not early-returns
    with pytest.raises(RuntimeError, match="closed before"):
        ent.future.result(timeout=5)


def test_pq_overflow_refusal_keeps_resident_requests():
    """ISSUE 5 overflow audit, scheduler side: a deadline-PQ occupancy
    refusal fails ONLY the flood's futures — resident requests keep
    their place (device PQ, table and lazy min-heap untouched thanks to
    the PQ-side atomic guard) and are served by later passes."""
    def slow_step(rows):
        time.sleep(0.1)
        return [r * 2 for r in rows]

    sch = PCScheduler(slow_step, max_batch=2, rounds_cap=1,
                      pq_capacity=8, n_shards=1, pipeline=False)
    f0 = sch.submit_async(0, deadline=0.0)
    time.sleep(0.02)             # let pass 1 start its slow step
    stage1 = [sch.submit_async(i, deadline=float(i))
              for i in range(1, 7)]      # 2 eliminated + 4 PQ residents
    time.sleep(0.12)             # pass 2 publishes the residents
    flood = [sch.submit_async(100 + i, deadline=100.0 + i)
             for i in range(12)]         # overflows the 8-slot shard
    failed = 0
    for i, f in enumerate(flood):
        try:
            # a flood entry that slipped into an earlier (legal) pass is
            # served normally; the rest fail with the refusal
            assert f.result(timeout=10) == (100 + i) * 2
        except ValueError as e:
            assert "capacity" in str(e)
            failed += 1
    assert failed > 0            # the refusal surfaced on flood futures
    assert f0.result(timeout=10) == 0
    for i, f in enumerate(stage1, start=1):
        assert f.result(timeout=10) == i * 2   # residents survived
    assert sch.submit(50, deadline=0.0) == 100  # still serving
    sch.close()
    assert sch._peek_resident() is None         # heap fully drained
