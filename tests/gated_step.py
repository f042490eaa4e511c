"""Deterministic helpers for ``PCScheduler``'s waiting batch: a step_fn
that holds the device thread until released, atomic publication, and a
wait on what the combiner has handed off — no sleeps decide an outcome."""
import threading
from typing import Any, List


class GatedStep:
    """``step_fn`` whose calls block until :meth:`release`.  Keeps
    each call's list as received beside a copy taken on entry."""

    def __init__(self, fn=lambda x: x * 2):
        self.fn = fn
        self.entered = threading.Event()
        self.gate = threading.Event()
        self.received: List[List[Any]] = []
        self.snapshots: List[List[Any]] = []

    def __call__(self, rows):
        self.received.append(rows)
        self.snapshots.append(list(rows))
        self.entered.set()
        assert self.gate.wait(timeout=30), "gate never released"
        return [self.fn(r) for r in rows]

    def release(self) -> None:
        self.gate.set()


def publish_together(sch, items):
    """Submit ``items`` (deadline = item) so that one ordering pass
    collects them all: the scheduler's lock is reentrant, and its
    combiner cannot collect while this thread holds it."""
    with sch._cond:
        return [sch.submit_async(i, deadline=float(i)) for i in items]


def wait_waiting(sch, n: int, timeout: float = 30.0) -> List[List[Any]]:
    """Wait until the batches the device thread has not taken hold ``n``
    requests; returns their inputs, batch by batch."""
    def inputs():
        return [[e.req.inputs for e in b] for b in sch._waiting]

    with sch._ready:
        assert sch._ready.wait_for(
            lambda: sum(map(len, sch._waiting)) == n, timeout), inputs()
        return inputs()
