"""In-program spans and per-request stage stamps (DESIGN.md §19).

One module-level :data:`TRACER`, off by default.  Nothing turns it on
but a call: an operator or a benchmark calls ``TRACER.start()``, serves,
and takes the records with ``TRACER.stop()``.

Off, every site costs one attribute test: :meth:`Tracer.span` returns one
shared no-op context manager and :meth:`Tracer.stamp` returns 0.  On:

* ``span(name)`` keeps ``(name, t0_ns, t1_ns, cpu_ns)`` in a list of the
  calling thread (wall time from ``perf_counter_ns``; ``cpu_ns`` is the
  thread's own CPU time over the span, from ``thread_time_ns``);
* ``batch(...)`` keeps one record per executor pass: the ``step_fn``
  call's start and end, its operation count, and its operations' summed
  waits before it;
* ``anchor()`` writes one ``jax.profiler.TraceAnnotation`` named
  :data:`ANCHOR`, with its index as the stat ``n``, between two
  ``perf_counter_ns`` reads.  Where a profiler session records it, the
  annotation's start on the trace's clock less the first read is the
  offset from this module's clock to the trace's, to within the
  bracket between the two reads.

Records stay in memory; the anchors are the only profiler events this
module writes.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, List, Tuple

import jax

ANCHOR = "pc:anchor"

_NOOP = contextlib.nullcontext()
_clock = time.perf_counter_ns
_cpu = time.thread_time_ns

# (name, t0, t1, cpu), in ns
Span = Tuple[str, int, int, int]
# (t_start, t_end, n, pending, handoff): ns, but n counts operations
Batch = Tuple[int, int, int, int, int]


class _Span:
    __slots__ = ("_out", "_name", "_t0", "_c0")

    def __init__(self, out: List[Span], name: str):
        self._out, self._name = out, name

    def __enter__(self):
        # the wall interval encloses the CPU interval: cpu_ns <= wall
        self._t0 = _clock()
        self._c0 = _cpu()

    def __exit__(self, *exc):
        c1 = _cpu()
        self._out.append((self._name, self._t0, _clock(), c1 - self._c0))


class Tracer:
    """Spans per thread, pass records and clock anchors, kept between
    :meth:`start` and :meth:`stop`."""

    def __init__(self):
        self.on = False
        self._gen = 0                       # one per start(): stale lists
        self._local = threading.local()     # are left behind, not reused
        self._lock = threading.Lock()
        self._lists: List[List[Span]] = []
        self._batches: List[Batch] = []
        self._anchors: List[Tuple[int, int]] = []

    def start(self) -> None:
        with self._lock:
            self._gen += 1
            self._lists = []
            self._batches = []
            self._anchors = []
            self.on = True

    def stop(self) -> Dict[str, Any]:
        """Turn recording off and return what it kept: ``spans`` (every
        thread's, by start), ``batches`` (in pass order) and ``anchors``
        (``(before, after)`` by index)."""
        with self._lock:
            self.on = False
            spans = sorted((s for lst in self._lists for s in lst),
                           key=lambda s: s[1])
            return {"spans": spans, "batches": list(self._batches),
                    "anchors": list(self._anchors)}

    def _thread_list(self) -> List[Span]:
        loc = self._local
        if getattr(loc, "gen", None) != self._gen:
            with self._lock:
                loc.gen, loc.spans = self._gen, []
                self._lists.append(loc.spans)
        return loc.spans

    def span(self, name: str):
        if not self.on:
            return _NOOP
        return _Span(self._thread_list(), name)

    def stamp(self) -> int:
        """``perf_counter_ns()`` while on, else 0."""
        return _clock() if self.on else 0

    def batch(self, t_start: int, t_end: int, n: int, pending_ns: int,
              handoff_ns: int) -> None:
        """One executor pass: ``step_fn`` ran over ``[t_start, t_end]``
        for ``n`` operations, which waited ``pending_ns`` in all from
        publication to being chosen and ``handoff_ns`` from being chosen
        to the pass's start."""
        if self.on:
            self._batches.append((t_start, t_end, n, pending_ns,
                                  handoff_ns))

    def anchor(self) -> None:
        """One clock anchor (see the module's docstring).  Anchors are
        indexed in call order, so one thread takes them."""
        if not self.on:
            return
        n = len(self._anchors)
        before = _clock()
        with jax.profiler.TraceAnnotation(ANCHOR, n=n):
            pass
        self._anchors.append((before, _clock()))


TRACER = Tracer()
