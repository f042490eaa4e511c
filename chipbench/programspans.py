"""The program's own spans and stage stamps, read over one window.

``program`` is what ``repro.core.tracing.TRACER.stop()`` returns:
``spans`` ``(name, t0, t1, cpu)``, one ``batches`` record per executor
pass ``(t_start, t_end, n, pending, handoff)`` and ``anchors``
``(before, after)``, all in ns on the program's clock
(``perf_counter_ns``).  A pass belongs to the window when its
``step_fn`` call starts in it; a per-pass span belongs to the last pass
that started before it (the device thread runs one pass at a time, and
``scheduler.resolve`` follows its pass's ``step_fn`` call).

The clock anchors (``pc:anchor`` annotations, indexed by the stat
``n``) map the program's clock onto the device trace's: the offset is
the annotation's start less the ``before`` read of the anchor with the
narrowest bracket, which is also its error bound (Cristian's method).
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from tracereduce import union

ANCHOR = "pc:anchor"
WAIT = "scheduler.wait_batch"
RESOLVE = "scheduler.resolve"
MAP_SPANS = ("map.prep", "map.dispatch", "map.fetch", "map.convert")
# executor stage -> the spans summed into it, per pass
STAGES = {"prep": ("map.prep",), "dispatch": ("map.dispatch",),
          "fetch": ("map.fetch",), "resolve": ("map.convert", RESOLVE)}
# pure-Python spans: wall less thread CPU time is time without the GIL
# or without a core
OFFCPU = ("map.prep", "map.convert", RESOLVE)

Interval = Tuple[float, float]


@dataclass
class Pass:
    t_start: int
    t_end: int
    n: int
    pending: int
    handoff: int
    wall: Dict[str, int] = field(default_factory=dict)
    cpu: Dict[str, int] = field(default_factory=dict)


def window_passes(program: dict, t0: float, t1: float) -> List[Pass]:
    """Passes whose ``step_fn`` call starts in ``[t0, t1)``, each with
    its per-pass spans' wall and CPU time summed by name."""
    batches = sorted(program["batches"])
    starts = [b[0] for b in batches]
    passes = [Pass(*b) for b in batches]
    for name, s0, s1, cpu in program["spans"]:
        if name not in MAP_SPANS and name != RESOLVE:
            continue
        i = bisect.bisect_right(starts, s0) - 1
        if i < 0:
            continue
        p = passes[i]
        p.wall[name] = p.wall.get(name, 0) + (s1 - s0)
        p.cpu[name] = p.cpu.get(name, 0) + cpu
    return [p for p in passes if t0 <= p.t_start < t1]


def pending_ms(passes: Sequence[Pass]) -> Optional[float]:
    """Mean over operations of publication to being chosen."""
    n = sum(p.n for p in passes)
    return 1e-6 * sum(p.pending for p in passes) / n if n else None


def handoff_ms(passes: Sequence[Pass]) -> Optional[float]:
    """Mean over operations of being chosen to their pass's start."""
    n = sum(p.n for p in passes)
    return 1e-6 * sum(p.handoff for p in passes) / n if n else None


def stage_ms(passes: Sequence[Pass], stage: str) -> Optional[float]:
    """Mean wall time per pass of the spans of one executor stage."""
    if not passes:
        return None
    names = STAGES[stage]
    return 1e-6 * sum(p.wall.get(s, 0) for p in passes
                      for s in names) / len(passes)


def offcpu_share(passes: Sequence[Pass]) -> Optional[float]:
    """100 x (wall - thread CPU) / wall over the pure-Python spans."""
    wall = sum(p.wall.get(s, 0) for p in passes for s in OFFCPU)
    cpu = sum(p.cpu.get(s, 0) for p in passes for s in OFFCPU)
    return 100.0 * (wall - cpu) / wall if wall else None


# ---------------------------------------------------------------------------
# The clock anchor
# ---------------------------------------------------------------------------
def load_anchors(xplane: str) -> Dict[int, float]:
    """Start (trace clock, ns) of every ``pc:anchor`` in a trace, by
    index."""
    from jax.profiler import ProfileData

    out: Dict[int, float] = {}
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == ANCHOR:
                    out[int(dict(e.stats)["n"])] = e.start_ns
    return out


def clock_offset(anchors: Sequence[Tuple[int, int]],
                 starts: Dict[int, float]
                 ) -> Optional[Tuple[float, float]]:
    """``(offset, bracket)`` in ns: trace time = program time + offset,
    from the recorded anchor with the narrowest bracket."""
    got = [(anchors[n][1] - anchors[n][0], starts[n] - anchors[n][0])
           for n in starts if n < len(anchors)]
    if not got:
        return None
    bracket, offset = min(got)
    return offset, bracket


# ---------------------------------------------------------------------------
# Idle time by program span
# ---------------------------------------------------------------------------
def covered(gaps: Sequence[Interval], spans: Sequence[Interval]) -> float:
    """Length of the gaps that the spans cover (both on one clock)."""
    merged = union(spans)
    total, j = 0.0, 0
    for g0, g1 in sorted(gaps):
        while j < len(merged) and merged[j][1] <= g0:
            j += 1
        k = j
        while k < len(merged) and merged[k][0] < g1:
            total += min(g1, merged[k][1]) - max(g0, merged[k][0])
            k += 1
    return total


def _on_trace(program: dict, name: str, offset: float) -> List[Interval]:
    return [(s0 + offset, s1 + offset)
            for n, s0, s1, _c in program["spans"] if n == name]


def idle_waiting_share(program: dict, gaps: Sequence[Interval],
                       window: Interval, offset: float
                       ) -> Optional[float]:
    """Share (%) of the window in which device 0 is idle while the
    device thread waits for the combiner's next batch.  ``gaps`` and
    ``window`` are on the trace's clock."""
    w = window[1] - window[0]
    if w <= 0:
        return None
    return 100.0 * covered(gaps, _on_trace(program, WAIT, offset)) / w


def idle_by_span(program: dict, gaps: Sequence[Interval],
                 offset: float) -> Dict[str, float]:
    """Device 0's idle seconds, split by what the device thread was
    doing: each program span, ``step_fn`` outside any map span, or none
    of these."""
    total = sum(g1 - g0 for g0, g1 in gaps)
    out = {name: covered(gaps, _on_trace(program, name, offset))
           for name in MAP_SPANS + (RESOLVE, WAIT)}
    step = covered(gaps, [(b[0] + offset, b[1] + offset)
                          for b in program["batches"]])
    out["step_fn, outside map spans"] = step - sum(out[n]
                                                   for n in MAP_SPANS)
    out["none"] = total - step - out[RESOLVE] - out[WAIT]
    return {k: 1e-9 * v for k, v in out.items()}
