"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

The traced run writes its own host spans into the profiler's trace
(``jax.profiler.TraceAnnotation``, names prefixed ``cb:``), so device
events and host spans share one clock.  The reduction takes:

* the window: the ``cb:window`` span;
* busy time: the union of the device's operation intervals (line
  ``XLA Ops`` of each ``/device:TPU:<n>`` plane) clipped to the window;
* per-program device time: the durations of the program executions
  (line ``XLA Modules``) inside the window, by program name;
* idle gaps: the window less the busy union, each named by the host span
  that covered most of it.  At each instant the innermost span counts,
  by the rank in ``SPAN_RANK``; an instant no span covers is ``none``.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "cb:"
WINDOW = "window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# innermost last: a gap inside a fetch is the fetch's, not the pass's
SPAN_RANK = ("client.publish", "combiner.order", "executor.pass",
             "executor.update", "executor.read", "fetch")

Interval = Tuple[float, float]


@dataclass
class Trace:
    """What the reduction reads: host spans and, per device, operation
    intervals and program executions (all in ns on one clock)."""

    spans: List[Tuple[str, float, float]] = field(default_factory=list)
    ops: Dict[str, List[Interval]] = field(default_factory=dict)
    programs: Dict[str, List[Tuple[str, float, float]]] = \
        field(default_factory=dict)


@dataclass
class Summary:
    window: Interval
    window_s: float
    busy_s: float                     # mean over devices
    program_s: Dict[str, float]       # summed over devices
    program_n: Dict[str, int]
    gaps: List[Tuple[float, float, str]]   # (start, end, label), device 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def idle_by_label(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for s, e, lab in self.gaps:
            out[lab] = out.get(lab, 0.0) + (e - s) * 1e-9
        return out


def program_name(event_name: str) -> str:
    """``jit__apply_impl(1234)`` -> ``jit__apply_impl``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {found}")
    return found[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.ops[plane.name] = [(e.start_ns, e.end_ns)
                                          for e in line.events]
                elif line.name == MODULES_LINE:
                    tr.programs[plane.name] = [
                        (program_name(e.name), e.start_ns, e.end_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans.extend(
                    (e.name[len(SPAN_PREFIX):], e.start_ns, e.end_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return tr


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def complement(busy: Sequence[Interval], lo: float, hi: float
               ) -> List[Interval]:
    """``[lo, hi]`` less the sorted, disjoint ``busy`` intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def label_gaps(gaps: Sequence[Interval],
               spans: Sequence[Tuple[str, float, float]],
               rank: Sequence[str] = SPAN_RANK
               ) -> List[Tuple[float, float, str]]:
    """Name each gap by the innermost span over most of its length."""
    r = {name: i for i, name in enumerate(rank)}
    edges = []
    for name, s, e in spans:
        if name in r and e > s:
            edges.append((s, 1, r[name]))
            edges.append((e, -1, r[name]))
    edges.sort()
    active = [0] * len(rank)
    out = []
    j = 0
    for g0, g1 in gaps:
        # advance the span edges up to the gap's start
        while j < len(edges) and edges[j][0] <= g0:
            active[edges[j][2]] += edges[j][1]
            j += 1
        share: Dict[str, float] = {}
        t, k = g0, j
        local = list(active)
        while True:
            nxt = edges[k][0] if k < len(edges) and edges[k][0] < g1 else g1
            top = max((i for i, c in enumerate(local) if c > 0),
                      default=None)
            lab = rank[top] if top is not None else "none"
            share[lab] = share.get(lab, 0.0) + (nxt - t)
            if nxt >= g1:
                break
            while k < len(edges) and edges[k][0] == nxt:
                local[edges[k][2]] += edges[k][1]
                k += 1
            t = nxt
        out.append((g0, g1, max(share, key=share.get)))
    return out


def reduce(tr: Trace) -> Summary:
    windows = [(s, e) for n, s, e in tr.spans if n == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {SPAN_PREFIX}{WINDOW} span, "
                           f"found {len(windows)}")
    (w0, w1), = windows
    devices = sorted(tr.ops) or sorted(tr.programs)
    busy_ns, gaps = 0.0, []
    for i, dev in enumerate(devices):
        busy = union(clip(tr.ops.get(dev, []), w0, w1))
        busy_ns += sum(e - s for s, e in busy)
        if i == 0:
            gaps = label_gaps(complement(busy, w0, w1), tr.spans)
    program_s: Dict[str, float] = {}
    program_n: Dict[str, int] = {}
    for dev in devices:
        for name, s, e in tr.programs.get(dev, []):
            if s >= w0 and s < w1:
                program_s[name] = program_s.get(name, 0.0) + (e - s) * 1e-9
                program_n[name] = program_n.get(name, 0) + 1
    return Summary(window=(w0, w1), window_s=(w1 - w0) * 1e-9,
                   busy_s=busy_ns * 1e-9 / max(1, len(devices)),
                   program_s=program_s, program_n=program_n, gaps=gaps)


def summarize(log_dir: str) -> Optional[Summary]:
    return reduce(load(find_xplane(log_dir)))
