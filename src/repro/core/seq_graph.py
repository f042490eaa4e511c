"""Plain dynamic-connectivity reference and the Graph500 generator.

``SeqGraph`` answers ``insert``/``delete``/``connected`` one operation at a
time with numpy alone, independently of the device graph and of its label
propagation (``kernels/label_prop``):

* the initial edges live in a CSR adjacency (both directions, sorted by
  source then neighbour) with a liveness bit an entry; an inserted edge
  that the CSR lacks goes to a side table of neighbour sets;
* ``comp`` holds a component id a vertex, found at load by breadth-first
  search; an insert that joins two components relabels the smaller one;
* a delete that removes a present edge runs a bidirectional breadth-first
  search between its endpoints, always growing the smaller frontier; if
  one side runs out before they meet, that side is a new component.

``step`` applies a combined batch the way the served executor does: its
updates in arrival order, then its reads.

``kronecker_edges`` is the Graph500 specification's generator ("Graph
Generation"): R-MAT with initiator A=0.57, B=0.19, C=0.19, D=0.05, a random
vertex permutation and a shuffled edge list, duplicates and self-loops kept.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

INITIATOR = (0.57, 0.19, 0.19, 0.05)


def kronecker_edges(scale: int, m: int, rng: np.random.Generator,
                    perm: Optional[np.ndarray] = None) -> np.ndarray:
    """``(m, 2)`` int64 Kronecker edges on ``2**scale`` vertices, in
    shuffled order.  Each level draws two float32 uniforms an edge: the
    row bit falls with probability A+B, the column bit with C/(C+D) or
    A/(A+B) by the row bit.  ``perm`` relabels the vertices (default: a
    random permutation from ``rng``)."""
    a, b, c, _d = INITIATOR
    ab = a + b
    c_norm, a_norm = c / (1.0 - ab), a / ab
    i = np.zeros(m, np.int64)
    j = np.zeros(m, np.int64)
    for level in range(scale):
        ii = rng.random(m, dtype=np.float32) > ab
        jj = rng.random(m, dtype=np.float32) > np.where(ii, c_norm,
                                                        a_norm)
        i |= ii.astype(np.int64) << level
        j |= jj.astype(np.int64) << level
    if perm is None:
        perm = rng.permutation(1 << scale)
    order = rng.permutation(m)
    return np.stack([perm[i[order]], perm[j[order]]], axis=1).astype(
        np.int64)


def canonical_edges(edges, n: int) -> np.ndarray:
    """The distinct non-loop edges as sorted ``(u < v)`` int64 rows."""
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    u, v = e.min(axis=1), e.max(axis=1)
    keep = u != v
    code = np.unique(u[keep] * n + v[keep])
    return np.stack([code // n, code % n], axis=1)


class SeqGraph:
    """Exact dynamic connectivity on vertices ``[0, n)``: ``insert``
    answers "was absent", ``delete`` "was present", a self-loop False to
    both (never stored), ``connected(u, u)`` True."""

    def __init__(self, n: int, edges=()):
        self.n = int(n)
        e = canonical_edges(edges, self.n)
        code = np.sort(np.concatenate([e[:, 0] * self.n + e[:, 1],
                                       e[:, 1] * self.n + e[:, 0]]))
        self.nbr = (code % self.n).astype(np.int64)
        self.indptr = np.zeros(self.n + 1, np.int64)
        np.cumsum(np.bincount(code // self.n, minlength=self.n),
                  out=self.indptr[1:])
        self.alive = np.ones(self.nbr.size, bool)
        self.extra: Dict[int, Set[int]] = {}
        self._has_extra = np.zeros(self.n, bool)
        self._mark = np.zeros(self.n, np.int64)
        self._stamp = 0
        self.comp = np.arange(self.n, dtype=np.int64)
        self._next = self.n                   # fresh component ids
        seen = np.diff(self.indptr) == 0      # isolated: their own comp
        for r in range(self.n):
            if not seen[r]:
                members = self._search(r)
                seen[members] = True
                self.comp[members] = r

    # -- adjacency ----------------------------------------------------------
    def _csr_pos(self, u: int, v: int) -> int:
        lo, hi = int(self.indptr[u]), int(self.indptr[u + 1])
        k = lo + int(np.searchsorted(self.nbr[lo:hi], v))
        return k if k < hi and int(self.nbr[k]) == v else -1

    def _neighbours(self, front: np.ndarray) -> np.ndarray:
        """Every live neighbour of the ``front`` vertices (repeats kept)."""
        starts = self.indptr[front]
        lens = self.indptr[front + 1] - starts
        total = int(lens.sum())
        out = np.zeros(0, np.int64)
        if total:
            idx = (np.arange(total)
                   + np.repeat(starts - (np.cumsum(lens) - lens), lens))
            out = self.nbr[idx[self.alive[idx]]]
        xs = front[self._has_extra[front]]
        if xs.size:
            more = [w for x in xs.tolist() for w in self.extra.get(x, ())]
            out = np.concatenate([out, np.asarray(more, np.int64)])
        return out

    def _present(self, u: int, v: int) -> bool:
        k = self._csr_pos(u, v)
        if k >= 0:
            return bool(self.alive[k])
        return v in self.extra.get(u, ())

    def _set(self, u: int, v: int, live: bool) -> None:
        k = self._csr_pos(u, v)
        if k >= 0:
            self.alive[k] = live
            self.alive[self._csr_pos(v, u)] = live
            return
        for x, y in ((u, v), (v, u)):
            if live:
                self.extra.setdefault(x, set()).add(y)
                self._has_extra[x] = True
            else:
                self.extra[x].discard(y)

    # -- searches -----------------------------------------------------------
    def _new_stamp(self) -> int:
        self._stamp += 1
        return self._stamp

    def _search(self, r: int) -> np.ndarray:
        """The vertices reachable from ``r``."""
        s = self._new_stamp()
        self._mark[r] = s
        front = np.asarray([r], np.int64)
        seen = [front]
        while front.size:
            nb = self._neighbours(front)
            front = np.unique(nb[self._mark[nb] != s])
            self._mark[front] = s
            seen.append(front)
        return np.concatenate(seen)

    def _split_side(self, u: int, v: int) -> Optional[np.ndarray]:
        """None if ``u`` and ``v`` are still joined, else the vertices of
        the side whose search ran out first."""
        stamps = (self._new_stamp(), self._new_stamp())
        self._mark[u], self._mark[v] = stamps
        fronts = [np.asarray([u], np.int64), np.asarray([v], np.int64)]
        seen: List[List[np.ndarray]] = [[fronts[0]], [fronts[1]]]
        while True:
            k = 0 if fronts[0].size <= fronts[1].size else 1
            nb = self._neighbours(fronts[k])
            if np.any(self._mark[nb] == stamps[1 - k]):
                return None
            nb = np.unique(nb[self._mark[nb] != stamps[k]])
            if not nb.size:
                return np.concatenate(seen[k])
            self._mark[nb] = stamps[k]
            seen[k].append(nb)
            fronts[k] = nb

    # -- operations ---------------------------------------------------------
    def insert(self, u: int, v: int) -> bool:
        if u == v or self._present(u, v):
            return False
        self._set(u, v, True)
        cu, cv = self.comp[u], self.comp[v]
        if cu != cv:
            in_u, in_v = self.comp == cu, self.comp == cv
            if in_u.sum() < in_v.sum():
                self.comp[in_u] = cv
            else:
                self.comp[in_v] = cu
        return True

    def delete(self, u: int, v: int) -> bool:
        if u == v or not self._present(u, v):
            return False
        self._set(u, v, False)
        side = self._split_side(u, v)
        if side is not None:
            self.comp[side] = self._next
            self._next += 1
        return True

    def connected(self, u: int, v: int) -> bool:
        return u == v or bool(self.comp[u] == self.comp[v])

    def step(self, methods: Sequence[str],
             inputs: Sequence[Tuple[int, int]]) -> List[Any]:
        """One combined batch: updates in arrival order, then reads."""
        out: List[Any] = [None] * len(methods)
        for i, (m, (u, v)) in enumerate(zip(methods, inputs)):
            if m == "insert":
                out[i] = self.insert(int(u), int(v))
            elif m == "delete":
                out[i] = self.delete(int(u), int(v))
            elif m != "connected":
                raise ValueError(f"unknown operation {m!r}")
        for i, (m, (u, v)) in enumerate(zip(methods, inputs)):
            if m == "connected":
                out[i] = self.connected(int(u), int(v))
        return out

    def edges(self) -> np.ndarray:
        """The live edges, sorted ``(u < v)`` rows."""
        src = np.repeat(np.arange(self.n, dtype=np.int64),
                        np.diff(self.indptr))
        keep = self.alive & (src < self.nbr)
        code = src[keep] * self.n + self.nbr[keep]
        more = [x * self.n + y for x, ys in self.extra.items() for y in ys
                if x < y]
        if more:
            code = np.sort(np.concatenate([code, np.asarray(more,
                                                            np.int64)]))
        return np.stack([code // self.n, code % self.n], axis=1)
