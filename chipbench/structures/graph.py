"""The dynamic graph (registry name ``graph``): preload, requests, plain
reference and final state.

The preload is a Graph500 Kronecker graph (specification, "Graph
Generation"): R-MAT with initiator (0.57, 0.19, 0.19, 0.05),
``edgefactor`` edges a vertex, a random vertex permutation and a shuffled
edge list with its duplicates and self-loops, all from ``--seed``.  The
structure loads it whole and keeps the distinct edges.

The harness hands ``request_input`` two draws an operation, each a whole
number below 2^24 held exactly in f32 (the configuration's ``keys`` and
``values``).  By the operation they pick:

* ``connected``: two vertices, each uniform (up to the draws' rounding)
  over the vertices of degree >= 1 in the initial graph, as Graph500
  picks its search keys;
* ``insert``: one edge of a pool of fresh edges from the same generator
  and vertex permutation;
* ``delete``: one edge uniform over the initial distinct edges.

``request_input`` has no argument for the preload, so ``preload`` keeps
the three pools in this module.  The reference takes its graph from the
preload's data, not from the pools.

The reference is ``SeqGraph``, a copy of ``repro.core.seq_graph`` kept
here so that the benchmark can check a program that lacks that module:
an exact, incremental numpy implementation that shares nothing with the
device graph.  Vertex ids go through the stated precision on the way in,
as the map's keys do.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

import precision as _precision

DRAW_BITS = 24                    # each draw is a whole number below 2^24
_POOLS: Dict[str, np.ndarray] = {}


def preload(cfg: Dict, seed: int) -> Dict[str, np.ndarray]:
    """The Kronecker edge list and the pools the requests draw from."""
    scale = int(cfg["scale"])
    n = 1 << scale
    if n != int(cfg["n_vertices"]):
        raise ValueError("n_vertices must be 2**scale")
    rng = np.random.default_rng([seed, 0])
    perm = rng.permutation(n)
    edges = kronecker_edges(scale, int(cfg["edgefactor"]) * n, rng, perm)
    fresh = kronecker_edges(scale, int(cfg["insert_pool"]),
                            np.random.default_rng([seed, 1]), perm)
    distinct = canonical_edges(edges, n)
    data = {"edges": edges, "distinct": distinct, "fresh": fresh,
            "verts": np.flatnonzero(np.bincount(distinct.ravel(),
                                                minlength=n))}
    _POOLS.clear()
    _POOLS.update(data)
    return data


def make_kwargs(cfg: Dict, data: Dict[str, np.ndarray]) -> Dict[str, Any]:
    return dict(n=int(cfg["n_vertices"]),
                edge_capacity=int(cfg["edge_capacity"]),
                c_max=int(cfg["c_max"]), n_shards=int(cfg["n_shards"]),
                edges=data["edges"])


def _pick(pool: np.ndarray, draw: int, bits: int) -> Any:
    """Entry ``floor(draw * len(pool) / 2**bits)`` of ``pool``."""
    return pool[(draw * len(pool)) >> bits]


def request_input(method: str, key: float, value: float) -> Any:
    a, b = int(key) - 1, int(value)          # keys are ids 1..2^24-1
    if method == "connected":
        verts = _POOLS["verts"]
        return (int(_pick(verts, a, DRAW_BITS)),
                int(_pick(verts, b, DRAW_BITS)))
    if method in ("insert", "delete"):
        pool = _POOLS["fresh" if method == "insert" else "distinct"]
        u, v = _pick(pool, (a << DRAW_BITS) | b, 2 * DRAW_BITS).tolist()
        return (u, v)
    raise ValueError(f"the graph reference has no method {method!r}")


def fetch_state(ds) -> np.ndarray:
    """The live edges as the device holds them, sorted ``(u < v)`` rows."""
    import jax

    eu, ev, valid = jax.device_get((ds.state.eu, ds.state.ev,
                                    ds.state.valid))
    valid = np.asarray(valid)
    return canonical_edges(np.stack([np.asarray(eu)[valid],
                                     np.asarray(ev)[valid]], axis=1), ds.n)


def state_mismatch(got: np.ndarray, want: np.ndarray) -> int:
    """Edges in one set and not the other."""
    got = np.asarray(got, np.int64).reshape(-1, 2)
    want = np.asarray(want, np.int64).reshape(-1, 2)
    if got.shape == want.shape and np.array_equal(got, want):
        return 0
    base = int(max(got.max(initial=0), want.max(initial=0))) + 1
    return int(np.setxor1d(got[:, 0] * base + got[:, 1],
                           want[:, 0] * base + want[:, 1]).size)


class Reference:
    """:class:`SeqGraph` over the preloaded edges, every vertex id
    rounded through ``precision`` on the way in."""

    def __init__(self, data: Dict[str, np.ndarray], precision: str,
                 cfg: Dict):
        self.q = _precision.rounder(precision)
        n = int(cfg["n_vertices"])
        # a coarser precision may round the top ids up past n - 1
        space = max(n, int(self.q(n - 1)) + 1)
        self.g = SeqGraph(space, self.q(data["distinct"]).astype(np.int64))

    def step(self, methods: Sequence[str], inputs: Sequence[Any]
             ) -> List[Any]:
        uv = self.q(np.asarray(inputs, np.float64).reshape(-1, 2))
        return self.g.step(methods, uv.astype(np.int64).tolist())

    def state(self) -> np.ndarray:
        return self.g.edges()


# ---------------------------------------------------------------------------
# A copy of repro.core.seq_graph from here on
# ---------------------------------------------------------------------------
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
