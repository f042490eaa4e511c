"""Device-resident batched union-find (DESIGN.md §16).

The second workload landed through the :mod:`~repro.core.substrate`
protocol: the label-propagation step (kernels/label_prop) run on the
CONTRACTED graph computes exactly the union-find transition — fold a
batch of new edges into a valid component-min labeling — so this module
only wraps that fixpoint in the substrate idioms: a
donated apply pass with an undonated twin, pow2 rounds lowering onto one
``lax.scan`` (DESIGN.md §12), transactional snapshot/restore (DESIGN.md
§15), the async one-fetch contract (DESIGN.md §11), and an atomic
validation guard (out-of-range vertices refuse with ``ValueError``
before anything reaches the device).

State is the canonical min-label array over vertices ``[0, n)`` —
``find(u)`` is the smallest vertex id in ``u``'s component, which makes
labels unique and lets the differential battery compare them bit-exact
against :class:`~repro.core.seq_union_find.SequentialUnionFind`.

Batch semantics — the PRE-BATCH snapshot rule (the PQ's "extracts see
the pre-batch multiset", DESIGN.md §9): every ``union`` in one batch
reports True iff its endpoints were in different components at batch
START, whatever earlier in-batch unions did; all unions apply together.
This keeps the result masks one fused gather (``labels0[u] !=
labels0[v]``) instead of a sequential in-batch replay, and the oracle
implements the same rule.
"""
from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.label_prop import (label_step, label_step_xla,
                                      require_pallas_fits)

from . import substrate
from .batched_map import _pow2
from .faults import make_guard
from .seq_union_find import SequentialUnionFind

# test hook: module-level so sync-counting tests can monkeypatch it
_host_fetch = jax.device_get

RD_FIND = 0
RD_CONN = 1
RD_COMPS = 2
_READ_CODE = {"find": RD_FIND, "connected": RD_CONN,
              "components": RD_COMPS}


class UFState(NamedTuple):
    labels: jax.Array  # (n,) int32 component-min labeling (a fixpoint)


def _contracted_fixpoint(ceu, cev, *, n: int, n_shards: int,
                         use_pallas: bool) -> jax.Array:
    """Component-min relabeling ``p`` of the contracted graph: vertices =
    current labels, edges = the batch's label pairs.  ``use_pallas`` iterates the ``grid=(K,)`` kernel,
    else the XLA twin — bit-exact per iteration, hence at the fixpoint."""
    if use_pallas:
        step = lambda p: label_step(p, ceu, cev, n_shards=n_shards)
    else:
        step = lambda p: label_step_xla(p, ceu, cev)

    def cond(st):
        return st[1]

    def body(st):
        p, _ = st
        p2 = step(p)
        return p2, jnp.any(p2 != p)

    p0 = jnp.arange(n, dtype=jnp.int32)
    p, _ = jax.lax.while_loop(cond, body, (p0, jnp.bool_(True)))
    return p


def _apply_impl(state: UFState, eu: jax.Array, ev: jax.Array,
                nb: jax.Array, *, n: int, n_shards: int = 1,
                use_pallas: bool = False) -> Tuple[UFState, jax.Array]:
    """Fold ≤ c_max unions as ONE fused pass.

    ``eu``/``ev``: (c,) int32 endpoints; ``nb``: () int32 live lanes
    (inactive lanes sanitize to (0, 0) self-loops).  Returns ``(state,
    ok)`` — ok per the pre-batch rule, left on device."""
    labels = state.labels
    c = eu.shape[0]
    lane = jnp.arange(c, dtype=jnp.int32)
    active = lane < nb
    u = jnp.where(active, eu, 0)
    v = jnp.where(active, ev, 0)
    ok = active & (labels[u] != labels[v])
    p = _contracted_fixpoint(labels[u], labels[v], n=n, n_shards=n_shards,
                             use_pallas=use_pallas)
    return UFState(p[labels]), ok


def _rounds_impl(state: UFState, eu: jax.Array, ev: jax.Array,
                 nb: jax.Array, *, n: int, n_shards: int = 1,
                 use_pallas: bool = False) -> Tuple[UFState, jax.Array]:
    """R sequential ≤ c_max slices as ONE ``lax.scan`` program
    (DESIGN.md §12).  ``eu``/``ev``: (R, c); ``nb``: (R,).  The ok masks
    follow the pre-batch rule, so they gather against the labels BEFORE
    any slice — one fused comparison, not a per-slice replay."""
    labels0 = state.labels
    c = eu.shape[1]
    active = jnp.arange(c, dtype=jnp.int32)[None, :] < nb[:, None]
    u = jnp.where(active, eu, 0)
    v = jnp.where(active, ev, 0)
    oks = active & (labels0[u] != labels0[v])

    def body(st, rnd):
        st, _ = _apply_impl(st, rnd[0], rnd[1], rnd[2], n=n,
                            n_shards=n_shards, use_pallas=use_pallas)
        return st, 0

    state, _ = jax.lax.scan(body, state, (eu, ev, nb))
    return state, oks


_STATIC = ("n", "n_shards", "use_pallas")
apply_pass = jax.jit(_apply_impl, static_argnames=_STATIC,
                     donate_argnums=(0,))
apply_pass_undonated = jax.jit(_apply_impl, static_argnames=_STATIC)
apply_rounds = jax.jit(_rounds_impl, static_argnames=_STATIC,
                       donate_argnums=(0,))
apply_rounds_undonated = jax.jit(_rounds_impl, static_argnames=_STATIC)


def _read_impl(state: UFState, qa: jax.Array, qb: jax.Array,
               qkind: jax.Array) -> jax.Array:
    """Answer a mixed read batch with ONE program: ``find`` gathers the
    label, ``connected`` compares two, ``components`` counts label
    fixpoints (i == labels[i]).  Returns (q,) int32."""
    labels = state.labels
    n = labels.shape[0]
    fnd = labels[qa]
    conn = (labels[qa] == labels[qb]).astype(jnp.int32)
    comps = jnp.sum((labels == jnp.arange(n, dtype=jnp.int32))
                    .astype(jnp.int32))
    return jnp.select([qkind == RD_FIND, qkind == RD_CONN],
                      [fnd, conn], comps)


read_pass = jax.jit(_read_impl)


class AsyncUFUpdate:
    """Deferred per-op merged flags (one-fetch contract, DESIGN.md §11)."""

    def __init__(self, owner: "BatchedUnionFind", masks: List[jax.Array],
                 lane_counts: List[int], c_max: int):
        self._owner: Optional["BatchedUnionFind"] = owner
        self.masks = masks
        self._lane_counts = lane_counts
        self._c_max = c_max
        self._out: Optional[List[bool]] = None

    def _resolve(self, masks_h) -> None:
        if masks_h:
            rows = np.concatenate(
                [np.asarray(m).reshape(-1, self._c_max) for m in masks_h],
                axis=0)
            out = np.concatenate(
                [rows[r, :nc] for r, nc in enumerate(self._lane_counts)]) \
                if self._lane_counts else np.zeros((0,), bool)
        else:
            out = np.zeros((0,), bool)
        self._out = [bool(x) for x in out]
        self._owner = None
        self.masks = []

    def result(self) -> List[bool]:
        if self._out is None:
            self._owner._resolve_through(self)
        return self._out


class BatchedUnionFind(substrate.BatchedStructure):
    """Device-resident union-find over vertices ``[0, n)``.

    Args:
      n: vertex count (compile-time constant — labels are (n,) i32).
      c_max: combined union-batch capacity per pass.
      n_shards: shard-grid width of the Pallas label kernel (only
        meaningful with ``use_pallas``; state itself is one array).
      use_pallas / donate / fault_plan / guard: the uniform knob set.

    There is no occupancy bound (components only merge), so the atomic
    refusal contract is carried by validation: any out-of-range vertex
    refuses the WHOLE batch with ``ValueError`` before dispatch, leaving
    state bit-identical.
    """

    structure = "unionfind"
    read_only: Set[str] = {"find", "connected", "components"}
    # No fused megapass lowering: mixed_rounds rides the base fallback
    # (``substrate.BatchedStructure.mixed_rounds`` — one device program
    # per round).  Declared explicitly so the registry's ``megapass``
    # flag and the conformance kit's flag-vs-behavior assertion have a
    # ground truth to check against (ISSUE-10 satellite; the PR-9
    # carry-over left this implicit).
    supports_megapass = False

    def __init__(self, n: int, c_max: int = 8, n_shards: int = 1,
                 use_pallas: bool = False, donate: bool = True,
                 fault_plan=None, guard=None):
        if n < 1:
            raise ValueError("n must be >= 1")
        if c_max < 1:
            raise ValueError("c_max must be >= 1")
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        self.n = int(n)
        self.c_max = int(c_max)
        self.n_shards = int(n_shards)
        self.use_pallas = bool(use_pallas)
        if self.use_pallas:
            require_pallas_fits(self.n)
        self.donate = bool(donate)
        self.fault_plan = fault_plan
        self._guard = make_guard(fault_plan, guard)
        self.state = UFState(jnp.arange(self.n, dtype=jnp.int32))
        self._unresolved: List[AsyncUFUpdate] = []

    # -- transactional dispatch (DESIGN.md §15) -------------------------------
    def _snapshot(self):
        return UFState(self.state.labels.copy())

    def _restore(self, snap) -> None:
        self.state = snap

    def _check(self, u) -> int:
        u = int(u)
        if not 0 <= u < self.n:
            raise ValueError(f"vertex {u} outside [0, {self.n})")
        return u

    # -- updates --------------------------------------------------------------
    def update_batch_async(self, methods: Sequence[str],
                           inputs: Sequence[Any]) -> AsyncUFUpdate:
        """Fold a combined union batch: ≤ c_max ops dispatch as ONE fused
        pass; wider batches lower onto pow2-padded rows of ONE donated
        scan program.  NO blocking transfer; results follow the
        pre-batch snapshot rule (module docstring)."""
        n_ops = len(methods)
        eu = np.zeros((n_ops,), np.int32)
        ev = np.zeros((n_ops,), np.int32)
        # validate the WHOLE batch before anything dispatches — the
        # atomic refusal contract for a structure with no occupancy bound
        for i, (m, inp) in enumerate(zip(methods, inputs)):
            if m != "union":
                raise ValueError(f"unknown update method {m!r}")
            eu[i] = self._check(inp[0])
            ev[i] = self._check(inp[1])
        if n_ops == 0:
            handle = AsyncUFUpdate(self, [], [], self.c_max)
            handle._out = []
            return handle
        c = self.c_max
        n_rounds = _pow2(-(-n_ops // c))
        us = np.zeros((n_rounds, c), np.int32)
        vs = np.zeros((n_rounds, c), np.int32)
        lane_counts: List[int] = []
        for r in range(n_rounds):
            nc = max(0, min(c, n_ops - r * c))
            us[r, :nc] = eu[r * c : r * c + nc]
            vs[r, :nc] = ev[r * c : r * c + nc]
            lane_counts.append(nc)
        nb = np.asarray(lane_counts, np.int32)

        def commit():
            if n_rounds == 1:
                fn = apply_pass if self.donate else apply_pass_undonated
                self.state, ok = fn(self.state, jnp.asarray(us[0]),
                                    jnp.asarray(vs[0]), jnp.int32(nb[0]),
                                    n=self.n, n_shards=self.n_shards,
                                    use_pallas=self.use_pallas)
                return [ok]
            fn = apply_rounds if self.donate else apply_rounds_undonated
            self.state, oks = fn(self.state, jnp.asarray(us),
                                 jnp.asarray(vs), jnp.asarray(nb),
                                 n=self.n, n_shards=self.n_shards,
                                 use_pallas=self.use_pallas)
            return [oks]

        if self._guard is None:
            masks = commit()
        else:
            masks = self._guard.run(commit, self._snapshot, self._restore,
                                    site="unionfind.apply_pass")
        handle = AsyncUFUpdate(self, masks, lane_counts, c)
        self._unresolved.append(handle)
        return handle

    def _resolve_through(self, handle: Optional[AsyncUFUpdate],
                         extra=None):
        """ONE combined fetch resolves every unresolved handle plus
        ``extra`` (DESIGN.md §11)."""
        todo = list(self._unresolved)
        if handle is not None and handle not in todo:
            todo = []
        if not todo and extra is None:
            return None
        fetched = _host_fetch(([h.masks for h in todo], extra))
        for h, masks_h in zip(todo, fetched[0]):
            h._resolve(masks_h)
            self._unresolved.remove(h)
        return fetched[1]

    def union(self, u: int, v: int) -> bool:
        return self.update_batch(["union"], [(u, v)])[0]

    # -- reads ----------------------------------------------------------------
    def read_batch(self, methods: Sequence[str],
                   inputs: Sequence[Any]) -> List[Any]:
        """ONE device program + ONE blocking fetch for the whole batch
        (which also resolves outstanding update handles)."""
        nq = len(methods)
        if nq == 0:
            return []
        qa = np.zeros((_pow2(nq),), np.int32)
        qb = np.zeros((_pow2(nq),), np.int32)
        kind = np.full((_pow2(nq),), RD_FIND, np.int32)
        for i, (m, inp) in enumerate(zip(methods, inputs)):
            if m not in _READ_CODE:
                raise ValueError(f"unknown read method {m!r}")
            kind[i] = _READ_CODE[m]
            if m == "find":
                qa[i] = self._check(inp)
            elif m == "connected":
                qa[i] = self._check(inp[0])
                qb[i] = self._check(inp[1])
        res = read_pass(self.state, jnp.asarray(qa), jnp.asarray(qb),
                        jnp.asarray(kind))
        got = self._resolve_through(None, extra=res)
        res_h = np.asarray(got)
        out: List[Any] = []
        for i, m in enumerate(methods):
            if m == "connected":
                out.append(bool(res_h[i]))
            else:                       # find / components
                out.append(int(res_h[i]))
        return out

    def find(self, u: int) -> int:
        return self.read_batch(["find"], [u])[0]

    def connected(self, u: int, v: int) -> bool:
        return self.read_batch(["connected"], [(u, v)])[0]

    def components(self) -> int:
        return self.read_batch(["components"], [None])[0]

    # -- debug / test helpers -------------------------------------------------
    def labels(self) -> List[int]:
        """Host copy of the canonical labeling (one fetch)."""
        return [int(x) for x in _host_fetch(self.state.labels)]

    def __len__(self) -> int:
        return self.n


# ---------------------------------------------------------------------------
# Registration (DESIGN.md §16)
# ---------------------------------------------------------------------------
N_DEFAULT = 48


def _gen_update(rng, k, ctx):
    """Union batches biased toward chain edges (long merge paths — the
    stress case for the contracted fixpoint) with random long links."""
    n = ctx.setdefault("n", N_DEFAULT)
    methods, inputs = [], []
    for _ in range(k):
        u = int(rng.integers(n))
        if rng.random() < 0.5:
            v = (u + 1) % n
        else:
            v = int(rng.integers(n))
        methods.append("union")
        inputs.append((u, v))
    return methods, inputs


def _gen_read(rng, k, ctx):
    n = ctx.setdefault("n", N_DEFAULT)
    methods, inputs = [], []
    for _ in range(k):
        r = rng.random()
        if r < 0.4:
            methods.append("find")
            inputs.append(int(rng.integers(n)))
        elif r < 0.8:
            methods.append("connected")
            inputs.append((int(rng.integers(n)), int(rng.integers(n))))
        else:
            methods.append("components")
            inputs.append(None)
    return methods, inputs


def _canon_op(method: str, input: Any) -> Any:
    """Normalize union/connected edges to sorted int tuples (DESIGN.md
    §14) so the compaction dedup sees (u, v) == (v, u)."""
    if method in ("union", "connected"):
        u, v = int(input[0]), int(input[1])
        return (min(u, v), max(u, v))
    if method == "find":
        return int(input)
    return input


def _compact(log, host):
    """Unions are idempotent on state: keep one per normalized edge."""
    seen, ops = set(), []
    for m, e in log:
        if e not in seen:
            seen.add(e)
            ops.append((m, e))
    return ops


def _host_mirror(ds: BatchedUnionFind) -> SequentialUnionFind:
    h = SequentialUnionFind(ds.n)
    h._label = list(ds.labels())
    return h


def _dump_compare(ds: BatchedUnionFind,
                  oracle: SequentialUnionFind) -> None:
    assert ds.labels() == oracle.labels(), (ds.labels(), oracle.labels())


def _make(n: int = N_DEFAULT, c_max: int = 8, **kw) -> BatchedUnionFind:
    return BatchedUnionFind(n, c_max=c_max, **kw)


substrate.register(substrate.StructureSpec(
    name="unionfind",
    module="repro.core.batched_union_find",
    title="batched union-find",
    make=_make,
    make_host=_host_mirror,
    gen_update=_gen_update,
    gen_read=_gen_read,
    dump_compare=_dump_compare,
    canon=_canon_op,
    compact=_compact,
    refusal_batch=lambda ds: (["union"], [(0, ds.n)]),
    bench="benchmarks.bench_unionfind",
    bench_smoke=("--vertices", "256", "--reads", "50", "100",
                 "--threads", "1", "4", "--ops", "60",
                 "--impls", "FC host", "PC", "PC-adaptive"),
    extras={"serve_kw": dict(n=512, c_max=32)},
))
