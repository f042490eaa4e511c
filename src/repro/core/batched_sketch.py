"""Device-resident batched counting/top-k sketch (DESIGN.md §16).

The first workload landed THROUGH the :mod:`~repro.core.substrate`
protocol rather than by copy-adaptation: this module ships only the
fused passes + a registration, and inherits ``update_batch`` / ``apply``
from :class:`~repro.core.substrate.BatchedStructure`, scheduler/serving
wiring from the registry, and its entire test battery from the
conformance kit (``tests/conformance.py``).

Structure: a bounded table of ``key -> count`` counters, hash-sharded
(``sharded_pq.route_hash``) across K sorted-array shards with the map's
scratch-slot layout.  Updates are ``add(key, w)`` with positive integer
weights stored as f32 — integer-valued sums are exact in f32, so the
vectorized per-slice class totals match a sequential oracle bit-for-bit.
``add`` returns True iff the op CREATED the counter (arrival order:
later duplicate lanes in the slice see it present).  Reads — ``count`` /
``total`` / ``distinct`` / ``topk`` — answer in one fused program and
one blocking fetch; ``topk`` merges per-shard top-M candidate lists on
the host (count descending, key ascending tie-break; exact because a
global top-k element is in its shard's top-k for any k ≤ M).

All the substrate idioms apply: donated apply passes with undonated
ablation twins, pow2 rounds lowering onto one ``lax.scan`` (DESIGN.md
§12), the sync-free occupancy guard with an atomic host mirror
(DESIGN.md §10), transactional snapshot/restore for fault guards
(DESIGN.md §15), and the async one-fetch contract (DESIGN.md §11).
"""
from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.sorted_merge import (merge_compact_sharded,
                                        merge_edits_xla,
                                        require_pallas_fits)

from . import substrate
from .batched_map import _pow2
from .batched_pq import INF, _flush_subnormals
from .faults import make_guard
from .seq_sketch import SequentialSketch, _qk, _qw
from .sharded_pq import route_hash, route_hash_host

# test hook: module-level so sync-counting tests can monkeypatch it
_host_fetch = jax.device_get

RD_COUNT = 0
RD_TOTAL = 1
RD_DISTINCT = 2
RD_TOPK = 3
_READ_CODE = {"count": RD_COUNT, "total": RD_TOTAL,
              "distinct": RD_DISTINCT, "topk": RD_TOPK}


class SketchState(NamedTuple):
    """K sorted-array shards; index ``capacity`` is the scratch slot
    (predicated-scatter target for inactive lanes, the map idiom)."""

    keys: jax.Array    # (K, capacity+1) f32 ascending in [0,size), +inf pad
    counts: jax.Array  # (K, capacity+1) f32 integer-valued, +inf past size
    size: jax.Array    # (K,) int32


# ---------------------------------------------------------------------------
# Fused add pass (donated) — class-total sort-merge
# ---------------------------------------------------------------------------
def _prep_one(keys1, counts1, size1, k1, w1, nb1, *, c_max: int):
    """Net a shard's ≤ c_max add row down to merge-compact inputs.

    Returns ``(counts1, b_keys, b_counts, b_count, new_size, ok)``: the
    bumped counts, the sorted run of new counters, and per-lane created
    flags.  Nothing is deleted: every live slot survives the merge.  Increments commute, so the
    chain rule collapses: one representative lane per key class carries
    the class's WEIGHT TOTAL, and only the first lane of an absent key
    reports created=True.
    """
    cap = keys1.shape[0] - 1
    lane = jnp.arange(c_max, dtype=jnp.int32)
    active = lane < nb1

    body = keys1[:cap]
    pos = jnp.searchsorted(body, k1, side="left").astype(jnp.int32)
    pos_c = jnp.clip(pos, 0, cap - 1)
    in_tab = (pos < size1) & (body[pos_c] == k1)

    same = ((k1[:, None] == k1[None, :])
            & active[:, None] & active[None, :])          # (c, c)
    is_rep = active & ~jnp.any(same & (lane[None, :] < lane[:, None]),
                               axis=1)
    wsum = jnp.sum(jnp.where(same, w1[None, :], 0.0), axis=1)
    ok = is_rep & ~in_tab                                 # created

    # existing counters bump in place (predicated scatter-add)
    upd = is_rep & in_tab
    tgt = jnp.where(upd, pos, cap)
    counts1 = counts1.at[tgt].add(jnp.where(upd, wsum, 0.0))
    counts1 = counts1.at[cap].set(INF)                    # scratch stays pad

    # new counters become the sorted b-run (distinct keys by rep-ness)
    add = is_rep & ~in_tab
    bkey_raw = jnp.where(add, k1, INF)
    order = jnp.argsort(bkey_raw)
    b_keys = bkey_raw[order]
    b_counts = jnp.where(add, wsum, INF)[order]
    b_count = jnp.sum(add.astype(jnp.int32))
    new_size = size1 + b_count
    return counts1, b_keys, b_counts, b_count, new_size, ok


def _apply_impl(state: SketchState, op_keys: jax.Array, op_w: jax.Array,
                nb: jax.Array, *,
                use_pallas: bool = False) -> Tuple[SketchState, jax.Array]:
    """Apply ≤ c_max adds as ONE fused pass.

    ``op_keys``/``op_w``: (c,) f32; ``nb``: () int32 live lane count.
    Returns ``(state, ok)`` with per-lane created flags left on device."""
    keys, counts, size = state
    K = keys.shape[0]
    cap = keys.shape[1] - 1
    c = op_keys.shape[0]
    lane = jnp.arange(c, dtype=jnp.int32)
    k = _flush_subnormals(op_keys.astype(jnp.float32))
    w = op_w.astype(jnp.float32)
    active = lane < nb

    # hash-route ops to shards, preserving lane order within each row
    shard_of = jnp.where(active, route_hash(k, K), 0)
    one_hot = ((shard_of[None, :] == jnp.arange(K)[:, None])
               & active[None, :])                         # (K, c)
    rank = jnp.cumsum(one_hot, axis=1) - 1
    cnts = jnp.sum(one_hot, axis=1).astype(jnp.int32)

    def scatter_row(dest, payload, fill):
        row = jnp.full((c + 1,), fill, payload.dtype)
        return row.at[dest].set(payload)[:c]

    dest = jnp.where(one_hot, rank, c)                    # scratch col c
    rows_k = jax.vmap(scatter_row, in_axes=(0, 0, None))(
        dest, jnp.where(one_hot, k[None, :], INF), INF)
    rows_w = jax.vmap(scatter_row, in_axes=(0, 0, None))(
        dest, jnp.where(one_hot, w[None, :], jnp.float32(0)),
        jnp.float32(0))

    counts2, b_keys, b_counts, b_count, new_size, ok_rows = \
        jax.vmap(lambda a, b, s, rk, rw, n: _prep_one(
            a, b, s, rk, rw, n, c_max=c))(
            keys, counts, size, rows_k, rows_w, cnts)

    # insert-only merge of every shard: ONE grid=(K,) kernel, or the
    # vmapped bounded-edit merge with no deletion slots
    if use_pallas:
        keep = jnp.arange(cap)[None, :] < size[:, None]
        mk, mc = merge_compact_sharded(keys[:, :cap], counts2[:, :cap],
                                       keep, b_keys, b_counts, b_count)
    else:
        no_dels = jnp.zeros((K, 0), jnp.int32)
        mk, mc = jax.vmap(merge_edits_xla)(
            keys[:, :cap], counts2[:, :cap], size, no_dels, b_keys,
            b_counts, b_count)
    pad = jnp.full((K, 1), INF, jnp.float32)
    state = SketchState(jnp.concatenate([mk, pad], axis=1),
                        jnp.concatenate([mc, pad], axis=1), new_size)

    ok = active & ok_rows[shard_of, jnp.clip(rank[shard_of, lane],
                                             0, c - 1)]
    return state, ok


def _rounds_impl(state: SketchState, op_keys: jax.Array, op_w: jax.Array,
                 nb: jax.Array, *,
                 use_pallas: bool = False) -> Tuple[SketchState, jax.Array]:
    """R sequential ≤ c_max slices as ONE ``lax.scan`` program
    (DESIGN.md §12).  ``op_keys``/``op_w``: (R, c); ``nb``: (R,)."""

    def body(st, rnd):
        st, ok = _apply_impl(st, rnd[0], rnd[1], rnd[2],
                             use_pallas=use_pallas)
        return st, ok

    state, oks = jax.lax.scan(body, state, (op_keys, op_w, nb))
    return state, oks


_STATIC = ("use_pallas",)
# ``state`` is DONATED on every apply pass (DESIGN.md §10/§13); the
# ``*_undonated`` twins are the copy-per-pass ablation.
apply_pass = jax.jit(_apply_impl, static_argnames=_STATIC,
                     donate_argnums=(0,))
apply_pass_undonated = jax.jit(_apply_impl, static_argnames=_STATIC)
apply_rounds = jax.jit(_rounds_impl, static_argnames=_STATIC,
                       donate_argnums=(0,))
apply_rounds_undonated = jax.jit(_rounds_impl, static_argnames=_STATIC)


# ---------------------------------------------------------------------------
# Fused vectorized read pass (never donated)
# ---------------------------------------------------------------------------
def _read_impl(state: SketchState, qa: jax.Array, qkind: jax.Array, *,
               topk_m: int = 8):
    """Answer a mixed read batch with ONE program.

    ``qa``: (q,) f32 — the key (count; unused otherwise); ``qkind``:
    (q,) int32.  Returns ``(res (q,) f32, tk (K, M) f32, tc (K, M) f32)``
    — the shared per-shard top-M candidate lists every ``topk`` query in
    the batch merges on the host (exact for k ≤ M)."""
    keys, counts, size = state
    cap = keys.shape[1] - 1
    qa = _flush_subnormals(qa.astype(jnp.float32))

    def per_shard(bk, bc, sz):
        body = bk[:cap]
        pos = jnp.searchsorted(body, qa, side="left").astype(jnp.int32)
        pos_c = jnp.clip(pos, 0, cap - 1)
        found = (pos < sz) & (body[pos_c] == qa)
        cval = jnp.where(found, bc[pos_c], 0.0)
        live = jnp.arange(cap) < sz
        tot = jnp.sum(jnp.where(live, bc[:cap], 0.0))
        # per-shard top-M by (count desc, key asc): two-key sort on
        # (-count, key) — dead slots sink via +inf negated count
        negc = jnp.where(live, -bc[:cap], INF)
        negc_s, key_s = jax.lax.sort((negc, body), num_keys=2)
        tk = key_s[:topk_m]
        tc = jnp.where(negc_s[:topk_m] < INF, -negc_s[:topk_m], 0.0)
        return cval, tot, tk, tc

    cval, tot, tk, tc = jax.vmap(per_shard)(keys, counts, size)
    cnt = jnp.sum(cval, axis=0)            # one shard holds the key
    total = jnp.sum(tot)
    distinct = jnp.sum(size).astype(jnp.float32)
    res = jnp.select(
        [qkind == RD_COUNT, qkind == RD_TOTAL, qkind == RD_DISTINCT],
        [cnt, jnp.broadcast_to(total, cnt.shape),
         jnp.broadcast_to(distinct, cnt.shape)], 0.0)
    return res, tk, tc


read_pass = jax.jit(_read_impl, static_argnames=("topk_m",))


class AsyncSketchUpdate:
    """Deferred per-op created flags (one-fetch contract, DESIGN.md §11):
    masks stay on device until :meth:`result` or the owner's next
    ``read_batch`` fetch, which also re-tightens the occupancy mirror."""

    def __init__(self, owner: "ShardedSketch", masks: List[jax.Array],
                 lane_counts: List[int], c_max: int):
        self._owner: Optional["ShardedSketch"] = owner
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


# ---------------------------------------------------------------------------
# Host-facing wrapper
# ---------------------------------------------------------------------------
class ShardedSketch(substrate.BatchedStructure):
    """K-sharded device-resident counting/top-k sketch.

    Args:
      capacity: per-shard counter capacity (plus one scratch slot).
      c_max: combined update-batch capacity per pass (compile-time).
      n_shards: shard count K (hash routing — no key_range needed).
      topk_max: static per-shard candidate width M; ``topk(k)`` requires
        k ≤ M (exactness bound for the host-side merge).
      items: optional initial (key, weight) pairs.
      use_pallas / donate / fault_plan / guard: the uniform knob set
        (DESIGN.md §10/§13/§15).
    """

    structure = "sketch"
    read_only: Set[str] = {"count", "total", "distinct", "topk"}
    # No fused megapass lowering: mixed_rounds rides the base fallback
    # (``substrate.BatchedStructure.mixed_rounds`` — one device program
    # per round).  Declared explicitly so the registry's ``megapass``
    # flag and the conformance kit's flag-vs-behavior assertion have a
    # ground truth to check against (ISSUE-10 satellite; the PR-9
    # carry-over left this implicit).
    supports_megapass = False

    def __init__(self, capacity: int, c_max: int, n_shards: int = 1,
                 topk_max: int = 8, items=None, use_pallas: bool = False,
                 donate: bool = True, fault_plan=None, guard=None):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if c_max < 1:
            raise ValueError("c_max must be >= 1")
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        if topk_max < 1:
            raise ValueError("topk_max must be >= 1")
        self.capacity = int(capacity)
        self.c_max = int(c_max)
        self.n_shards = int(n_shards)
        self.topk_max = int(topk_max)
        self.use_pallas = bool(use_pallas)
        if self.use_pallas:
            require_pallas_fits(self.capacity)
        self.donate = bool(donate)
        self.fault_plan = fault_plan
        self._guard = make_guard(fault_plan, guard)
        self.state = self._init_state(items)
        self._unresolved: List[AsyncSketchUpdate] = []

    # -- transactional dispatch (DESIGN.md §15) -------------------------------
    def _snapshot(self):
        st = SketchState(self.state.keys.copy(), self.state.counts.copy(),
                         self.state.size.copy())
        return st, self._sizes_ub.copy()

    def _restore(self, snap) -> None:
        self.state, self._sizes_ub = snap

    def _init_state(self, items) -> SketchState:
        K, cap = self.n_shards, self.capacity
        keys = np.full((K, cap + 1), np.inf, np.float32)
        counts = np.full((K, cap + 1), np.inf, np.float32)
        size = np.zeros((K,), np.int32)
        if items:
            table = {}
            for key, w in items:
                q = _qk(key)
                table[q] = table.get(q, 0.0) + _qw(w)
            ks = np.asarray(sorted(table), np.float32)
            cs = np.asarray([table[float(k)] for k in ks], np.float32)
            shards = route_hash_host(ks, K)
            for k in range(K):
                mine = shards == k
                n = int(mine.sum())
                if n > cap:
                    raise ValueError("per-shard capacity too small")
                keys[k, :n] = ks[mine]
                counts[k, :n] = cs[mine]
                size[k] = n
        self._sizes_ub = size.astype(np.int64).copy()
        return SketchState(jnp.asarray(keys), jnp.asarray(counts),
                           jnp.asarray(size))

    def __len__(self) -> int:
        return int(np.sum(np.asarray(self.state.size)))

    # -- occupancy guard (DESIGN.md §10) --------------------------------------
    def _refresh_sizes(self, sizes) -> None:
        self._sizes_ub = np.asarray(sizes, np.int64).copy()

    def occupancy_mirror(self):
        return {"sizes_ub": self._sizes_ub}

    def _guard_slices(self, slices) -> None:
        """Atomic sync-free overflow guard over ALL slices: every add is
        a potential new counter (upper bound — duplicates re-tighten at
        the next fetch); refusal restores the mirror bit-for-bit and
        nothing is ever dispatched."""
        ub = self._sizes_ub.copy()
        for opk, nc in slices:
            if nc:
                shards = route_hash_host(opk[:nc], self.n_shards)
                ub += np.bincount(shards, minlength=self.n_shards
                                  ).astype(np.int64)
            if np.any(ub > self.capacity):
                raise ValueError(
                    f"per-shard capacity {self.capacity} exceeded: "
                    f"add routing would grow a shard past it")
        self._sizes_ub = ub

    # -- updates --------------------------------------------------------------
    def update_batch_async(self, methods: Sequence[str],
                           inputs: Sequence[Any]) -> AsyncSketchUpdate:
        """Apply a combined add batch: ≤ c_max ops dispatch as ONE fused
        pass; wider batches lower onto pow2-padded rows of ONE donated
        scan program.  NO blocking transfer (DESIGN.md §11/§12)."""
        n_ops = len(methods)
        opk = np.zeros((n_ops,), np.float32)
        opw = np.zeros((n_ops,), np.float32)
        for i, (m, inp) in enumerate(zip(methods, inputs)):
            if m != "add":
                raise ValueError(f"unknown update method {m!r}")
            opk[i] = _qk(inp[0])
            opw[i] = _qw(inp[1])
        if n_ops == 0:
            handle = AsyncSketchUpdate(self, [], [], self.c_max)
            handle._out = []
            return handle
        c = self.c_max
        n_rounds = _pow2(-(-n_ops // c))
        ks = np.full((n_rounds, c), np.inf, np.float32)
        ws = np.zeros((n_rounds, c), np.float32)
        lane_counts: List[int] = []
        slices = []
        for r in range(n_rounds):
            nc = max(0, min(c, n_ops - r * c))
            ks[r, :nc] = opk[r * c : r * c + nc]
            ws[r, :nc] = opw[r * c : r * c + nc]
            lane_counts.append(nc)
            slices.append((ks[r], nc))
        nb = np.asarray(lane_counts, np.int32)

        def commit():
            # guard the WHOLE batch before dispatching anything; inside
            # the thunk so a transactional restore rewinds mirror + state
            # together (DESIGN.md §15)
            self._guard_slices(slices)
            if n_rounds == 1:
                fn = apply_pass if self.donate else apply_pass_undonated
                self.state, ok = fn(self.state, jnp.asarray(ks[0]),
                                    jnp.asarray(ws[0]), jnp.int32(nb[0]),
                                    use_pallas=self.use_pallas)
                return [ok]
            fn = apply_rounds if self.donate else apply_rounds_undonated
            self.state, oks = fn(self.state, jnp.asarray(ks),
                                 jnp.asarray(ws), jnp.asarray(nb),
                                 use_pallas=self.use_pallas)
            return [oks]

        if self._guard is None:
            masks = commit()
        else:
            masks = self._guard.run(commit, self._snapshot, self._restore,
                                    site="sketch.apply_pass")
        handle = AsyncSketchUpdate(self, masks, lane_counts, c)
        self._unresolved.append(handle)
        return handle

    def _resolve_through(self, handle: Optional[AsyncSketchUpdate],
                         extra=None):
        """ONE combined fetch resolves every unresolved handle plus
        ``extra`` and re-tightens the mirror (DESIGN.md §11)."""
        todo = list(self._unresolved)
        if handle is not None and handle not in todo:
            todo = []
        if not todo and extra is None:
            return None
        # `+ 0` detaches from a buffer a later donated apply would eat
        fetched = _host_fetch(([h.masks for h in todo],
                               self.state.size + 0, extra))
        for h, masks_h in zip(todo, fetched[0]):
            h._resolve(masks_h)
            self._unresolved.remove(h)
        self._refresh_sizes(fetched[1])
        return fetched[2]

    def add(self, key: float, w: float = 1.0) -> bool:
        return self.update_batch(["add"], [(key, w)])[0]

    # -- reads ----------------------------------------------------------------
    def read_batch(self, methods: Sequence[str],
                   inputs: Sequence[Any]) -> List[Any]:
        """ONE device program + ONE blocking fetch for the whole batch
        (resolves outstanding update handles, re-tightens the mirror)."""
        nq = len(methods)
        if nq == 0:
            return []
        qa = np.zeros((_pow2(nq),), np.float32)
        kind = np.full((_pow2(nq),), RD_TOTAL, np.int32)
        topks: List[int] = []
        for i, (m, inp) in enumerate(zip(methods, inputs)):
            if m not in _READ_CODE:
                raise ValueError(f"unknown read method {m!r}")
            kind[i] = _READ_CODE[m]
            if m == "count":
                qa[i] = _qk(inp)
            elif m == "topk":
                kq = int(inp)
                if not 1 <= kq <= self.topk_max:
                    raise ValueError(
                        f"topk k={kq} outside [1, topk_max="
                        f"{self.topk_max}]")
                topks.append(kq)
        res, tk, tc = read_pass(self.state, jnp.asarray(qa),
                                jnp.asarray(kind), topk_m=self.topk_max)
        got = self._resolve_through(None, extra=(res, tk, tc))
        res_h = np.asarray(got[0])
        if topks:
            # merge the K per-shard candidate lists: count desc, key asc
            cand = sorted(
                ((float(k), float(c))
                 for k, c in zip(np.asarray(got[1]).ravel(),
                                 np.asarray(got[2]).ravel()) if c > 0),
                key=lambda kc: (-kc[1], kc[0]))
        out: List[Any] = []
        for i, m in enumerate(methods):
            if m == "topk":
                out.append(cand[: int(inputs[i])])
            elif m == "distinct":
                out.append(int(res_h[i]))
            else:                       # count / total
                out.append(float(res_h[i]))
        return out

    def count(self, key: float) -> float:
        return self.read_batch(["count"], [key])[0]

    def total(self) -> float:
        return self.read_batch(["total"], [None])[0]

    def distinct(self) -> int:
        return self.read_batch(["distinct"], [None])[0]

    def topk(self, k: int) -> List[Tuple[float, float]]:
        return self.read_batch(["topk"], [k])[0]

    # -- debug / test helpers -------------------------------------------------
    def counters(self) -> List[Tuple[float, float]]:
        """Host copy of live (key, count) pairs, ascending (one fetch)."""
        keys, counts, size = _host_fetch((self.state.keys,
                                          self.state.counts,
                                          self.state.size))
        out: List[Tuple[float, float]] = []
        for k in range(self.n_shards):
            n = int(size[k])
            out.extend(zip(keys[k, :n].tolist(), counts[k, :n].tolist()))
        return sorted(out)


# ---------------------------------------------------------------------------
# Registration (DESIGN.md §16) — factories + op generators + adaptive hooks
# ---------------------------------------------------------------------------
def _gen_update(rng, k, ctx):
    """Pool-biased add batches: 60% revisit a hot key, else a fresh one."""
    pool = ctx.setdefault("keys", [])
    methods, inputs = [], []
    for _ in range(k):
        if pool and rng.random() < 0.6:
            key = pool[int(rng.integers(len(pool)))]
        else:
            key = _qk(float(rng.uniform(0.0, 100.0)))
            pool.append(key)
        methods.append("add")
        inputs.append((key, float(int(rng.integers(1, 10)))))
    return methods, inputs


def _gen_read(rng, k, ctx):
    pool = ctx.setdefault("keys", [])
    methods, inputs = [], []
    for _ in range(k):
        r = rng.random()
        if r < 0.4 and pool:
            methods.append("count")
            inputs.append(pool[int(rng.integers(len(pool)))])
        elif r < 0.55:
            methods.append("count")
            inputs.append(_qk(float(rng.uniform(0.0, 100.0))))
        elif r < 0.7:
            methods.append("total")
            inputs.append(None)
        elif r < 0.85:
            methods.append("distinct")
            inputs.append(None)
        else:
            methods.append("topk")
            inputs.append(int(rng.integers(1, 6)))
    return methods, inputs


def _canon_op(method: str, input: Any) -> Any:
    """Adaptive-tier op canonicalization (DESIGN.md §14): quantize keys
    and weights to the exact images both tiers store."""
    if method == "add":
        return (_qk(input[0]), _qw(input[1]))
    if method == "count":
        return _qk(input)
    return input


def _compact(log, host):
    """Increments commute: one add per key with the summed weight."""
    totals, order = {}, []
    for _m, (key, w) in log:
        if key not in totals:
            order.append(key)
        totals[key] = totals.get(key, 0.0) + w
    return [("add", (key, totals[key])) for key in order]


def _refusal_batch(ds: ShardedSketch):
    """More distinct fresh keys than total capacity: pigeonhole forces a
    per-shard overflow whatever the hash routing does."""
    n = ds.capacity * ds.n_shards + 1
    return (["add"] * n,
            [(1.0e6 + 2.0 * i, 1.0) for i in range(n)])


def _make(capacity: int = 512, c_max: int = 8, n_shards: int = 2,
          **kw) -> ShardedSketch:
    return ShardedSketch(capacity, c_max=c_max, n_shards=n_shards, **kw)


substrate.register(substrate.StructureSpec(
    name="sketch",
    module="repro.core.batched_sketch",
    title="counting/top-k sketch",
    make=_make,
    make_host=lambda ds: SequentialSketch(ds.counters()),
    gen_update=_gen_update,
    gen_read=_gen_read,
    dump_compare=lambda ds, oracle: _dump_compare(ds, oracle),
    canon=_canon_op,
    compact=_compact,
    refusal_batch=_refusal_batch,
    bench="benchmarks.bench_sketch",
    bench_smoke=("--keys", "256", "--reads", "50", "100",
                 "--threads", "1", "4", "--ops", "60",
                 "--impls", "FC host", "PC-K1", "PC-K4", "PC-adaptive"),
    extras={"serve_kw": dict(capacity=1024, c_max=32, n_shards=4)},
))


def _dump_compare(ds: ShardedSketch, oracle: SequentialSketch) -> None:
    got, want = ds.counters(), oracle.items()
    assert len(got) == len(want), (got, want)
    for (gk, gc), (wk, wc) in zip(got, want):
        assert gk == wk and gc == wc, (got, want)
