"""Device-resident batched ordered map (DESIGN.md §13) — the flagship
structure of the batch-parallel literature (Lim's 2-3 trees), rebuilt to
the sharded-PQ / device-graph tier's standard.

Each shard is a **flat 2-3 tree**: a fixed-capacity sorted unique-key
array (keys ascending in ``[0, size)``, ``(+inf, +inf)`` padding beyond,
one scratch slot for predicated scatters).  Sorted order makes every read
a vectorized search — no pointers, no rebalancing — and makes one
combining pass of mixed updates a **sort-merge**:

* **fused apply pass** — ONE donated program applies a ≤ ``c_max`` MIXED
  insert/delete/assign batch with sequential arrival-order semantics.
  Per-lane results follow the last-earlier-same-key chain rule (an op's
  outcome fully determines presence for the next op on that key, exactly
  the device graph's rule); the array takes only the NET effect per key
  class: deletions become a ``keep`` mask, insertions become a short
  sorted run, in-place value writes scatter at their slot, and one
  **merge-compact** (``kernels/sorted_merge``) rebuilds the sorted array
  — rank arithmetic + scatter in the XLA twin, a ``grid=(K,)``
  broadcast-compare kernel under ``use_pallas=True``.
* **vectorized batched reads** — ``lookup``, ``range_count``,
  ``range_sum`` (closed interval [lo, hi]) and ``kth_smallest`` are ONE
  fused program per read batch: masked binary search (``searchsorted``
  against the sorted body), fixed-order block sums over the rank
  interval for range aggregation, and a shard-size cumsum for the global
  k-th — reads never mutate state, so
  the read pass is never donated and a read-only workload never copies
  the map (the §5.1 read-dominated setting this structure targets).
* **multi-round scan path** (DESIGN.md §12) — update batches wider than
  ``c_max`` lower onto pow2-padded rows of ONE donated ``lax.scan``
  program (``apply_rounds``), the PR-4 command-queue recipe; result
  masks stay on device and ride the next read's single blocking fetch
  (``update_batch_async`` — the PQ/graph one-sync contract).
* **key-range sharding** — ``ShardedMap`` stacks K shards on a leading
  axis and routes every op by the Lim-style key-range partition
  (``sharded_pq.route_range`` and its bit-exact host twin), so shard
  concatenation stays globally sorted: range queries sum per-shard
  answers, the k-th key is found by a cumulative-size search, and the
  sync-free host occupancy guard refuses overflowing batches
  **atomically** — a refused batch leaves the device buffers and the
  host mirror untouched (the sharded-PQ guard pattern, hardened per the
  ISSUE-5 overflow audit).

Everything is shape-static (``c_max`` lanes, pow2-padded read widths and
scan rows) so each pass jits to a single XLA program; the apply passes
**donate** the map state (``donate=False`` is the copy-per-pass ablation
twin, EXPERIMENTS §Ablations).  The wrapper is not thread-safe; confine
each instance to one thread (the read-optimized combiner does).
"""
from __future__ import annotations

import math
from typing import Any, List, NamedTuple, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import placement as _placement
from . import substrate
from repro.kernels.sorted_merge import (merge_compact_sharded,
                                        merge_compact_xla,
                                        require_pallas_fits)

from .batched_pq import INF, _flush_subnormals
from .faults import make_guard
from .sharded_pq import _flush_host, _route, _route_host, host_key
from .tracing import TRACER

# All device→host transfers on the map hot path route through this hook
# so tests can count blocking syncs (same idiom as batched_pq._host_fetch).
_host_fetch = jax.device_get

OP_INSERT, OP_DELETE, OP_ASSIGN = 0, 1, 2
RD_LOOKUP, RD_COUNT, RD_SUM, RD_KTH = 0, 1, 2, 3

_UPDATE_CODE = {"insert": OP_INSERT, "delete": OP_DELETE,
                "assign": OP_ASSIGN}
_READ_CODE = {"lookup": RD_LOOKUP, "range_count": RD_COUNT,
              "range_sum": RD_SUM, "kth_smallest": RD_KTH}


def _qkey(x: float) -> float:
    """The exact f32 key the device map stores (f32 + flush-to-zero,
    DESIGN.md §7).  ±inf is the padding sentinel and NaN breaks the
    binary search, so both are rejected at this host boundary."""
    k = float(np.float32(x))
    if math.isnan(k) or math.isinf(k):
        raise ValueError("map keys must be finite f32: ±inf is the "
                         "padding sentinel and NaN breaks the search")
    return host_key(k)


def _qval(x: float) -> float:
    """Values are stored as f32; NaN is rejected (the merge kernel's
    masked-min materialization is undefined for NaN payloads)."""
    v = float(np.float32(x))
    if math.isnan(v):
        raise ValueError("map values must not be NaN")
    return v


class MapState(NamedTuple):
    """K sorted-array shards stacked on the leading axis.

    Index ``capacity`` of every row is the SCRATCH slot for predicated
    scatters (the graph/heap idiom): inactive lanes write there with one
    fixed payload, so they can never collide with an active write."""

    keys: jax.Array   # (K, capacity+1) f32 ascending in [0,size), +inf pad
    vals: jax.Array   # (K, capacity+1) f32, +inf past size
    size: jax.Array   # (K,) int32


def _pow2(m: int) -> int:
    return 1 << max(0, (m - 1).bit_length())


# ---------------------------------------------------------------------------
# Fused mixed-op apply pass (donated) — net-effect sort-merge
# ---------------------------------------------------------------------------
def _prep_one(keys1, vals1, size1, k1, v1, code1, nb1, *, c_max: int):
    """Net a shard's ≤ c_max op row down to merge-compact inputs.

    Returns ``(keys1, vals1, keep, b_keys, b_vals, b_count, new_size,
    ok)``: the value-updated arrays, the survivor mask over the body, the
    sorted run of netted-in pairs, and the per-lane arrival-order results
    (the chain rule, see module docstring).  Pure XLA, vmapped over the
    shard axis by :func:`_apply_impl`.
    """
    cap = keys1.shape[0] - 1
    lane = jnp.arange(c_max, dtype=jnp.int32)
    active = lane < nb1
    is_ins = active & (code1 == OP_INSERT)
    is_del = active & (code1 == OP_DELETE)
    is_asn = active & (code1 == OP_ASSIGN)

    body = keys1[:cap]
    pos = jnp.searchsorted(body, k1, side="left").astype(jnp.int32)
    pos_c = jnp.clip(pos, 0, cap - 1)
    in_map0 = (pos < size1) & (body[pos_c] == k1)
    stored = vals1[pos_c]                     # junk unless in_map0

    # arrival-order chain rule: a lane's key is "present before" iff the
    # LAST earlier presence-changing lane on the same key was an insert
    same = ((k1[:, None] == k1[None, :])
            & active[:, None] & active[None, :])          # (c, c)
    pchg = is_ins | is_del
    earlier_p = same & pchg[None, :] & (lane[None, :] < lane[:, None])
    has_prev = jnp.any(earlier_p, axis=1)
    prev = jnp.argmax(jnp.where(earlier_p, lane[None, :], -1), axis=1)
    present_before = jnp.where(has_prev, is_ins[prev], in_map0)
    ok = active & jnp.where(is_ins, ~present_before, present_before)

    # net effect per key class: the last presence-changing lane decides
    # final presence; the last EFFECTIVE write decides the final value
    has_pchg = jnp.any(same & pchg[None, :], axis=1)
    last_p = jnp.argmax(jnp.where(same & pchg[None, :], lane[None, :],
                                  -1), axis=1)
    final_present = jnp.where(has_pchg, is_ins[last_p], in_map0)
    wr = (is_ins & ~present_before) | (is_asn & present_before)
    has_wr = jnp.any(same & wr[None, :], axis=1)
    last_wr = jnp.argmax(jnp.where(same & wr[None, :], lane[None, :],
                                   -1), axis=1)
    final_val = jnp.where(has_wr, v1[last_wr], stored)

    # one representative lane per class carries the buffer effect
    is_rep = active & ~jnp.any(same & (lane[None, :] < lane[:, None]),
                               axis=1)
    rem = is_rep & in_map0 & ~final_present               # netted out
    upd = is_rep & in_map0 & final_present & has_wr       # value rewrite
    add = is_rep & ~in_map0 & final_present               # netted in

    # in-place value rewrites at the exact slot (predicated scatter)
    tgt = jnp.where(upd, pos, cap)
    vals1 = vals1.at[tgt].set(jnp.where(upd, final_val, vals1[tgt]))
    vals1 = vals1.at[cap].set(INF)                        # scratch stays pad

    # deletions become the merge's keep mask
    rflag = jnp.zeros((cap + 1,), jnp.bool_)
    rflag = rflag.at[jnp.where(rem, pos, cap)].set(rem)
    keep = (jnp.arange(cap) < size1) & ~rflag[:cap]

    # insertions become the sorted b-run (stable argsort; distinct keys)
    bkey_raw = jnp.where(add, k1, INF)
    order = jnp.argsort(bkey_raw)
    b_keys = bkey_raw[order]
    b_vals = jnp.where(add, final_val, INF)[order]
    b_count = jnp.sum(add.astype(jnp.int32))
    new_size = size1 - jnp.sum(rem.astype(jnp.int32)) + b_count
    return keys1, vals1, keep, b_keys, b_vals, b_count, new_size, ok


def _apply_impl(state: MapState, op_keys: jax.Array, op_vals: jax.Array,
                op_code: jax.Array, nb: jax.Array, *,
                key_range: Optional[Tuple[float, float]] = None,
                use_pallas: bool = False,
                placement=None) -> Tuple[MapState, jax.Array]:
    """Apply ≤ c_max MIXED insert/delete/assign ops as ONE fused pass.

    ``op_keys``/``op_vals``: (c,) f32; ``op_code``: (c,) int32
    (0=insert, 1=delete, 2=assign); ``nb``: () int32 live lane count.
    Returns ``(state, ok)`` with per-lane arrival-order results — the
    results stay on device until fetched (``AsyncMapUpdate``).

    ``placement`` (static): ``None`` traces the single-device program
    below; a ``MeshPlacement`` dispatches to the shard_map twin
    (DESIGN.md §18)."""
    if placement is not None and placement.is_mesh:
        return _mesh_apply(state, op_keys, op_vals, op_code, nb,
                           key_range=key_range, placement=placement)
    keys, vals, size = state
    K = keys.shape[0]
    cap = keys.shape[1] - 1
    c = op_keys.shape[0]
    lane = jnp.arange(c, dtype=jnp.int32)
    k = _flush_subnormals(op_keys.astype(jnp.float32))
    v = op_vals.astype(jnp.float32)
    active = lane < nb

    # route ops to shards (key-range partition), preserving lane order
    # within each shard row — load-bearing for the chain rule
    shard_of = jnp.where(active, _route(k, K, key_range), 0)
    one_hot = ((shard_of[None, :] == jnp.arange(K)[:, None])
               & active[None, :])                         # (K, c)
    rank = jnp.cumsum(one_hot, axis=1) - 1                # (K, c)
    counts = jnp.sum(one_hot, axis=1).astype(jnp.int32)

    def scatter_row(dest, payload, fill):
        row = jnp.full((c + 1,), fill, payload.dtype)
        return row.at[dest].set(payload)[:c]

    dest = jnp.where(one_hot, rank, c)                    # scratch col c
    rows_k = jax.vmap(scatter_row, in_axes=(0, 0, None))(
        dest, jnp.where(one_hot, k[None, :], INF), INF)
    rows_v = jax.vmap(scatter_row, in_axes=(0, 0, None))(
        dest, jnp.where(one_hot, v[None, :], jnp.float32(0)),
        jnp.float32(0))
    rows_c = jax.vmap(scatter_row, in_axes=(0, 0, None))(
        dest, jnp.where(one_hot, op_code[None, :], 0), 0)

    keys2, vals2, keep, b_keys, b_vals, b_count, new_size, ok_rows = \
        jax.vmap(lambda a, b, s, rk, rv, rc, n: _prep_one(
            a, b, s, rk, rv, rc, n, c_max=c))(
            keys, vals, size, rows_k, rows_v, rows_c, counts)

    # merge-compact every shard: ONE grid=(K,) kernel or the vmapped twin
    if use_pallas:
        mk, mv = merge_compact_sharded(keys2[:, :cap], vals2[:, :cap],
                                       keep, b_keys, b_vals, b_count)
    else:
        mk, mv = jax.vmap(merge_compact_xla)(
            keys2[:, :cap], vals2[:, :cap], keep, b_keys, b_vals,
            b_count)
    pad = jnp.full((K, 1), INF, jnp.float32)
    state = MapState(jnp.concatenate([mk, pad], axis=1),
                     jnp.concatenate([mv, pad], axis=1), new_size)

    # gather per-lane results back into arrival order
    ok = active & ok_rows[shard_of, jnp.clip(rank[shard_of, lane],
                                             0, c - 1)]
    return state, ok


def _rounds_impl(state: MapState, op_keys: jax.Array, op_vals: jax.Array,
                 op_code: jax.Array, nb: jax.Array, *,
                 key_range: Optional[Tuple[float, float]] = None,
                 use_pallas: bool = False,
                 placement=None) -> Tuple[MapState, jax.Array]:
    """R sequential ≤ c_max slices as ONE ``lax.scan`` program
    (DESIGN.md §12): ``op_keys``/``op_vals`` (R, c), ``op_code`` (R, c),
    ``nb`` (R,).  Each scan step is the full fused mixed-op pass, so a
    batch spanning R slices costs one dispatch.  Returns (state, oks).
    Under a ``MeshPlacement`` the scan moves inside one shard_map body."""
    if placement is not None and placement.is_mesh:
        return _mesh_rounds(state, op_keys, op_vals, op_code, nb,
                            key_range=key_range, placement=placement)

    def body(st, rnd):
        st, ok = _apply_impl(st, rnd[0], rnd[1], rnd[2], rnd[3],
                             key_range=key_range, use_pallas=use_pallas)
        return st, ok

    state, oks = jax.lax.scan(body, state, (op_keys, op_vals, op_code, nb))
    return state, oks


_STATIC = ("key_range", "use_pallas", "placement")
# ``state`` is DONATED on every apply pass — the sorted arrays update in
# place (DESIGN.md §10/§13); the ``*_undonated`` twins are the
# copy-per-pass ablation (EXPERIMENTS §Ablations).
apply_pass = jax.jit(_apply_impl, static_argnames=_STATIC,
                     donate_argnums=(0,))
apply_pass_undonated = jax.jit(_apply_impl, static_argnames=_STATIC)
apply_rounds = jax.jit(_rounds_impl, static_argnames=_STATIC,
                       donate_argnums=(0,))
apply_rounds_undonated = jax.jit(_rounds_impl, static_argnames=_STATIC)


# ---------------------------------------------------------------------------
# Fused vectorized read pass (never donated — reads copy nothing)
# ---------------------------------------------------------------------------
_SUM_BLOCK = 128     # slots per block of the range-sum tree


def _ordered_sum(x: jax.Array) -> jax.Array:
    """Sum over the last axis as a fixed tree of pairwise adds (zero-padded
    to a power of two).  The TPU compiler picks a reduce's order per
    program shape, so a ``jnp.sum`` gives the stacked and the mesh layout
    different last bits; elementwise adds give the same bits in any
    program."""
    n = x.shape[-1]
    p = 1 << max(n - 1, 0).bit_length()
    if p != n:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, p - n)])
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x[..., 0]


def _probe_shard(bk, bv, sz, qa, qb):
    """One shard's read probes for a (q,) query batch: (found, lookup
    value, closed-interval count, closed-interval value sum).

    The range sum over slots [lo, hi) adds the partial first and last
    128-slot blocks to the sums of the whole blocks between them, every
    sum an :func:`_ordered_sum`.  A difference of f32 prefix sums is wrong
    at deployment size: on a million-slot shard the running total reaches
    ~2^14, where one f32 ulp is already above the sum's tolerance."""
    cap = bk.shape[0] - 1
    body = bk[:cap]
    # masked binary search: the +inf padding keeps searchsorted exact
    pos = jnp.searchsorted(body, qa, side="left").astype(jnp.int32)
    pos_c = jnp.clip(pos, 0, cap - 1)
    found = (pos < sz) & (body[pos_c] == qa)
    lval = jnp.where(found, bv[pos_c], INF)
    # closed-interval rank bounds
    lo = jnp.minimum(pos, sz)
    hi = jnp.minimum(jnp.searchsorted(body, qb, side="right"), sz)
    cnt = jnp.maximum(hi - lo, 0).astype(jnp.int32)
    # blocks past sz hold the +inf padding: hi <= sz never selects them
    B = _SUM_BLOCK
    nb = -(-cap // B)
    blocks = jnp.pad(bv[:cap], (0, nb * B - cap)).reshape(nb, B)
    blo, bhi = lo // B, hi // B
    lane = jnp.arange(B)[None, :]
    s_first = blo[:, None] * B + lane
    s_last = bhi[:, None] * B + lane
    first = jnp.where((s_first >= lo[:, None]) & (s_first < hi[:, None]),
                      blocks[jnp.clip(blo, 0, nb - 1)], 0.0)
    last = jnp.where((bhi > blo)[:, None] & (s_last < hi[:, None]),
                     blocks[jnp.clip(bhi, 0, nb - 1)], 0.0)
    b = jnp.arange(nb)[None, :]
    between = jnp.where((b > blo[:, None]) & (b < bhi[:, None]),
                        _ordered_sum(blocks)[None, :], 0.0)
    rsum = (_ordered_sum(first) + _ordered_sum(between)) \
        + _ordered_sum(last)
    return found, lval, cnt, rsum


def _read_impl(state: MapState, qa: jax.Array, qb: jax.Array,
               qkind: jax.Array,
               *, placement=None) -> Tuple[jax.Array, jax.Array]:
    """Answer a mixed read batch with ONE program.

    ``qa``/``qb``: (q,) f32 — the key (lookup), [lo, hi] bounds
    (range_count / range_sum) or k (kth_smallest, in ``qa``);
    ``qkind``: (q,) int32.  Returns ``(res (q,) f32, ok (q,) bool)`` —
    ``ok`` is the found/in-range flag for lookup and kth_smallest.
    """
    if placement is not None and placement.is_mesh:
        return _mesh_read(state, qa, qb, qkind, placement=placement)
    keys, vals, size = state
    K = keys.shape[0]
    cap = keys.shape[1] - 1
    qa = _flush_subnormals(qa.astype(jnp.float32))
    qb = _flush_subnormals(qb.astype(jnp.float32))
    found, lval, cnt, rsum = jax.vmap(
        lambda bk, bv, sz: _probe_shard(bk, bv, sz, qa, qb))(keys, vals,
                                                             size)
    any_found = jnp.any(found, axis=0)
    # exactly one shard can hold the key (routing) — masked min IS select
    look_val = jnp.min(jnp.where(found, lval, INF), axis=0)
    total_cnt = jnp.sum(cnt, axis=0).astype(jnp.float32)
    total_sum = _ordered_sum(rsum.T)

    # global k-th: key-range routing keeps the shard concatenation
    # globally sorted, so a cumulative-size search finds the owner shard
    ccum = jnp.cumsum(size)
    kq = qa.astype(jnp.int32)
    sh = jnp.sum((ccum[:, None] < kq[None, :]).astype(jnp.int32), axis=0)
    sh_c = jnp.clip(sh, 0, K - 1)
    prior = jnp.where(sh > 0, ccum[jnp.clip(sh - 1, 0, K - 1)], 0)
    loc = kq - prior
    kth_ok = (kq >= 1) & (kq <= ccum[K - 1])
    kth_val = keys[sh_c, jnp.clip(loc - 1, 0, cap - 1)]

    res = jnp.select(
        [qkind == RD_LOOKUP, qkind == RD_COUNT, qkind == RD_SUM],
        [look_val, total_cnt, total_sum], kth_val)
    ok = jnp.select([qkind == RD_LOOKUP, qkind == RD_KTH],
                    [any_found, kth_ok], jnp.bool_(True))
    return res, ok


read_pass = jax.jit(_read_impl, static_argnames=("placement",))


# ---------------------------------------------------------------------------
# Fused mixed update+read megapass (DESIGN.md §17)
# ---------------------------------------------------------------------------
MEGA_UPDATE, MEGA_READ = 0, 1


def _mixed_impl(state: MapState, tags: jax.Array, op_a: jax.Array,
                op_b: jax.Array, op_code: jax.Array, nb: jax.Array, *,
                key_range: Optional[Tuple[float, float]] = None,
                use_pallas: bool = False, placement=None,
                ) -> Tuple[MapState, jax.Array, jax.Array]:
    """R heterogeneous combining rounds as ONE donated scan program.

    Each row is one tagged round slice: ``tags`` (R,) int32 selects the
    fused apply pass (``MEGA_UPDATE``) or the vectorized read pass
    (``MEGA_READ``) inside a ``lax.cond``, so interleaved update and
    read rounds cost one dispatch instead of one each.  Row payloads
    share lanes: ``op_a``/``op_b`` (R, c) f32 carry (keys, vals) for
    updates and (qa, qb) for reads; ``op_code`` (R, c) int32 carries the
    op code or the read kind; ``nb`` (R,) is the live lane count (reads
    answer all c lanes — the host masks).  Returns ``(state, res, ok)``
    with per-round (R, c) result slots: update rows fill ``res`` with
    the +inf sentinel and ``ok`` with the arrival-order masks; read rows
    leave the state untouched and fill both."""
    if placement is not None and placement.is_mesh:
        return _mesh_mixed(state, tags, op_a, op_b, op_code, nb,
                           key_range=key_range, placement=placement)

    def body(st, rnd):
        tag, ra, rb, rc, rnb = rnd

        def upd(s):
            s2, ok = _apply_impl(s, ra, rb, rc, rnb, key_range=key_range,
                                 use_pallas=use_pallas)
            return s2, (jnp.full(ra.shape, INF, jnp.float32), ok)

        def rd(s):
            res, ok = _read_impl(s, ra, rb, rc)
            return s, (res, ok)

        st, out = jax.lax.cond(tag == MEGA_READ, rd, upd, st)
        return st, out

    state, (res, ok) = jax.lax.scan(body, state,
                                    (tags, op_a, op_b, op_code, nb))
    return state, res, ok


mixed_pass = jax.jit(_mixed_impl, static_argnames=_STATIC,
                     donate_argnums=(0,))
mixed_pass_undonated = jax.jit(_mixed_impl, static_argnames=_STATIC)


# ---------------------------------------------------------------------------
# Mesh placement (DESIGN.md §18): the K shard rows live on D devices
# ---------------------------------------------------------------------------
# Same shape as the PQ's mesh twin: routing (O(K·c), tiny) is computed
# replicated on every device against GLOBAL shard ids, each device runs
# the net-effect prep + merge-compact on its K/D local shard rows only
# (the O(c² + capacity) work scale-out parallelizes), and the arrival-
# order result gather / global read reductions become collectives.
# all_gather's device-major stacking makes global shard k = d·K/D + j —
# exactly the stacked row order — so every gathered reduction reuses the
# stacked reduction code on an identical (K, ·) array, which keeps the
# float sums bit-identical.  merge_compact_sharded (the Pallas kernel)
# assumes the whole stack in one address space: use_pallas composes with
# StackedPlacement only (the wrapper refuses the combination).
from jax.sharding import PartitionSpec as _P


def _mesh_apply_body(keys, vals, size, op_keys, op_vals, op_code, nb,
                     *, n_shards: int, key_range, axis: str):
    """One fused mixed-op pass on the LOCAL K/D shard rows."""
    K = n_shards
    K_local = keys.shape[0]
    cap = keys.shape[1] - 1
    c = op_keys.shape[0]
    lane = jnp.arange(c, dtype=jnp.int32)
    k = _flush_subnormals(op_keys.astype(jnp.float32))
    v = op_vals.astype(jnp.float32)
    active = lane < nb
    base = jax.lax.axis_index(axis) * K_local

    # global routing, replicated (every device must agree on the lane →
    # shard assignment to gather results back in arrival order)
    shard_of = jnp.where(active, _route(k, K, key_range), 0)
    one_hot_g = ((shard_of[None, :] == jnp.arange(K)[:, None])
                 & active[None, :])                        # (K, c)
    rank_g = jnp.cumsum(one_hot_g, axis=1) - 1             # (K, c)
    one_hot = jax.lax.dynamic_slice_in_dim(one_hot_g, base, K_local, 0)
    rank = jax.lax.dynamic_slice_in_dim(rank_g, base, K_local, 0)
    counts = jnp.sum(one_hot, axis=1).astype(jnp.int32)

    def scatter_row(dest, payload, fill):
        row = jnp.full((c + 1,), fill, payload.dtype)
        return row.at[dest].set(payload)[:c]

    dest = jnp.where(one_hot, rank, c)                     # scratch col c
    rows_k = jax.vmap(scatter_row, in_axes=(0, 0, None))(
        dest, jnp.where(one_hot, k[None, :], INF), INF)
    rows_v = jax.vmap(scatter_row, in_axes=(0, 0, None))(
        dest, jnp.where(one_hot, v[None, :], jnp.float32(0)),
        jnp.float32(0))
    rows_c = jax.vmap(scatter_row, in_axes=(0, 0, None))(
        dest, jnp.where(one_hot, op_code[None, :], 0), 0)

    keys2, vals2, keep, b_keys, b_vals, b_count, new_size, ok_rows = \
        jax.vmap(lambda a, b, s, rk, rv, rc, n: _prep_one(
            a, b, s, rk, rv, rc, n, c_max=c))(
            keys, vals, size, rows_k, rows_v, rows_c, counts)
    mk, mv = jax.vmap(merge_compact_xla)(
        keys2[:, :cap], vals2[:, :cap], keep, b_keys, b_vals, b_count)
    pad = jnp.full((K_local, 1), INF, jnp.float32)
    new_keys = jnp.concatenate([mk, pad], axis=1)
    new_vals = jnp.concatenate([mv, pad], axis=1)

    # collective arrival-order gather: every device needs every shard's
    # per-lane results to answer its (replicated) copy of the batch
    ok_g = jax.lax.all_gather(ok_rows, axis).reshape(K, c)
    ok = active & ok_g[shard_of, jnp.clip(rank_g[shard_of, lane],
                                          0, c - 1)]
    return new_keys, new_vals, new_size, ok


def _mesh_read_body(keys, vals, size, qa, qb, qkind,
                    *, n_shards: int, axis: str):
    """Collective twin of :func:`_read_impl`: per-shard probes run on
    the local rows, the cross-shard reductions run on the gathered
    (K, q) stats — the same reduction code on the same array values as
    the stacked trace, so sums are bit-identical.  The k-th owner-shard
    key is fetched with a ``pmin`` (only the owner contributes a finite
    value)."""
    K = n_shards
    K_local = keys.shape[0]
    cap = keys.shape[1] - 1
    qa = _flush_subnormals(qa.astype(jnp.float32))
    qb = _flush_subnormals(qb.astype(jnp.float32))
    base = jax.lax.axis_index(axis) * K_local
    found_l, lval_l, cnt_l, rsum_l = jax.vmap(
        lambda bk, bv, sz: _probe_shard(bk, bv, sz, qa, qb))(keys, vals,
                                                             size)
    q = qa.shape[0]
    found = jax.lax.all_gather(found_l, axis).reshape(K, q)
    lval = jax.lax.all_gather(lval_l, axis).reshape(K, q)
    cnt = jax.lax.all_gather(cnt_l, axis).reshape(K, q)
    rsum = jax.lax.all_gather(rsum_l, axis).reshape(K, q)
    size_g = jax.lax.all_gather(size, axis).reshape(K)

    any_found = jnp.any(found, axis=0)
    look_val = jnp.min(jnp.where(found, lval, INF), axis=0)
    total_cnt = jnp.sum(cnt, axis=0).astype(jnp.float32)
    total_sum = _ordered_sum(rsum.T)

    ccum = jnp.cumsum(size_g)
    kq = qa.astype(jnp.int32)
    sh = jnp.sum((ccum[:, None] < kq[None, :]).astype(jnp.int32), axis=0)
    sh_c = jnp.clip(sh, 0, K - 1)
    prior = jnp.where(sh > 0, ccum[jnp.clip(sh - 1, 0, K - 1)], 0)
    loc = kq - prior
    kth_ok = (kq >= 1) & (kq <= ccum[K - 1])
    mine = (sh_c >= base) & (sh_c < base + K_local)
    kv = jnp.where(
        mine,
        keys[jnp.clip(sh_c - base, 0, K_local - 1),
             jnp.clip(loc - 1, 0, cap - 1)],
        INF)
    kth_val = jax.lax.pmin(kv, axis)

    res = jnp.select(
        [qkind == RD_LOOKUP, qkind == RD_COUNT, qkind == RD_SUM],
        [look_val, total_cnt, total_sum], kth_val)
    ok = jnp.select([qkind == RD_LOOKUP, qkind == RD_KTH],
                    [any_found, kth_ok], jnp.bool_(True))
    return res, ok


def _map_mesh_specs(placement):
    ax = placement.axis
    return ax, (_P(ax, None), _P(ax, None), _P(ax))


def _mesh_apply(state, op_keys, op_vals, op_code, nb,
                *, key_range, placement):
    K = state.keys.shape[0]
    ax, st_specs = _map_mesh_specs(placement)

    def body(keys, vals, size, rk, rv, rc, rnb):
        return _mesh_apply_body(keys, vals, size, rk, rv, rc, rnb,
                                n_shards=K, key_range=key_range, axis=ax)

    fn = jax.shard_map(body, mesh=placement.mesh,
                       in_specs=st_specs + (_P(), _P(), _P(), _P()),
                       out_specs=st_specs + (_P(),),
                       check_vma=False)
    keys, vals, size, ok = fn(state.keys, state.vals, state.size,
                              op_keys, op_vals, op_code, nb)
    return MapState(keys, vals, size), ok


def _mesh_rounds(state, op_keys, op_vals, op_code, nb,
                 *, key_range, placement):
    K = state.keys.shape[0]
    ax, st_specs = _map_mesh_specs(placement)

    def body(keys, vals, size, rks, rvs, rcs, rnbs):
        def step(carry, rnd):
            keys, vals, size = carry
            rk, rv, rc, rnb = rnd
            keys, vals, size, ok = _mesh_apply_body(
                keys, vals, size, rk, rv, rc, rnb,
                n_shards=K, key_range=key_range, axis=ax)
            return (keys, vals, size), ok

        (keys, vals, size), oks = jax.lax.scan(
            step, (keys, vals, size), (rks, rvs, rcs, rnbs))
        return keys, vals, size, oks

    fn = jax.shard_map(body, mesh=placement.mesh,
                       in_specs=st_specs + (_P(), _P(), _P(), _P()),
                       out_specs=st_specs + (_P(),),
                       check_vma=False)
    keys, vals, size, oks = fn(state.keys, state.vals, state.size,
                               op_keys, op_vals, op_code, nb)
    return MapState(keys, vals, size), oks


def _mesh_read(state, qa, qb, qkind, *, placement):
    K = state.keys.shape[0]
    ax, st_specs = _map_mesh_specs(placement)

    def body(keys, vals, size, qa, qb, qkind):
        return _mesh_read_body(keys, vals, size, qa, qb, qkind,
                               n_shards=K, axis=ax)

    fn = jax.shard_map(body, mesh=placement.mesh,
                       in_specs=st_specs + (_P(), _P(), _P()),
                       out_specs=(_P(), _P()),
                       check_vma=False)
    return fn(state.keys, state.vals, state.size, qa, qb, qkind)


def _mesh_mixed(state, tags, op_a, op_b, op_code, nb,
                *, key_range, placement):
    K = state.keys.shape[0]
    ax, st_specs = _map_mesh_specs(placement)

    def body(keys, vals, size, tags, ras, rbs, rcs, rnbs):
        def step(carry, rnd):
            keys, vals, size = carry
            tag, ra, rb, rc, rnb = rnd

            def upd(st):
                keys, vals, size, ok = _mesh_apply_body(
                    st[0], st[1], st[2], ra, rb, rc, rnb,
                    n_shards=K, key_range=key_range, axis=ax)
                return (keys, vals, size,
                        jnp.full(ra.shape, INF, jnp.float32), ok)

            def rd(st):
                res, ok = _mesh_read_body(st[0], st[1], st[2], ra, rb, rc,
                                          n_shards=K, axis=ax)
                return st[0], st[1], st[2], res, ok

            keys, vals, size, res, ok = jax.lax.cond(
                tag == MEGA_READ, rd, upd, (keys, vals, size))
            return (keys, vals, size), (res, ok)

        (keys, vals, size), (res, ok) = jax.lax.scan(
            step, (keys, vals, size), (tags, ras, rbs, rcs, rnbs))
        return keys, vals, size, res, ok

    fn = jax.shard_map(body, mesh=placement.mesh,
                       in_specs=st_specs + (_P(), _P(), _P(), _P(), _P()),
                       out_specs=st_specs + (_P(), _P()),
                       check_vma=False)
    keys, vals, size, res, ok = fn(state.keys, state.vals, state.size,
                                   tags, op_a, op_b, op_code, nb)
    return MapState(keys, vals, size), res, ok


def _encode_update_ops(methods: Sequence[str], inputs: Sequence[Any]):
    """Validate + quantize an update op list into (opk, opv, code) f32/
    f32/int32 arrays — raises ``ValueError`` before anything dispatches."""
    n_ops = len(methods)
    opk = np.zeros((n_ops,), np.float32)
    opv = np.zeros((n_ops,), np.float32)
    code = np.zeros((n_ops,), np.int32)
    for i, (m, inp) in enumerate(zip(methods, inputs)):
        if m not in _UPDATE_CODE:
            raise ValueError(f"unknown update method {m!r}")
        code[i] = _UPDATE_CODE[m]
        if m == "delete":
            opk[i] = _qkey(inp)
        else:
            opk[i] = _qkey(inp[0])
            opv[i] = _qval(inp[1])
    return opk, opv, code


def _encode_read_ops(methods: Sequence[str], inputs: Sequence[Any]):
    """Validate + quantize a read op list into (qa, qb, kind) arrays."""
    n = len(methods)
    qa = np.zeros((n,), np.float32)
    qb = np.full((n,), -1.0, np.float32)
    kind = np.full((n,), RD_COUNT, np.int32)
    for i, (m, inp) in enumerate(zip(methods, inputs)):
        if m not in _READ_CODE:
            raise ValueError(f"unknown read method {m!r}")
        kind[i] = _READ_CODE[m]
        if m == "lookup":
            qa[i] = _qkey(inp)
        elif m == "kth_smallest":
            qa[i] = np.float32(int(inp))
        else:
            qa[i] = _qkey(inp[0])
            qb[i] = _qkey(inp[1])
    return qa, qb, kind


def _convert_read_results(methods: Sequence[str], res_h, ok_h) -> List[Any]:
    """Fetched (res, ok) lanes → per-op python results, arrival order."""
    out: List[Any] = []
    for i, m in enumerate(methods):
        if m == "range_count":
            out.append(int(res_h[i]))
        elif m == "range_sum":
            out.append(float(res_h[i]))
        else:                          # lookup / kth_smallest
            out.append(float(res_h[i]) if ok_h[i] else None)
    return out


# ---------------------------------------------------------------------------
# Deferred update results (the one-sync contract, DESIGN.md §10/§11)
# ---------------------------------------------------------------------------
class AsyncMapUpdate:
    """Deferred host view of one update batch's per-op results.

    The ok masks stay on device until the first :meth:`result` call — or,
    cheaper, until the owning map's next ``read_batch`` fetches them
    inside its single blocking transfer.  Resolution also re-tightens the
    owner's occupancy mirror to the exact shard sizes."""

    def __init__(self, owner: "ShardedMap", masks: List[jax.Array],
                 lane_counts: List[int], c_max: int):
        self._owner: Optional["ShardedMap"] = owner
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
        """Per-op results in arrival order (cached after first call)."""
        if self._out is None:
            self._owner._resolve_through(self)
        return self._out


class _MegapassFetch:
    """The ONE deferred blocking fetch shared by every handle of a fused
    megapass dispatch (DESIGN.md §17).

    The (R, c) per-round result slots stay on device until the first
    handle resolves; that resolution rides ``_resolve_through`` so it
    also drains any OLDER outstanding update handles and re-tightens the
    occupancy mirror — the whole megapass (updates, reads, sizes, and
    prior batches) costs exactly one host sync."""

    def __init__(self, owner: "ShardedMap", res_rows, ok_rows):
        self._owner: Optional["ShardedMap"] = owner
        self._res = res_rows
        self._ok = ok_rows
        self._upd: List[Tuple[AsyncMapUpdate, int, int]] = []
        self._cache = None

    def rows(self):
        if self._cache is None:
            got = self._owner._resolve_through(
                None, extra=(self._res, self._ok))
            res_h, ok_h = np.asarray(got[0]), np.asarray(got[1])
            for inner, lo, hi in self._upd:
                if inner._out is None:
                    inner._resolve([ok_h[lo:hi]])
            self._cache = (res_h, ok_h)
            self._owner = self._res = self._ok = None
            self._upd = []
        return self._cache


class _MegaUpdateRound:
    """Handle for one update round of a megapass: per-op ok masks in
    arrival order, resolved through the dispatch's shared fetch."""

    def __init__(self, shared: _MegapassFetch, inner: AsyncMapUpdate):
        self._shared = shared
        self._inner = inner

    def result(self) -> List[bool]:
        if self._inner._out is None:
            self._shared.rows()
        return self._inner._out


class _MegaReadRound:
    """Handle for one read round of a megapass."""

    def __init__(self, shared: _MegapassFetch, row_lo: int,
                 counts: List[int], methods: List[str]):
        self._shared = shared
        self._row_lo = row_lo
        self._counts = counts
        self._methods = methods

    def result(self) -> List[Any]:
        res_h, ok_h = self._shared.rows()
        res = np.concatenate(
            [res_h[self._row_lo + r, :nc]
             for r, nc in enumerate(self._counts)]) \
            if self._counts else np.zeros((0,), np.float32)
        ok = np.concatenate(
            [ok_h[self._row_lo + r, :nc]
             for r, nc in enumerate(self._counts)]) \
            if self._counts else np.zeros((0,), bool)
        return _convert_read_results(self._methods, res, ok)


# ---------------------------------------------------------------------------
# Host-facing wrappers
# ---------------------------------------------------------------------------
class ShardedMap(substrate.BatchedStructure):
    """K-sharded device-resident ordered map with combining passes.

    Args:
      capacity: per-shard slot capacity (plus one scratch slot).
      c_max: combined update-batch capacity per pass (compile-time
        constant; larger batches lower onto one ``lax.scan`` program).
      n_shards: shard count K.  K > 1 requires ``key_range``.
      key_range: (lo, hi) — the Lim-style key-range partition
        (``sharded_pq.route_range``); keys outside clamp to the edge
        shards, so the shard concatenation stays globally sorted.
      items: optional initial (key, value) pairs.
      use_pallas: run the merge-compact through the ``grid=(K,)`` Pallas
        kernel (``kernels/sorted_merge``) instead of the XLA twin.
      donate: zero-copy (donated) apply passes (default); ``False`` is
        the copy-per-pass ablation twin.
      placement: shard layout (DESIGN.md §18) — ``None``/
        ``StackedPlacement`` keeps all K rows on one device (the
        original trace); ``MeshPlacement`` splits them across a 1-D
        mesh and runs the fused passes under shard_map.  Requires
        ``K % D == 0``; not combinable with ``use_pallas``.

    Sync-free occupancy guard (DESIGN.md §10): the wrapper mirrors the
    device's key-range routing on the host (``route_range_host``, bit
    exact) and keeps per-shard occupancy upper bounds — inserts grow the
    bound at dispatch, the bound re-tightens to the true sizes at every
    consumed fetch.  The guard is ATOMIC across the slices of one batch:
    a refused batch leaves the device buffers and the mirror exactly as
    they were (regression-tested; the sharded-PQ overflow audit).
    """

    structure = "map"
    read_only: Set[str] = {"lookup", "range_count", "range_sum",
                           "kth_smallest"}
    supports_megapass = True
    supports_placement = True

    def __init__(self, capacity: int, c_max: int, n_shards: int = 1,
                 key_range: Optional[Tuple[float, float]] = None,
                 items=None, use_pallas: bool = False,
                 donate: bool = True, fault_plan=None, guard=None,
                 placement=None):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if c_max < 1:
            raise ValueError("c_max must be >= 1")
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        if n_shards > 1 and key_range is None:
            raise ValueError(
                "n_shards > 1 requires key_range: the ordered reads "
                "(kth_smallest) need the key-range partition")
        self.capacity = int(capacity)
        self.c_max = int(c_max)
        self.n_shards = int(n_shards)
        self.use_pallas = bool(use_pallas)
        self.donate = bool(donate)
        self.placement = _placement.resolve_placement(placement)
        self.placement.validate(self.n_shards)
        self._pstatic = _placement.as_static(self.placement)
        if self._pstatic is not None and self.use_pallas:
            raise ValueError(
                "use_pallas is not supported under MeshPlacement: the "
                "grid=(K,) merge-compact kernel assumes the whole shard "
                "stack in one device's address space (DESIGN.md §18)")
        if self.use_pallas:
            require_pallas_fits(self.capacity)
        self.key_range = ((float(key_range[0]), float(key_range[1]))
                          if key_range is not None else None)
        self.fault_plan = fault_plan
        self._guard = make_guard(fault_plan, guard)
        self.state = self.placement.put(self._init_state(items))
        self._unresolved: List[AsyncMapUpdate] = []

    # -- transactional dispatch (DESIGN.md §15) -------------------------------
    def _snapshot(self):
        """Device-side copies (never donated) + the occupancy mirror."""
        st = MapState(self.state.keys.copy(), self.state.vals.copy(),
                      self.state.size.copy())
        return st, self._sizes_ub.copy()

    def _restore(self, snap) -> None:
        self.state, self._sizes_ub = snap

    def _init_state(self, items) -> MapState:
        K, cap = self.n_shards, self.capacity
        keys = np.full((K, cap + 1), np.inf, np.float32)
        vals = np.full((K, cap + 1), np.inf, np.float32)
        size = np.zeros((K,), np.int32)
        if items:
            pairs = {}
            for key, val in items:
                pairs[_qkey(key)] = _qval(val)   # last write wins
            ks = _flush_host(sorted(pairs))
            vs = np.asarray([pairs[float(k)] for k in ks], np.float32)
            shards = _route_host(ks, K, self.key_range)
            for k in range(K):
                mine = shards == k
                n = int(mine.sum())
                if n > cap:
                    raise ValueError("per-shard capacity too small")
                keys[k, :n] = ks[mine]
                vals[k, :n] = vs[mine]
                size[k] = n
        # host occupancy mirror: exact at init, upper bounds in between
        self._sizes_ub = size.astype(np.int64).copy()
        return MapState(jnp.asarray(keys), jnp.asarray(vals),
                        jnp.asarray(size))

    def __len__(self) -> int:
        return int(np.sum(np.asarray(self.state.size)))

    # -- occupancy guard ------------------------------------------------------
    def _refresh_sizes(self, sizes) -> None:
        self._sizes_ub = np.asarray(sizes, np.int64).copy()

    def occupancy_mirror(self):
        return {"sizes_ub": self._sizes_ub}

    def _guard_slices(self, slices) -> None:
        """Atomic sync-free overflow guard over ALL slices of a batch:
        refusal restores the mirror bit-for-bit and nothing is ever
        dispatched (the sharded-PQ overflow-audit contract)."""
        ub = self._sizes_ub.copy()
        for opk, _opv, code, nc in slices:
            ins = opk[:nc][code[:nc] == OP_INSERT]
            if ins.size:
                shards = _route_host(ins, self.n_shards, self.key_range)
                ub += np.bincount(shards, minlength=self.n_shards
                                  ).astype(np.int64)
            if np.any(ub > self.capacity):
                raise ValueError(
                    f"per-shard capacity {self.capacity} exceeded: "
                    f"insert routing would grow a shard past it")
        self._sizes_ub = ub

    # -- updates --------------------------------------------------------------
    def update_batch_async(self, methods: Sequence[str],
                           inputs: Sequence[Any]) -> AsyncMapUpdate:
        """Apply a combined MIXED update batch, arrival order preserved.

        ≤ c_max ops dispatch as ONE fused pass; wider batches lower onto
        pow2-padded rows of ONE donated ``apply_rounds`` scan program
        (DESIGN.md §12).  NO blocking transfer: the per-op result masks
        stay on device and ride the next read's fetch."""
        n_ops = len(methods)
        if n_ops == 0:
            handle = AsyncMapUpdate(self, [], [], self.c_max)
            handle._out = []
            return handle
        with TRACER.span("map.prep"):
            opk, opv, code = _encode_update_ops(methods, inputs)
            c = self.c_max
            n_rounds = _pow2(-(-n_ops // c))
            ks = np.full((n_rounds, c), np.inf, np.float32)
            vs = np.zeros((n_rounds, c), np.float32)
            cs = np.zeros((n_rounds, c), np.int32)
            lane_counts: List[int] = []
            slices = []
            for r in range(n_rounds):
                nc = max(0, min(c, n_ops - r * c))
                ks[r, :nc] = opk[r * c : r * c + nc]
                vs[r, :nc] = opv[r * c : r * c + nc]
                cs[r, :nc] = code[r * c : r * c + nc]
                lane_counts.append(nc)
                slices.append((ks[r], vs[r], cs[r], nc))
            nb = np.asarray(lane_counts, np.int32)

        def commit():
            # guard the WHOLE batch before dispatching anything — atomic:
            # _guard_slices validates every slice on a local copy and only
            # commits the mirror after all of them pass.  It lives inside
            # the dispatch thunk so a transactional restore rewinds the
            # mirror and the device state together (DESIGN.md §15).
            with TRACER.span("map.prep"):
                self._guard_slices(slices)
            with TRACER.span("map.dispatch"):
                if n_rounds == 1:
                    fn = apply_pass if self.donate else apply_pass_undonated
                    self.state, ok = fn(self.state, jnp.asarray(ks[0]),
                                        jnp.asarray(vs[0]),
                                        jnp.asarray(cs[0]),
                                        jnp.int32(nb[0]),
                                        key_range=self.key_range,
                                        use_pallas=self.use_pallas,
                                        placement=self._pstatic)
                    return [ok]
                fn = apply_rounds if self.donate else apply_rounds_undonated
                self.state, oks = fn(self.state, jnp.asarray(ks),
                                     jnp.asarray(vs), jnp.asarray(cs),
                                     jnp.asarray(nb),
                                     key_range=self.key_range,
                                     use_pallas=self.use_pallas,
                                     placement=self._pstatic)
                return [oks]

        if self._guard is None:
            masks = commit()
        else:
            masks = self._guard.run(commit, self._snapshot, self._restore,
                                    site="map.apply_pass")
        handle = AsyncMapUpdate(self, masks, lane_counts, c)
        self._unresolved.append(handle)
        return handle

    def _resolve_through(self, handle: Optional[AsyncMapUpdate],
                         extra=None):
        """Fetch (once) the masks of EVERY unresolved update handle plus
        ``extra`` and the exact shard sizes, then resolve in dispatch
        order — one combined fetch is exactly the budgeted sync."""
        todo = list(self._unresolved)
        if handle is not None and handle not in todo:
            todo = []                          # already resolved
        if not todo and extra is None:
            return None
        # `+ 0` detaches the sizes from state.size, which a later donated
        # apply would invalidate (fetching a donated buffer throws)
        with TRACER.span("map.fetch"):
            fetched = _host_fetch(([h.masks for h in todo],
                                   self.state.size + 0, extra))
        with TRACER.span("map.convert"):
            for h, masks_h in zip(todo, fetched[0]):
                h._resolve(masks_h)
                self._unresolved.remove(h)
            self._refresh_sizes(fetched[1])
        return fetched[2]

    # ``update_batch`` / generic ``apply`` inherit from BatchedStructure

    def insert(self, key: float, value: float) -> bool:
        return self.update_batch(["insert"], [(key, value)])[0]

    def assign(self, key: float, value: float) -> bool:
        return self.update_batch(["assign"], [(key, value)])[0]

    def delete(self, key: float) -> bool:
        return self.update_batch(["delete"], [key])[0]

    # -- reads ----------------------------------------------------------------
    def read_batch(self, methods: Sequence[str],
                   inputs: Sequence[Any]) -> List[Any]:
        """Answer a mixed read batch with ONE device program and ONE
        blocking fetch (which also resolves every outstanding update
        handle and re-tightens the occupancy mirror).  Queries are
        padded to a power of two to bound recompiles."""
        nq = len(methods)
        if nq == 0:
            return []
        with TRACER.span("map.prep"):
            qa0, qb0, kind0 = _encode_read_ops(methods, inputs)
            qa = np.zeros((_pow2(nq),), np.float32)
            qb = np.full((_pow2(nq),), -1.0, np.float32)
            kind = np.full((_pow2(nq),), RD_COUNT, np.int32)  # pad: count 0
            qa[:nq], qb[:nq], kind[:nq] = qa0, qb0, kind0
        with TRACER.span("map.dispatch"):
            res, ok = read_pass(self.state, jnp.asarray(qa),
                                jnp.asarray(qb), jnp.asarray(kind),
                                placement=self._pstatic)
        got = self._resolve_through(None, extra=(res, ok))
        with TRACER.span("map.convert"):
            res_h, ok_h = np.asarray(got[0]), np.asarray(got[1])
            return _convert_read_results(methods, res_h, ok_h)

    def lookup(self, key: float) -> Optional[float]:
        return self.read_batch(["lookup"], [key])[0]

    def range_count(self, lo: float, hi: float) -> int:
        return self.read_batch(["range_count"], [(lo, hi)])[0]

    def range_sum(self, lo: float, hi: float) -> float:
        return self.read_batch(["range_sum"], [(lo, hi)])[0]

    def kth_smallest(self, k: int) -> Optional[float]:
        return self.read_batch(["kth_smallest"], [k])[0]

    # -- fused mixed update+read megapass (DESIGN.md §17) ---------------------
    def mixed_rounds(self, rounds):
        """R heterogeneous update/read rounds as ONE donated scan program.

        Round r+1 observes all of round r's effects (the scan carry IS
        the serial schedule); per-round results stack into never-donated
        (R, c) output slots and every returned handle resolves through
        ONE shared blocking fetch.  Refusal is atomic across the whole
        megapass: the occupancy guard validates every update slice
        before anything dispatches."""
        c = self.c_max
        tags: List[int] = []
        ras: List[np.ndarray] = []
        rbs: List[np.ndarray] = []
        rcs: List[np.ndarray] = []
        nbs: List[int] = []
        plans: List[Tuple] = []
        upd_slices = []
        for kind, methods, inputs in rounds:
            methods, inputs = list(methods), list(inputs)
            n = len(methods)
            row_lo = len(tags)
            if kind == "update":
                opk, opv, code = _encode_update_ops(methods, inputs)
                lane_counts: List[int] = []
                for r in range(-(-n // c) if n else 0):
                    nc = min(c, n - r * c)
                    ka = np.full((c,), np.inf, np.float32)
                    va = np.zeros((c,), np.float32)
                    ca = np.zeros((c,), np.int32)
                    ka[:nc] = opk[r * c : r * c + nc]
                    va[:nc] = opv[r * c : r * c + nc]
                    ca[:nc] = code[r * c : r * c + nc]
                    tags.append(MEGA_UPDATE)
                    ras.append(ka); rbs.append(va); rcs.append(ca)
                    nbs.append(nc)
                    lane_counts.append(nc)
                    upd_slices.append((ka, va, ca, nc))
                plans.append(("update", row_lo, lane_counts))
            elif kind == "read":
                qa, qb, qk = _encode_read_ops(methods, inputs)
                counts: List[int] = []
                for r in range(-(-n // c) if n else 0):
                    nc = min(c, n - r * c)
                    aa = np.zeros((c,), np.float32)
                    bb = np.full((c,), -1.0, np.float32)
                    kk = np.full((c,), RD_COUNT, np.int32)
                    aa[:nc] = qa[r * c : r * c + nc]
                    bb[:nc] = qb[r * c : r * c + nc]
                    kk[:nc] = qk[r * c : r * c + nc]
                    tags.append(MEGA_READ)
                    ras.append(aa); rbs.append(bb); rcs.append(kk)
                    nbs.append(nc)
                    counts.append(nc)
                plans.append(("read", row_lo, counts, methods))
            else:
                raise ValueError(f"unknown round kind {kind!r} "
                                 f"(want 'update' or 'read')")
        n_rows = len(tags)
        if n_rows == 0:
            return [substrate._DoneReads([]) for _ in plans]
        # pow2-pad the row count with no-op READ rows — reads are pure,
        # so padding can never perturb the serial schedule
        while len(tags) < _pow2(n_rows):
            tags.append(MEGA_READ)
            ras.append(np.zeros((c,), np.float32))
            rbs.append(np.full((c,), -1.0, np.float32))
            rcs.append(np.full((c,), RD_COUNT, np.int32))
            nbs.append(0)
        tags_a = np.asarray(tags, np.int32)
        ra_a = np.stack(ras)
        rb_a = np.stack(rbs)
        rc_a = np.stack(rcs)
        nb_a = np.asarray(nbs, np.int32)

        def commit():
            self._guard_slices(upd_slices)
            fn = mixed_pass if self.donate else mixed_pass_undonated
            self.state, res_rows, ok_rows = fn(
                self.state, jnp.asarray(tags_a), jnp.asarray(ra_a),
                jnp.asarray(rb_a), jnp.asarray(rc_a), jnp.asarray(nb_a),
                key_range=self.key_range, use_pallas=self.use_pallas,
                placement=self._pstatic)
            return res_rows, ok_rows

        if self._guard is None:
            res_rows, ok_rows = commit()
        else:
            res_rows, ok_rows = self._guard.run(
                commit, self._snapshot, self._restore,
                site="map.mixed_rounds")

        shared = _MegapassFetch(self, res_rows, ok_rows)
        handles: List[Any] = []
        for plan in plans:
            if plan[0] == "update":
                _, row_lo, lane_counts = plan
                inner = AsyncMapUpdate(self, [], lane_counts, c)
                if not lane_counts:
                    inner._out = []
                else:
                    shared._upd.append(
                        (inner, row_lo, row_lo + len(lane_counts)))
                handles.append(_MegaUpdateRound(shared, inner))
            else:
                _, row_lo, counts, methods = plan
                handles.append(_MegaReadRound(shared, row_lo, counts,
                                              methods))
        return handles

    # -- debug / test helpers -------------------------------------------------
    def items(self) -> List[Tuple[float, float]]:
        """Host copy of the live (key, value) pairs, ascending (one
        fetch; test/debug)."""
        keys, vals, size = _host_fetch((self.state.keys, self.state.vals,
                                        self.state.size))
        out: List[Tuple[float, float]] = []
        for k in range(self.n_shards):
            n = int(size[k])
            out.extend(zip(keys[k, :n].tolist(), vals[k, :n].tolist()))
        return sorted(out)


class BatchedMap(ShardedMap):
    """Single-shard convenience wrapper (the §13 core structure)."""

    def __init__(self, capacity: int, c_max: int, items=None,
                 use_pallas: bool = False, donate: bool = True,
                 fault_plan=None, guard=None, placement=None):
        super().__init__(capacity, c_max=c_max, n_shards=1, items=items,
                         use_pallas=use_pallas, donate=donate,
                         fault_plan=fault_plan, guard=guard,
                         placement=placement)


# ---------------------------------------------------------------------------
# Registration (DESIGN.md §16) — factories + op generators + adaptive hooks
# ---------------------------------------------------------------------------
from . import read_opt as _read_opt
from .seq_map import SequentialSortedMap

_KEY_RANGE = (0.0, 100.0)


def _gen_update(rng, k, ctx):
    """Pool-biased mixed batches: 60% revisit a known key (so deletes and
    assigns actually hit), insert/assign/delete at 50/25/25."""
    pool = ctx.setdefault("keys", [])
    methods, inputs = [], []
    for _ in range(k):
        if pool and rng.random() < 0.6:
            key = pool[int(rng.integers(len(pool)))]
        else:
            key = _qkey(float(rng.uniform(_KEY_RANGE[0], _KEY_RANGE[1])))
            pool.append(key)
        r = rng.random()
        if r < 0.5:
            methods.append("insert")
            inputs.append((key, _qval(float(rng.uniform(-50.0, 50.0)))))
        elif r < 0.75:
            methods.append("assign")
            inputs.append((key, _qval(float(rng.uniform(-50.0, 50.0)))))
        else:
            methods.append("delete")
            inputs.append(key)
    return methods, inputs


def _gen_read(rng, k, ctx):
    pool = ctx.setdefault("keys", [])
    methods, inputs = [], []
    for _ in range(k):
        r = rng.random()
        if r < 0.35 and pool:
            methods.append("lookup")
            inputs.append(pool[int(rng.integers(len(pool)))])
        elif r < 0.5:
            methods.append("lookup")
            inputs.append(_qkey(float(rng.uniform(_KEY_RANGE[0],
                                                  _KEY_RANGE[1]))))
        elif r < 0.7:
            lo, hi = sorted((float(rng.uniform(*_KEY_RANGE)),
                             float(rng.uniform(*_KEY_RANGE))))
            methods.append("range_count")
            inputs.append((_qkey(lo), _qkey(hi)))
        elif r < 0.85:
            lo, hi = sorted((float(rng.uniform(*_KEY_RANGE)),
                             float(rng.uniform(*_KEY_RANGE))))
            methods.append("range_sum")
            inputs.append((_qkey(lo), _qkey(hi)))
        else:
            methods.append("kth_smallest")
            inputs.append(int(rng.integers(1, 21)))
    return methods, inputs


def _result_ok(method: str, got: Any, want: Any) -> bool:
    if method == "range_sum":
        return abs(got - want) <= 1e-3 + 1e-5 * abs(want)
    if method in ("lookup", "kth_smallest"):
        if got is None or want is None:
            return got is None and want is None
        return abs(got - want) <= 1e-6 * max(1.0, abs(want))
    return got == want


def _refusal_batch(ds: ShardedMap):
    """capacity + 1 distinct keys packed into the lowest quarter of shard
    0's key range: every one routes to shard 0, so the batch must be
    refused whatever the other shards hold."""
    lo, hi = ds.key_range if ds.key_range else _KEY_RANGE
    sliver = lo + (hi - lo) / (4.0 * ds.n_shards)
    n = ds.capacity + 1
    ks = [_qkey(float(x)) for x in
          np.linspace(lo, sliver, num=4 * n).tolist()]
    ks = sorted(set(ks))[:n]
    assert len(ks) == n
    return (["insert"] * n, [(k, 1.0) for k in ks])


def _make(capacity: int = 256, c_max: int = 8, n_shards: int = 4,
          **kw) -> ShardedMap:
    kw.setdefault("key_range", _KEY_RANGE)
    return ShardedMap(capacity, c_max=c_max, n_shards=n_shards, **kw)


def _dump_compare(ds: ShardedMap, oracle) -> None:
    got, want = ds.items(), oracle.items()
    assert len(got) == len(want), (got, want)
    if got:
        gk, gv = zip(*got)
        wk, wv = zip(*want)
        assert np.allclose(gk, wk) and np.allclose(gv, wv), (got, want)


substrate.register(substrate.StructureSpec(
    name="map",
    module="repro.core.batched_map",
    title="batched ordered map",
    make=_make,
    make_host=lambda ds: SequentialSortedMap(ds.items()),
    gen_update=_gen_update,
    gen_read=_gen_read,
    result_ok=_result_ok,
    dump_compare=_dump_compare,
    canon=_read_opt._canon_map_op,
    compact=_read_opt._compact_map,
    refusal_batch=_refusal_batch,
    megapass=True,
    bench="benchmarks.bench_map",
    bench_smoke=("--keys", "1000", "--reads", "50", "100",
                 "--threads", "1", "4", "--ops", "60",
                 "--impls", "FC host", "PC-K1", "PC-K4",
                 "PC-K4 megapass", "PC-K4 alternating"),
    extras={"serve_kw": dict(capacity=512, c_max=64, n_shards=4),
            # ctor accepts placement= (DESIGN.md §18); serve.py keys
            # --mesh-shards eligibility off this marker, and the
            # placement tests pin it to the class attribute
            "placement": True},
))
