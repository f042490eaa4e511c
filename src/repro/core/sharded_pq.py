"""Sharded batched priority queue (DESIGN.md §9–§10) — K heaps, ONE dispatch.

The §4 batched heap applies a combined batch of ``|E|`` ExtractMin +
``|I|`` Insert in ``O(c log c + log n)`` parallel time, but a single heap
caps the payoff at one combining pass in flight at a time.  Following the
sharding recipe of batch-parallel search trees (Lim's 2-3 trees partition
batches by key range; Calciu et al.'s adaptive PQ grows combining capacity
with load), we stack **K independent ``HeapState`` shards on a leading
axis** and apply one combined batch across all of them as a single jitted
XLA program:

1. **route** — inserts are assigned to shards by a bit-mix hash of their
   key (default; load-balancing) or by a fixed key range (``key_range=``,
   the Lim-style partition), entirely inside the jitted program;
2. **frontier merge** — every shard's ``min(|E|, size_k)`` smallest nodes
   are found with the §4 Dijkstra-like frontier search (read-only)
   and the K candidate lists are merged by one global sort; the first
   ``|E|`` finite entries decide the per-shard extract counts ``e_k``;
3. **batch-apply** — phases 1–4 of the §4 algorithm run on all K
   shards simultaneously, each shard extracting its ``e_k`` minima and
   absorbing its routed inserts;
4. **answer merge** — the K per-shard extract lists are merged by one sort;
   the first ``k_eff = min(|E|, Σ size_k)`` values are the batch answer, in
   ascending order, exactly the single-heap (and ``SequentialHeap``
   oracle) semantics.

``use_pallas=True`` (DESIGN.md §10) runs phases 1, 3 and 4 as shard-grid
Pallas kernels over ``grid=(K,)`` (``kernels/heap_kmin``, ``heap_sift``,
``heap_insert``) — the whole K-shard pass stays one fused device program
with per-shard heap blocks in VMEM; ``use_pallas=False`` vmaps the pure-XLA
phase helpers instead (the semantics twin).  Either way the jitted entry
point **donates the heap state**, so the (K, capacity) arrays update in
place instead of being copied every pass.

Correctness: the global |E| smallest keys of the union are a subset of the
union of per-shard |E|-smallest candidate lists, so step 2's merge picks
exactly the right multiset; step 3 then extracts precisely those nodes
because each shard's frontier search is deterministic.  Insert routing is
an arbitrary partition — extraction always merges across shards, so ANY
deterministic routing preserves set semantics (fuzzed against the
sequential oracle, including batches larger than the live size).

Cost: the paper's single-heap pass is one ``O(c log c + log n)`` program;
here K such passes run as one program of the same depth — K concurrent
combining passes for the price of one dispatch.
"""
from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels._rows import require_heap_fits

from . import batched_pq as _bpq
from . import placement as _placement
from . import substrate
from .faults import make_guard
from .batched_pq import (
    INF,
    _TINY,
    AsyncBatchResult,
    RoundResult,
    _RoundsFetch,
    _chunk_len,
    _flush_subnormals,
    _k_smallest,
    _phase4_xla,
    _phases12,
    _sift_wavefront,
    apply_sliced_async,
    expand_rounds,
    require_finite_keys,
)


def host_key(x: float) -> float:
    """Quantize a host float to the exact f32 key the device heap stores.

    Applies f32 rounding, the device's flush-to-zero (DESIGN.md §7) and a
    clamp to the finite f32 range (±inf is the heap's empty-slot
    sentinel), so a key extracted from the device round-trips exactly to
    the host-side value produced here — load-bearing for dict lookups
    keyed on extracted values (the scheduler's persistent request table).
    """
    k = np.float32(x)
    if np.isnan(k):
        raise ValueError("key must not be NaN")
    if not np.isfinite(k):
        big = np.finfo(np.float32).max
        k = np.float32(big) if k > 0 else np.float32(-big)
    if abs(k) < _TINY:
        k = np.float32(0.0)
    return float(k)


class ShardedHeapState(NamedTuple):
    """K 1-indexed array heaps stacked on the leading axis."""

    a: jax.Array      # (K, capacity) float32, +inf marks empty slots
    size: jax.Array   # (K,) int32


# ---------------------------------------------------------------------------
# Insert routing — hash (default) or key-range (Lim-style partition).
# Each rule has a bit-exact numpy twin so the host wrapper can mirror the
# device's shard assignment WITHOUT a device round-trip (the overflow guard
# below runs sync-free, DESIGN.md §10).
# ---------------------------------------------------------------------------
def route_hash(vals: jax.Array, n_shards: int) -> jax.Array:
    """Shard id per value via a Fibonacci bit-mix of the f32 bit pattern."""
    bits = jax.lax.bitcast_convert_type(vals.astype(jnp.float32),
                                        jnp.uint32)
    h = bits * jnp.uint32(2654435761)
    h = h ^ (h >> 16)
    return (h % jnp.uint32(n_shards)).astype(jnp.int32)


def route_range(vals: jax.Array, n_shards: int,
                lo: float, hi: float) -> jax.Array:
    """Shard id per value by equal-width key range over [lo, hi)."""
    span = max(hi - lo, 1e-30)
    idx = jnp.floor((vals - lo) / span * n_shards).astype(jnp.int32)
    return jnp.clip(idx, 0, n_shards - 1)


def _route(vals: jax.Array, n_shards: int,
           key_range: Optional[Tuple[float, float]]) -> jax.Array:
    if key_range is None:
        return route_hash(vals, n_shards)
    return route_range(vals, n_shards, key_range[0], key_range[1])


def _flush_host(vals) -> np.ndarray:
    v = np.asarray(vals, np.float32)
    return np.where(np.abs(v) < _TINY, np.float32(0.0), v)


def route_hash_host(vals, n_shards: int) -> np.ndarray:
    """Numpy twin of :func:`route_hash` (bit-exact: uint32 wrap-around)."""
    bits = _flush_host(vals).view(np.uint32)
    with np.errstate(over="ignore"):
        h = bits * np.uint32(2654435761)
    h = h ^ (h >> np.uint32(16))
    return (h % np.uint32(n_shards)).astype(np.int32)


def route_range_host(vals, n_shards: int, lo: float, hi: float) -> np.ndarray:
    """Numpy twin of :func:`route_range` (same f32 arithmetic).

    The clip happens in FLOAT space before the int cast: XLA's f32→s32
    convert saturates out-of-range keys to ±INT32_MAX (→ clip to the edge
    shard) while numpy's cast wraps — clipping first makes both agree.
    """
    span = np.float32(max(hi - lo, 1e-30))
    v = _flush_host(vals)
    idx = np.floor((v - np.float32(lo)) / span * np.float32(n_shards))
    return np.clip(idx, 0, n_shards - 1).astype(np.int32)


def _route_host(vals, n_shards: int,
                key_range: Optional[Tuple[float, float]]) -> np.ndarray:
    if key_range is None:
        return route_hash_host(vals, n_shards)
    return route_range_host(vals, n_shards, key_range[0], key_range[1])


# ---------------------------------------------------------------------------
# One combined batch over all K shards — a single jitted XLA program
# ---------------------------------------------------------------------------
def _sharded_apply_batch(
    state: ShardedHeapState, n_extract: jax.Array,
    insert_vals: jax.Array, n_insert: jax.Array,
    *, c_max: int, n_shards: int,
    key_range: Optional[Tuple[float, float]] = None,
    use_pallas: bool = False, placement=None,
) -> Tuple[ShardedHeapState, jax.Array, jax.Array]:
    """Apply one combined batch of ≤ c_max extracts + ≤ c_max inserts.

    Returns (new_state, extracted (c_max,) ascending +inf-padded, k_eff)
    where k_eff = min(n_extract, Σ size_k).

    ``placement`` (static): ``None`` traces the single-device program
    below; a ``MeshPlacement`` dispatches to the shard_map twin whose
    K-way merges are collectives (DESIGN.md §18).
    """
    if placement is not None and placement.is_mesh:
        return _mesh_apply_batch(
            state, n_extract, insert_vals, n_insert, c_max=c_max,
            n_shards=n_shards, key_range=key_range, placement=placement)
    K = n_shards
    a, size = state
    cap = a.shape[1]
    max_depth = int(np.ceil(np.log2(cap))) + 1
    lane = jnp.arange(c_max, dtype=jnp.int32)

    n_extract = jnp.minimum(jnp.int32(n_extract), c_max)
    n_insert = jnp.minimum(jnp.int32(n_insert), c_max)
    insert_vals = _flush_subnormals(insert_vals.astype(jnp.float32))
    ins_valid = lane < n_insert

    # -- 1. route inserts to shards (invalid lanes park on shard 0 masked out)
    shard_of = jnp.where(ins_valid, _route(insert_vals, K, key_range), 0)
    # per-shard dense rows: row k holds shard-k inserts sorted ascending
    one_hot = (shard_of[None, :] == jnp.arange(K)[:, None]) & ins_valid[None, :]
    ins_rows = jnp.sort(jnp.where(one_hot, insert_vals[None, :], INF), axis=1)
    ins_counts = jnp.sum(one_hot, axis=1).astype(jnp.int32)

    # -- 2. per-shard frontier candidates (read-only) + global merge.
    # use_pallas: ONE grid=(K,) kernel instead of a vmapped c_max-step scan.
    if use_pallas:
        from repro.kernels.heap_kmin import k_smallest_sharded as _kmin_k
        cand_ids, cand_vals = _kmin_k(a, size, n_extract, c_max=c_max)
    else:
        cand_ids, cand_vals = jax.vmap(
            lambda ak, sk: _k_smallest(ak, sk, n_extract, c_max)
        )(a, size)                                       # (K, c_max) each
    flat_vals = cand_vals.reshape(-1)                    # (K*c_max,)
    flat_shard = jnp.repeat(jnp.arange(K, dtype=jnp.int32), c_max)
    order = jnp.argsort(flat_vals)                       # stable
    chosen = (jnp.arange(K * c_max) < n_extract) & jnp.isfinite(
        flat_vals[order])
    e_counts = jax.ops.segment_sum(
        chosen.astype(jnp.int32), flat_shard[order], num_segments=K)

    # -- 3. phases 1–2 on every shard (vmapped XLA — scatter-heavy, cheap).
    # The frontier scan is deterministic and prefix-stable, so the first
    # e_k lanes of the step-2 candidates ARE shard k's phase-1 result —
    # mask and reuse them instead of re-running the O(c log c) search.
    def prep(ak, sk, ek, row, ik, ids_k, vals_k):
        lane_k = jnp.arange(c_max, dtype=jnp.int32)
        p1 = (jnp.where(lane_k < ek, ids_k, 0),
              jnp.where(lane_k < ek, vals_k, INF))
        return _phases12(ak, sk, ek, row, ik, c_max=c_max, phase1=p1)

    a2, size2, out_rows, _k_eff_k, starts, active, rem, m_left = jax.vmap(
        prep)(a, size, e_counts, ins_rows, ins_counts, cand_ids, cand_vals)

    # -- 3b. sift wavefront + collective inserts on every shard: either the
    # shard-grid kernels (one launch each, per-shard heap block in VMEM) or
    # the vmapped pure-XLA twins.
    if use_pallas:
        from repro.kernels.heap_insert import insert_chunk_sharded as _ins_k
        from repro.kernels.heap_sift import sift_wavefront_sharded as _sift_k
        from repro.kernels._rows import from_rows, to_rows
        # convert to the kernel's row layout with the insert headroom ONCE
        # and carry it through the loop (re-padding per chunk would copy
        # the whole heap stack max_depth times — exactly the per-pass copy
        # donation removes)
        a3 = to_rows(_sift_k(a2, size2, starts, active),
                     min_width=cap + c_max, min_rows=2)

        # K-vector twin of batched_pq._phase4's chunk loop — the level-
        # boundary math is the shared elementwise _chunk_len
        def chunk(_, carry):
            ac, sz, off, left = carry
            m = _chunk_len(sz, left)                         # (K,)
            idx = jnp.clip(off[:, None] + lane[None, :], 0, c_max - 1)
            vals = jnp.where(lane[None, :] < m[:, None],
                             jnp.take_along_axis(rem, idx, axis=1), INF)
            ac, sz = _ins_k(ac, sz, vals, m, pre_padded=True)
            return (ac, sz, off + m, left - m)

        zeros = jnp.zeros((K,), jnp.int32)
        new_a, new_size, _, _ = jax.lax.fori_loop(
            0, max_depth + 1, chunk, (a3, size2, zeros, m_left))
        new_a = from_rows(new_a, cap)
    else:
        a3 = jax.vmap(_sift_wavefront)(a2, size2, starts, active)
        new_a, new_size = jax.vmap(
            lambda ak, sk, rk, mk: _phase4_xla(
                ak, sk, rk, mk, c_max=c_max, max_depth=max_depth)
        )(a3, size2, rem, m_left)

    # -- 4. merge the per-shard answers (ascending, +inf padded)
    merged = jnp.sort(out_rows.reshape(-1))[:c_max]
    k_eff = jnp.minimum(n_extract, jnp.sum(size))
    return ShardedHeapState(new_a, new_size), merged, k_eff


_STATIC = ("c_max", "n_shards", "key_range", "use_pallas", "placement")
# ``state`` is DONATED — the (K, capacity) heap stack updates in place
# (DESIGN.md §10); callers must not reuse a state after passing it in.
sharded_apply_batch = jax.jit(_sharded_apply_batch, static_argnames=_STATIC,
                              donate_argnums=(0,))
# Ablation twin (EXPERIMENTS §Ablations): no donation, copy per pass.
sharded_apply_batch_undonated = jax.jit(_sharded_apply_batch,
                                        static_argnames=_STATIC)


# ---------------------------------------------------------------------------
# Device command queue (DESIGN.md §12): R rounds, ONE dispatch
# ---------------------------------------------------------------------------
def _sharded_rounds_impl(
    state: ShardedHeapState, n_extracts: jax.Array,
    insert_rows: jax.Array, n_inserts: jax.Array,
    *, c_max: int, n_shards: int,
    key_range: Optional[Tuple[float, float]] = None,
    use_pallas: bool = False, placement=None,
) -> Tuple[ShardedHeapState, jax.Array, jax.Array]:
    """R sequential K-shard combined batches as ONE ``lax.scan`` program.

    Each scan step is the full :func:`_sharded_apply_batch` trace (route →
    frontier merge → phases 1–4 on all K shards → answer merge); the
    shard-grid Pallas kernels compose under the scan unchanged.  Returns
    ``(state, outs (R, c_max), k_effs (R,))``.  Under a ``MeshPlacement``
    the scan moves INSIDE one shard_map body — R rounds stay one
    dispatch AND one collective program (DESIGN.md §18).
    """
    if placement is not None and placement.is_mesh:
        return _mesh_rounds(
            state, n_extracts, insert_rows, n_inserts, c_max=c_max,
            n_shards=n_shards, key_range=key_range, placement=placement)

    def body(st, rnd):
        ne, vals, ni = rnd
        st, out, k_eff = _sharded_apply_batch(
            st, ne, vals, ni, c_max=c_max, n_shards=n_shards,
            key_range=key_range, use_pallas=use_pallas)
        return st, (out, k_eff)

    state, (outs, k_effs) = jax.lax.scan(
        body, state, (n_extracts, insert_rows, n_inserts))
    return state, outs, k_effs


sharded_apply_rounds = jax.jit(_sharded_rounds_impl, static_argnames=_STATIC,
                               donate_argnums=(0,))
sharded_apply_rounds_undonated = jax.jit(_sharded_rounds_impl,
                                         static_argnames=_STATIC)


# ---------------------------------------------------------------------------
# Fused mixed update+read megapass (DESIGN.md §17)
# ---------------------------------------------------------------------------
MEGA_UPDATE, MEGA_READ = 0, 1


def _peek_min_impl(state: ShardedHeapState, n_extract: jax.Array,
                   *, c_max: int, n_shards: int,
                   use_pallas: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Read-only twin of :func:`_sharded_apply_batch` steps 1–2: the
    per-shard frontier candidates and the global merge, WITHOUT the
    extraction phases — so a ``peek_min`` round can ride the mixed scan
    with zero state mutation.  Returns ``(merged (c_max,) ascending
    +inf-padded, k_eff)``: the ``n_extract`` globally smallest keys."""
    a, size = state
    n_extract = jnp.minimum(jnp.int32(n_extract), c_max)
    if use_pallas:
        from repro.kernels.heap_kmin import k_smallest_sharded as _kmin_k
        _ids, cand_vals = _kmin_k(a, size, n_extract, c_max=c_max)
    else:
        _ids, cand_vals = jax.vmap(
            lambda ak, sk: _k_smallest(ak, sk, n_extract, c_max)
        )(a, size)                                       # (K, c_max)
    flat = jnp.sort(cand_vals.reshape(-1))[:c_max]
    merged = jnp.where(jnp.arange(c_max) < n_extract, flat, INF)
    k_eff = jnp.minimum(n_extract, jnp.sum(size))
    return merged, k_eff


def _sharded_mixed_impl(
    state: ShardedHeapState, tags: jax.Array, n_extracts: jax.Array,
    insert_rows: jax.Array, n_inserts: jax.Array,
    *, c_max: int, n_shards: int,
    key_range: Optional[Tuple[float, float]] = None,
    use_pallas: bool = False, placement=None,
) -> Tuple[ShardedHeapState, jax.Array, jax.Array]:
    """R heterogeneous combining rounds as ONE donated scan program.

    ``tags`` (R,) int32 selects per row between the full combined batch
    (``MEGA_UPDATE``: the :func:`_sharded_apply_batch` trace) and the
    read-only frontier merge (``MEGA_READ``: :func:`_peek_min_impl`,
    ``n_extracts`` doubling as the peek width) inside a ``lax.cond`` —
    interleaved update and peek rounds cost one dispatch instead of one
    each.  Returns ``(state, outs (R, c_max), k_effs (R,))``."""
    if placement is not None and placement.is_mesh:
        return _mesh_mixed(
            state, tags, n_extracts, insert_rows, n_inserts, c_max=c_max,
            n_shards=n_shards, key_range=key_range, placement=placement)

    def body(st, rnd):
        tag, ne, vals, ni = rnd

        def upd(s):
            s2, out, k_eff = _sharded_apply_batch(
                s, ne, vals, ni, c_max=c_max, n_shards=n_shards,
                key_range=key_range, use_pallas=use_pallas)
            return s2, (out, k_eff)

        def rd(s):
            out, k_eff = _peek_min_impl(s, ne, c_max=c_max,
                                        n_shards=n_shards,
                                        use_pallas=use_pallas)
            return s, (out, k_eff)

        st, out = jax.lax.cond(tag == MEGA_READ, rd, upd, st)
        return st, out

    state, (outs, k_effs) = jax.lax.scan(
        body, state, (tags, n_extracts, insert_rows, n_inserts))
    return state, outs, k_effs


sharded_mixed_rounds = jax.jit(_sharded_mixed_impl, static_argnames=_STATIC,
                               donate_argnums=(0,))
sharded_mixed_rounds_undonated = jax.jit(_sharded_mixed_impl,
                                         static_argnames=_STATIC)


# ---------------------------------------------------------------------------
# Mesh placement (DESIGN.md §18): the K shard rows live on D devices
# ---------------------------------------------------------------------------
# Under a MeshPlacement each device holds K_local = K/D whole shard rows
# of the (K, capacity) heap stack, and the combined batch runs as a
# shard_map body: routing and the global candidate merge are computed
# REPLICATED on every device (they are O(K·c_max), tiny), the per-shard
# phases 1–4 run on the local rows only (the actual O(c log c + log n)
# work — this is what scale-out parallelizes), and the two K-way merges
# become collectives:
#
#   * frontier merge  — all_gather of the (K_local, c_max) candidate
#     lists; device-major × row-major gather order makes global shard k
#     = d·K_local + j, exactly the stacked flat order, so the merge
#     sort, `chosen` mask and per-shard extract counts are bit-identical
#     to the stacked trace;
#   * answer merge    — all_gather of the per-shard extract rows + the
#     same global sort;  k_eff's Σ size_k is a psum.
#
# Every device computes the same routing/e_counts from the same
# replicated inputs, so no device ever disagrees on who extracts what —
# the explicit-synchronization claim of the paper, now on real devices.
# The Pallas shard-grid kernels assume the whole (K, capacity) stack in
# one address space, so use_pallas composes with StackedPlacement only
# (the wrapper refuses the combination at construction).
from jax.sharding import PartitionSpec as _P


def _mesh_batch_body(a, size, n_extract, insert_vals, n_insert,
                     *, c_max: int, n_shards: int, key_range, axis: str):
    """One combined batch on the LOCAL K/D shard rows + collectives."""
    K = n_shards
    K_local, cap = a.shape
    max_depth = int(np.ceil(np.log2(cap))) + 1
    lane = jnp.arange(c_max, dtype=jnp.int32)
    base = jax.lax.axis_index(axis) * K_local

    n_extract = jnp.minimum(jnp.int32(n_extract), c_max)
    n_insert = jnp.minimum(jnp.int32(n_insert), c_max)
    insert_vals = _flush_subnormals(insert_vals.astype(jnp.float32))
    ins_valid = lane < n_insert

    # -- 1. route against GLOBAL shard ids; keep only the local rows
    shard_of = jnp.where(ins_valid, _route(insert_vals, K, key_range), 0)
    local_ids = base + jnp.arange(K_local, dtype=jnp.int32)
    one_hot = (shard_of[None, :] == local_ids[:, None]) & ins_valid[None, :]
    ins_rows = jnp.sort(jnp.where(one_hot, insert_vals[None, :], INF), axis=1)
    ins_counts = jnp.sum(one_hot, axis=1).astype(jnp.int32)

    # -- 2. local frontier candidates; global merge over the gathered
    # lists (device-major order == stacked shard order, see above)
    cand_ids, cand_vals = jax.vmap(
        lambda ak, sk: _k_smallest(ak, sk, n_extract, c_max))(a, size)
    flat_vals = jax.lax.all_gather(cand_vals, axis).reshape(-1)  # (K*c_max,)
    flat_shard = jnp.repeat(jnp.arange(K, dtype=jnp.int32), c_max)
    order = jnp.argsort(flat_vals)
    chosen = (jnp.arange(K * c_max) < n_extract) & jnp.isfinite(
        flat_vals[order])
    e_counts = jax.ops.segment_sum(
        chosen.astype(jnp.int32), flat_shard[order], num_segments=K)
    e_local = jax.lax.dynamic_slice(e_counts, (base,), (K_local,))

    # -- 3. phases 1–4 on the local shard rows (the vmapped XLA helpers,
    # unchanged — the same per-shard trace the stacked program vmaps)
    def prep(ak, sk, ek, row, ik, ids_k, vals_k):
        lane_k = jnp.arange(c_max, dtype=jnp.int32)
        p1 = (jnp.where(lane_k < ek, ids_k, 0),
              jnp.where(lane_k < ek, vals_k, INF))
        return _phases12(ak, sk, ek, row, ik, c_max=c_max, phase1=p1)

    a2, size2, out_rows, _k_eff_k, starts, active, rem, m_left = jax.vmap(
        prep)(a, size, e_local, ins_rows, ins_counts, cand_ids, cand_vals)
    a3 = jax.vmap(_sift_wavefront)(a2, size2, starts, active)
    new_a, new_size = jax.vmap(
        lambda ak, sk, rk, mk: _phase4_xla(
            ak, sk, rk, mk, c_max=c_max, max_depth=max_depth)
    )(a3, size2, rem, m_left)

    # -- 4. collective answer merge + global size total
    merged = jnp.sort(jax.lax.all_gather(out_rows, axis).reshape(-1))[:c_max]
    k_eff = jnp.minimum(n_extract, jax.lax.psum(jnp.sum(size), axis))
    return new_a, new_size, merged, k_eff


def _mesh_peek_body(a, size, n_extract, *, c_max: int, axis: str):
    """Collective twin of :func:`_peek_min_impl`: local frontier
    candidates, gathered and merge-sorted on every device."""
    n_extract = jnp.minimum(jnp.int32(n_extract), c_max)
    _ids, cand_vals = jax.vmap(
        lambda ak, sk: _k_smallest(ak, sk, n_extract, c_max))(a, size)
    flat = jnp.sort(jax.lax.all_gather(cand_vals, axis).reshape(-1))[:c_max]
    merged = jnp.where(jnp.arange(c_max) < n_extract, flat, INF)
    k_eff = jnp.minimum(n_extract, jax.lax.psum(jnp.sum(size), axis))
    return merged, k_eff


def _mesh_specs(placement):
    ax = placement.axis
    state_in = (_P(ax, None), _P(ax))
    return ax, state_in


def _mesh_apply_batch(state, n_extract, insert_vals, n_insert,
                      *, c_max: int, n_shards: int, key_range, placement):
    ax, st_specs = _mesh_specs(placement)

    def body(a, size, ne, vals, ni):
        return _mesh_batch_body(a, size, ne, vals, ni, c_max=c_max,
                                n_shards=n_shards, key_range=key_range,
                                axis=ax)

    fn = jax.shard_map(body, mesh=placement.mesh,
                       in_specs=st_specs + (_P(), _P(), _P()),
                       out_specs=st_specs + (_P(), _P()),
                       check_vma=False)
    new_a, new_size, merged, k_eff = fn(
        state.a, state.size, n_extract, insert_vals, n_insert)
    return ShardedHeapState(new_a, new_size), merged, k_eff


def _mesh_rounds(state, n_extracts, insert_rows, n_inserts,
                 *, c_max: int, n_shards: int, key_range, placement):
    ax, st_specs = _mesh_specs(placement)

    def body(a, size, ne_arr, bufs, ni_arr):
        def step(carry, rnd):
            a, size = carry
            ne, vals, ni = rnd
            a, size, merged, k_eff = _mesh_batch_body(
                a, size, ne, vals, ni, c_max=c_max, n_shards=n_shards,
                key_range=key_range, axis=ax)
            return (a, size), (merged, k_eff)

        (a, size), (outs, k_effs) = jax.lax.scan(
            step, (a, size), (ne_arr, bufs, ni_arr))
        return a, size, outs, k_effs

    fn = jax.shard_map(body, mesh=placement.mesh,
                       in_specs=st_specs + (_P(), _P(), _P()),
                       out_specs=st_specs + (_P(), _P()),
                       check_vma=False)
    a, size, outs, k_effs = fn(
        state.a, state.size, n_extracts, insert_rows, n_inserts)
    return ShardedHeapState(a, size), outs, k_effs


def _mesh_mixed(state, tags, n_extracts, insert_rows, n_inserts,
                *, c_max: int, n_shards: int, key_range, placement):
    """Mixed megapass under the mesh: the tag cond nests inside the
    shard_map scan — tags are replicated, so every device takes the same
    branch and the branch collectives line up across the mesh."""
    ax, st_specs = _mesh_specs(placement)

    def body(a, size, tags, ne_arr, bufs, ni_arr):
        def step(carry, rnd):
            a, size = carry
            tag, ne, vals, ni = rnd

            def upd(ops):
                return _mesh_batch_body(
                    ops[0], ops[1], ne, vals, ni, c_max=c_max,
                    n_shards=n_shards, key_range=key_range, axis=ax)

            def rd(ops):
                merged, k_eff = _mesh_peek_body(
                    ops[0], ops[1], ne, c_max=c_max, axis=ax)
                return ops[0], ops[1], merged, k_eff

            a, size, merged, k_eff = jax.lax.cond(
                tag == MEGA_READ, rd, upd, (a, size))
            return (a, size), (merged, k_eff)

        (a, size), (outs, k_effs) = jax.lax.scan(
            step, (a, size), (tags, ne_arr, bufs, ni_arr))
        return a, size, outs, k_effs

    fn = jax.shard_map(body, mesh=placement.mesh,
                       in_specs=st_specs + (_P(), _P(), _P(), _P()),
                       out_specs=st_specs + (_P(), _P()),
                       check_vma=False)
    a, size, outs, k_effs = fn(
        state.a, state.size, tags, n_extracts, insert_rows, n_inserts)
    return ShardedHeapState(a, size), outs, k_effs


# ---------------------------------------------------------------------------
# Host-facing wrapper (same interface as BatchedPriorityQueue)
# ---------------------------------------------------------------------------
class _PQBatchHandle:
    """Protocol-shaped view of an :class:`AsyncBatchResult`: per-op
    results in arrival order — ``extract_min`` ops get the batch's
    ascending extracted values (None-padded past the live size, matching
    the oracle's pop order), ``insert`` ops get None."""

    def __init__(self, batch_handle: Optional[AsyncBatchResult],
                 methods: List[str]):
        self._h = batch_handle
        self._methods = methods

    def result(self) -> List[Any]:
        vals = self._h.result() if self._h is not None else []
        out: List[Any] = []
        j = 0
        for m in self._methods:
            if m == "extract_min":
                out.append(vals[j] if j < len(vals) else None)
                j += 1
            else:
                out.append(None)
        return out


class _PQPeekRound:
    """Handle for one ``peek_min`` read round of a megapass: every op in
    the round observes the same linearization point, so each answers THE
    global minimum at that point (None when empty).  Resolution shares
    the dispatch's one :class:`_RoundsFetch` transfer."""

    def __init__(self, shared: Optional[_RoundsFetch], row_id: int,
                 n_ops: int):
        self._shared = shared
        self._row = row_id
        self._n = n_ops

    def result(self) -> List[Any]:
        if not self._n:
            return []
        v = float(self._shared.rows()[self._row][0])
        return [v if np.isfinite(v) else None] * self._n


class ShardedBatchedPQ(substrate.BatchedStructure):
    """K-sharded device-resident PQ with combined batch application.

    Args:
      capacity: per-shard heap capacity (slot 0 is scratch, as in §4).
      c_max: combined-batch capacity per apply (compile-time constant).
      n_shards: number of independent heap shards (K).
      values: optional initial values, routed with the same rule as inserts.
      key_range: optional (lo, hi) — route by key range instead of hash.
      use_pallas: run phases 1/3/4 as shard-grid Pallas kernels
        (``grid=(K,)``, DESIGN.md §10) instead of vmapped XLA.
      donate: dispatch through the donating jit (zero-copy pass, default);
        False is the copy-per-pass ablation twin.
      fault_plan: optional :class:`~repro.core.faults.FaultPlan` whose
        ``maybe_fail_dispatch`` probe fires after every device dispatch.
      guard: transactional dispatch (DESIGN.md §15) — a ready
        ``DispatchGuard``, ``True`` (guard without a plan: the fault-free
        overhead row), or ``None`` (guard exactly when a plan is given).
        Guarded dispatches snapshot the heap stack + occupancy mirror,
        restore bit-identically on failure and retry with backoff.
      placement: shard layout (DESIGN.md §18) — ``None``/
        ``StackedPlacement`` keeps all K rows on one device (the
        original trace, bit-exact); ``MeshPlacement`` splits them
        across a 1-D mesh and runs the fused passes under shard_map.
        Requires ``K % D == 0`` and composes with everything above —
        the occupancy guard's per-shard bounds ARE per-device bounds,
        snapshots ``.copy()`` preserve the sharding, donation reuses
        the per-device buffers in place — but not with ``use_pallas``
        (the shard-grid kernels assume a single address space).

    Sync-free occupancy guard (DESIGN.md §10): the wrapper mirrors the
    device's insert routing on the host (bit-exact numpy twins) and keeps
    per-shard occupancy *upper bounds* plus the *exact* total size, so the
    per-slice overflow check never reads a device value.  Same-slice
    extracts are credited with the guaranteed lower bound
    ``e_k ≥ min(ne, total) - Σ_{j≠k} size_j`` (the global ne smallest must
    come from somewhere).  The bounds re-tighten to the true sizes at
    every consumed ``result()``: the sizes are read at consumption time,
    so they reflect exactly the slices the mirror has accounted — correct
    under pipelined (one-pass-behind) consumption too.  The wrapper is
    not thread-safe; confine each instance to one thread (the scheduler's
    combiner loop does).
    """

    structure = "pq"
    read_only: Set[str] = {"values", "peek_min"}
    supports_megapass = True
    supports_placement = True

    def __init__(self, capacity: int, c_max: int, n_shards: int = 4,
                 values=None, key_range: Optional[Tuple[float, float]] = None,
                 use_pallas: bool = False, donate: bool = True,
                 fault_plan=None, guard=None, placement=None):
        if c_max < 1:
            raise ValueError("c_max must be >= 1")
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        self.c_max = int(c_max)
        self.capacity = int(capacity)
        self.n_shards = int(n_shards)
        self.use_pallas = bool(use_pallas)
        self.donate = bool(donate)
        self.placement = _placement.resolve_placement(placement)
        self.placement.validate(self.n_shards)
        self._pstatic = _placement.as_static(self.placement)
        if self._pstatic is not None and self.use_pallas:
            raise ValueError(
                "use_pallas is not supported under MeshPlacement: the "
                "shard-grid kernels assume the whole (K, capacity) stack "
                "in one device's address space (DESIGN.md §18)")
        if self.use_pallas:
            require_heap_fits(self.capacity)
        self.key_range = (
            (float(key_range[0]), float(key_range[1]))
            if key_range is not None else None)
        self.fault_plan = fault_plan
        self._guard = make_guard(fault_plan, guard)
        self.state = self.placement.put(self._init_state(values))

    def _init_state(self, values) -> ShardedHeapState:
        K, cap = self.n_shards, self.capacity
        a = np.full((K, cap), np.inf, np.float32)
        size = np.zeros((K,), np.int32)
        values = list(values) if values is not None else []
        if values:
            require_finite_keys(values)
            vals = _flush_host(values)
            shards = _route_host(vals, K, self.key_range)
            for k in range(K):
                mine = np.sort(vals[shards == k])
                if mine.size + 1 > cap:
                    raise ValueError("per-shard capacity too small")
                # a sorted array satisfies the heap property
                a[k, 1 : mine.size + 1] = mine
                size[k] = mine.size
        # host occupancy mirror: exact at init, upper bounds between syncs
        self._sizes_ub = size.astype(np.int64).copy()
        self._total = int(size.sum())
        return ShardedHeapState(jnp.asarray(a), jnp.asarray(size))

    def __len__(self) -> int:
        return int(np.sum(np.asarray(self.state.size)))

    def _refresh_sizes(self, sizes) -> None:
        """Replace the occupancy mirror with fetched true sizes.  The
        fetch thunk reads ``self.state.size`` at consumption time, so the
        values correspond exactly to the slices already accounted by
        :meth:`_guard_and_account` — the refresh is exact, never stale."""
        self._sizes_ub = np.asarray(sizes, np.int64).copy()
        self._total = int(self._sizes_ub.sum())

    def _guard_and_account(self, ne: int, buf: np.ndarray, ni: int) -> None:
        """Sync-free per-slice overflow guard + host mirror update."""
        K = self.n_shards
        growth = np.zeros((K,), np.int64)
        if ni:
            shards = _route_host(buf[:ni], K, self.key_range)
            growth = np.bincount(shards, minlength=K).astype(np.int64)
        ub = self._sizes_ub
        # guaranteed same-slice extract credit per shard: the min(ne, total)
        # globally smallest keys exist somewhere; at most Σ_{j≠k} size_j of
        # them live outside shard k.  size_k ≥ total - Σ_{j≠k} ub_j.
        take = min(ne, self._total)
        lb = np.maximum(self._total - (ub.sum() - ub), 0)
        credit = np.maximum(take - (self._total - lb), 0)
        peak = ub - credit + growth
        if np.any(peak + 1 > self.capacity):
            # routing skew could overflow one shard while the queue as a
            # whole has room — refuse rather than let the device scatter
            # silently drop keys.
            raise ValueError(
                f"per-shard capacity {self.capacity} exceeded: "
                f"insert routing would grow a shard past it")
        self._sizes_ub = peak
        self._total = self._total + int(growth.sum()) - take

    # -- transactional dispatch (DESIGN.md §15) --------------------------
    def _snapshot(self):
        """Device-side copies (never donated — restore survives the
        failed pass consuming the live buffers) + the host mirror."""
        st = ShardedHeapState(self.state.a.copy(), self.state.size.copy())
        return st, self._sizes_ub.copy(), self._total

    def _restore(self, snap) -> None:
        self.state, self._sizes_ub, self._total = snap

    def _step(self, ne, buf, ni):
        def thunk():
            # the mirror mutation lives INSIDE the guarded thunk so a
            # restore rewinds accounting and device state together
            self._guard_and_account(ne, buf, ni)
            fn = sharded_apply_batch if self.donate \
                else sharded_apply_batch_undonated
            self.state, vals, k_eff = fn(
                self.state, jnp.int32(ne), jnp.asarray(buf), jnp.int32(ni),
                c_max=self.c_max, n_shards=self.n_shards,
                key_range=self.key_range, use_pallas=self.use_pallas,
                placement=self._pstatic)
            return vals, k_eff

        if self._guard is None:
            return thunk()
        return self._guard.run(thunk, self._snapshot, self._restore,
                               site="pq.apply_batch")

    def apply_async(self, extracts: int, inserts) -> AsyncBatchResult:
        """Apply a combined batch; extracted values stay on device until
        ``.result()`` — one blocking host sync per call, not per slice.
        Batches larger than c_max are applied in c_max slices — still one
        device program per slice, K shards each.

        The overflow guard is ATOMIC across slices: every slice is
        pre-validated against the host mirror before ANY slice reaches
        the device, so a refused oversized batch leaves the device
        buffers and the mirror exactly as they were (a mid-loop refusal
        used to strand the already-applied prefix; the guard re-runs per
        dispatched slice and, being deterministic, takes the same
        branches it validated)."""
        inserts = list(inserts)
        require_finite_keys(inserts)
        # expand_rounds slices with the same ne/ni advance rule as
        # apply_sliced_async, so pre-guarding its specs validates exactly
        # the slices the dispatch loop will produce (pad rows are no-ops)
        specs, _ = expand_rounds([(extracts, inserts)], self.c_max)
        saved = (self._sizes_ub.copy(), self._total)
        try:
            for ne, buf, ni in specs:
                self._guard_and_account(ne, buf, ni)
        finally:
            self._sizes_ub, self._total = saved
        # `+ 0` detaches the fetched sizes from self.state.size, which a
        # later apply_async would donate (fetching a donated buffer throws)
        return apply_sliced_async(
            self._step, self.c_max, extracts, inserts,
            extra=lambda: self.state.size + 0,
            on_fetch=self._refresh_sizes)

    def apply(self, extracts: int, inserts) -> list:
        """Apply a combined batch; returns extracted values (None-padded)."""
        return self.apply_async(extracts, inserts).result()

    def apply_rounds_async(self, rounds) -> list:
        """Apply R sequential combined batches with ONE K-shard device
        dispatch (DESIGN.md §12): the rounds are lowered onto ≤ c_max scan
        rows, the sync-free occupancy guard runs per row on the host (in
        scan order — the mirror sees exactly the sequence the device will
        execute), and the donated ``lax.scan`` program applies them all.
        Returns one ``RoundResult`` per round; every round shares the one
        blocking fetch, which also re-tightens the occupancy mirror."""
        specs, layout = expand_rounds(rounds, self.c_max)
        if not specs:
            return [RoundResult(sn, ri, None) for sn, ri in layout]

        def commit():
            # guard the WHOLE command queue before dispatching anything: a
            # refusal must leave the mirror exactly as it was (atomic — no
            # row of a refused queue ever reaches the device)
            for ne, buf, ni in specs:
                self._guard_and_account(ne, buf, ni)
            ne_arr = jnp.asarray(np.array([s[0] for s in specs], np.int32))
            bufs = jnp.asarray(np.stack([s[1] for s in specs]))
            ni_arr = jnp.asarray(np.array([s[2] for s in specs], np.int32))
            fn = sharded_apply_rounds if self.donate \
                else sharded_apply_rounds_undonated
            self.state, outs, _k = fn(
                self.state, ne_arr, bufs, ni_arr, c_max=self.c_max,
                n_shards=self.n_shards, key_range=self.key_range,
                use_pallas=self.use_pallas, placement=self._pstatic)
            return outs

        if self._guard is not None:
            outs = self._guard.run(commit, self._snapshot, self._restore,
                                   site="pq.apply_rounds")
        else:
            saved = (self._sizes_ub.copy(), self._total)
            try:
                outs = commit()
            except ValueError:
                self._sizes_ub, self._total = saved
                raise
        shared = _RoundsFetch(outs, extra=lambda: self.state.size + 0,
                              on_fetch=self._refresh_sizes)
        return [RoundResult(sn, ri, shared) for sn, ri in layout]

    def apply_rounds(self, rounds) -> list:
        """Blocking :meth:`apply_rounds_async`: per-round answer lists."""
        return [h.result() for h in self.apply_rounds_async(rounds)]

    # -- fused mixed update+read megapass (DESIGN.md §17) --------------------
    def mixed_rounds(self, rounds):
        """R heterogeneous update/``peek_min`` rounds as ONE donated scan
        program.  Update rounds lower onto :func:`expand_rounds` rows
        (tag ``MEGA_UPDATE``), each ``peek_min`` round becomes one
        read-only frontier-merge row (tag ``MEGA_READ``), and every
        returned handle shares the dispatch's one blocking fetch.  Read
        rounds containing ``values`` fall back to the base per-round
        dispatch — a whole-heap dump cannot ride a (R, c_max) result
        slot."""
        rounds = [(k, list(m), list(i)) for k, m, i in rounds]
        for kind, methods, _ in rounds:
            if kind not in ("update", "read"):
                raise ValueError(f"unknown round kind {kind!r} "
                                 f"(want 'update' or 'read')")
            if kind == "read" and any(m != "peek_min" for m in methods):
                return substrate.BatchedStructure.mixed_rounds(self, rounds)

        specs: List[Tuple[int, int, np.ndarray, int]] = []
        plans: List[Tuple] = []
        pad_buf = np.full((self.c_max,), np.inf, np.float32)
        for kind, methods, inputs in rounds:
            if kind == "update":
                ne = 0
                ins: List[float] = []
                for m, i in zip(methods, inputs):
                    if m == "insert":
                        ins.append(float(i))
                    elif m == "extract_min":
                        ne += 1
                    else:
                        raise ValueError(f"unknown update method {m!r}")
                sub, layout = expand_rounds([(ne, ins)], self.c_max)
                # strip expand_rounds' per-call pow2 padding (trailing
                # no-op rows) — the megapass pads the GLOBAL row count
                while sub and sub[-1][0] == 0 and sub[-1][2] == 0:
                    sub.pop()
                row_lo = len(specs)
                (slice_ne, row_ids), = layout
                specs.extend((MEGA_UPDATE, ne_r, buf, ni)
                             for ne_r, buf, ni in sub)
                plans.append(("update", slice_ne,
                              [row_lo + r for r in row_ids], methods))
            else:
                if methods:
                    plans.append(("read", len(specs), len(methods)))
                    specs.append((MEGA_READ, 1, pad_buf, 0))
                else:
                    plans.append(("read", None, 0))
        if not specs:
            return [self._empty_round_handle(p) for p in plans]
        # pow2-pad the global row count with no-op PEEK rows (ne=0 reads
        # are pure — padding can never perturb the serial schedule)
        target = 1 << (len(specs) - 1).bit_length()
        while len(specs) < target:
            specs.append((MEGA_READ, 0, pad_buf, 0))

        def commit():
            # guard every update row before dispatching anything (atomic
            # refusal); peek rows never touch the occupancy mirror
            for tag, ne, buf, ni in specs:
                if tag == MEGA_UPDATE:
                    self._guard_and_account(ne, buf, ni)
            tags = jnp.asarray(np.array([s[0] for s in specs], np.int32))
            ne_arr = jnp.asarray(np.array([s[1] for s in specs], np.int32))
            bufs = jnp.asarray(np.stack([s[2] for s in specs]))
            ni_arr = jnp.asarray(np.array([s[3] for s in specs], np.int32))
            fn = sharded_mixed_rounds if self.donate \
                else sharded_mixed_rounds_undonated
            self.state, outs, _k = fn(
                self.state, tags, ne_arr, bufs, ni_arr, c_max=self.c_max,
                n_shards=self.n_shards, key_range=self.key_range,
                use_pallas=self.use_pallas, placement=self._pstatic)
            return outs

        if self._guard is not None:
            outs = self._guard.run(commit, self._snapshot, self._restore,
                                   site="pq.mixed_rounds")
        else:
            saved = (self._sizes_ub.copy(), self._total)
            try:
                outs = commit()
            except ValueError:
                self._sizes_ub, self._total = saved
                raise
        shared = _RoundsFetch(outs, extra=lambda: self.state.size + 0,
                              on_fetch=self._refresh_sizes)
        handles: List[Any] = []
        for plan in plans:
            if plan[0] == "update":
                _, slice_ne, row_ids, methods = plan
                rr = RoundResult(slice_ne, row_ids,
                                 shared if row_ids else None)
                handles.append(_PQBatchHandle(rr, methods))
            else:
                _, row, n_ops = plan
                handles.append(_PQPeekRound(shared if n_ops else None,
                                            row if row is not None else 0,
                                            n_ops))
        return handles

    @staticmethod
    def _empty_round_handle(plan):
        if plan[0] == "update":
            return _PQBatchHandle(None, plan[3])
        return _PQPeekRound(None, 0, 0)

    def values(self) -> list:
        a = np.asarray(self.state.a)
        sizes = np.asarray(self.state.size)
        out: list = []
        for k in range(self.n_shards):
            out.extend(a[k, 1 : sizes[k] + 1].tolist())
        return sorted(out)

    # -- BatchedStructure protocol surface (DESIGN.md §16) --------------------
    # The native combined-batch entry stays ``apply(extracts, inserts)``
    # (the §4 interface the scheduler drives); the protocol's generic
    # single-op entry is ``apply_op``.
    def update_batch_async(self, methods: Sequence[str],
                           inputs: Sequence[Any]) -> _PQBatchHandle:
        """Protocol adapter: a mixed insert/extract_min op list becomes
        ONE combined ``apply_async(ne, inserts)`` batch (extracts see the
        pre-batch multiset, §4 semantics)."""
        ne = 0
        ins: List[float] = []
        for m, i in zip(methods, inputs):
            if m == "insert":
                ins.append(float(i))
            elif m == "extract_min":
                ne += 1
            else:
                raise ValueError(f"unknown update method {m!r}")
        if ne == 0 and not ins:
            return _PQBatchHandle(None, list(methods))
        return _PQBatchHandle(self.apply_async(ne, ins), list(methods))

    def read_batch(self, methods: Sequence[str],
                   inputs: Sequence[Any]) -> List[Any]:
        """Answer ``values`` / ``peek_min`` reads with ONE blocking fetch
        (late-bound through ``batched_pq._host_fetch`` so sync-counting
        tests see it), which also re-tightens the occupancy mirror."""
        for m in methods:
            if m not in ("values", "peek_min"):
                raise ValueError(f"unknown read method {m!r}")
        if not methods:
            return []
        # `+ 0` detaches from buffers the next donated apply would eat
        a, sizes = _bpq._host_fetch((self.state.a + 0,
                                     self.state.size + 0))
        self._refresh_sizes(sizes)
        a = np.asarray(a)
        vals: List[float] = []
        for k in range(self.n_shards):
            vals.extend(a[k, 1 : int(sizes[k]) + 1].tolist())
        vals.sort()
        return [list(vals) if m == "values"
                else (vals[0] if vals else None) for m in methods]

    def apply_op(self, method: str, input: Any = None) -> Any:
        """Generic single-op entry (the protocol's ``apply`` under a
        non-clashing name — ``apply`` keeps the §4 batch signature)."""
        return substrate.BatchedStructure.apply(self, method, input)

    def occupancy_mirror(self):
        return {"sizes_ub": self._sizes_ub, "total": self._total}


# ---------------------------------------------------------------------------
# Registration (DESIGN.md §16)
# ---------------------------------------------------------------------------
class SequentialBatchedPQ:
    """Protocol-shaped PQ oracle/host mirror with the §4 batch rule,
    INCLUDING the slicing rule for oversized batches: one
    ``update_batch`` lowers onto ≤ c_max slices with extracts and
    inserts advancing together (exactly :func:`expand_rounds`), each
    slice's extracts seeing the pre-SLICE multiset, answered ascending
    with per-slice None padding past the live size; inserts return None.
    ``c_max=None`` means one unbounded slice (the pre-batch rule)."""

    read_only: Set[str] = {"values", "peek_min"}

    def __init__(self, values=None, c_max: Optional[int] = None):
        self._v: List[float] = sorted(
            host_key(float(np.float32(v))) for v in (values or []))
        self.c_max = c_max

    def __len__(self) -> int:
        return len(self._v)

    def update_batch(self, methods: Sequence[str],
                     inputs: Sequence[Any]) -> List[Any]:
        ne = 0
        ins: List[float] = []
        for m, i in zip(methods, inputs):
            if m == "insert":
                ins.append(host_key(float(np.float32(i))))
            elif m == "extract_min":
                ne += 1
            else:
                raise ValueError(f"unknown update method {m!r}")
        c = self.c_max if self.c_max is not None else max(1, ne, len(ins))
        take: List[Any] = []
        while ne > 0 or ins:
            k_e, k_i = min(ne, c), min(len(ins), c)
            vals, self._v = self._v[:k_e], self._v[k_e:]
            take.extend(vals)
            take.extend([None] * (k_e - len(vals)))   # empty-queue pads
            self._v = sorted(self._v + ins[:k_i])
            ne -= k_e
            ins = ins[k_i:]
        out: List[Any] = []
        j = 0
        for m in methods:
            if m == "extract_min":
                out.append(take[j])
                j += 1
            else:
                out.append(None)
        return out

    def read_batch(self, methods: Sequence[str],
                   inputs: Sequence[Any]) -> List[Any]:
        for m in methods:
            if m not in ("values", "peek_min"):
                raise ValueError(f"unknown read method {m!r}")
        return [list(self._v) if m == "values"
                else (self._v[0] if self._v else None) for m in methods]

    def apply(self, method: str, input: Any = None) -> Any:
        if method in self.read_only:
            return self.read_batch([method], [input])[0]
        return self.update_batch([method], [input])[0]

    def values(self) -> List[float]:
        return list(self._v)


def _gen_update(rng, k, ctx):
    """Mixed insert/extract batches; inserts draw fresh f32 keys, ~40%
    of lanes extract (crossing the empty-queue boundary regularly)."""
    methods, inputs = [], []
    for _ in range(k):
        if rng.random() < 0.4:
            methods.append("extract_min")
            inputs.append(None)
        else:
            methods.append("insert")
            inputs.append(float(np.float32(rng.uniform(-1000.0, 1000.0))))
    return methods, inputs


def _gen_read(rng, k, ctx):
    return ["values"] * k, [None] * k


def _result_ok(method: str, got: Any, want: Any) -> bool:
    def close(g, w):
        if g is None or w is None:
            return g is None and w is None
        return abs(g - w) <= 1e-6 * max(1.0, abs(w))

    if method == "values":
        return (len(got) == len(want)
                and all(close(g, w) for g, w in zip(got, want)))
    return close(got, want)


def _dump_compare(ds: ShardedBatchedPQ, oracle) -> None:
    got, want = ds.values(), oracle.values()
    assert len(got) == len(want), (got, want)
    assert all(abs(g - w) <= 1e-6 * max(1.0, abs(w))
               for g, w in zip(got, want)), (got, want)
    # device heap invariant: slot 0 of every shard is the +inf scratch,
    # parents never exceed children (the §4 layout)
    a = np.asarray(ds.state.a)
    sizes = np.asarray(ds.state.size)
    for k in range(ds.n_shards):
        assert np.isinf(a[k, 0]), a[k, 0]
        n = int(sizes[k])
        for v in range(2, n + 1):
            assert a[k, v >> 1] <= a[k, v], (k, v, a[k])


def _refusal_batch(ds: ShardedBatchedPQ):
    """More inserts than total slot capacity: pigeonhole forces one
    shard past ``capacity - 1`` live slots whatever the routing does."""
    n = (ds.capacity - 1) * ds.n_shards + 1
    return (["insert"] * n, [1000.0 + 2.0 * i for i in range(n)])


def _make(capacity: int = 512, c_max: int = 8, n_shards: int = 2,
          **kw) -> ShardedBatchedPQ:
    return ShardedBatchedPQ(capacity, c_max=c_max, n_shards=n_shards, **kw)


substrate.register(substrate.StructureSpec(
    name="pq",
    module="repro.core.batched_pq",
    title="sharded batched priority queue",
    make=_make,
    make_host=lambda ds: SequentialBatchedPQ(ds.values(),
                                             c_max=ds.c_max),
    gen_update=_gen_update,
    gen_read=_gen_read,
    result_ok=_result_ok,
    dump_compare=_dump_compare,
    refusal_batch=_refusal_batch,
    # the PQ's documented contract is one fetch per CONSUMED apply
    # (AsyncBatchResult), not read-resolves-updates
    reads_resolve_updates=False,
    megapass=True,
    bench="benchmarks.bench_pq",
    bench_smoke=("--size", "20000", "--threads", "1", "2", "4",
                 "--ops", "150"),
    extras={"serve_kw": dict(capacity=4096, c_max=16, n_shards=4),
            # ctor accepts placement= (DESIGN.md §18); serve.py keys
            # --mesh-shards eligibility off this marker, and the
            # placement tests pin it to the class attribute
            "placement": True,
            # reads the megapass conformance stage drives: peek_min can
            # ride the fused scan ("values" dumps the whole heap stack)
            "megapass_read": lambda rng, k, ctx: (["peek_min"] * k,
                                                  [None] * k)},
))
