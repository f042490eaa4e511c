"""Batched binary-heap priority queue (paper §4) — JAX, level-synchronous.

The paper applies a batch of ``|E|`` ExtractMin + ``|I|`` Insert requests to a
1-indexed array heap in ``O(c log c + log n)`` parallel time:

1. the combiner finds the ``|E|`` smallest nodes with a Dijkstra-like
   frontier search over the heap (``O(c log c)``),
2. ``min(|E|,|I|)`` extracted slots are refilled with insert values; the
   rest pull the heap tail (sequential, as in Gonnet–Munro),
3. the clients sift-down **in parallel** from the extracted nodes using
   hand-over-hand ``locked`` flags,
4. remaining inserts descend collectively from the root, each client
   carrying an ``InsertSet`` that is split by target-leaf counts at LCA
   nodes.

TPU adaptation (DESIGN.md §2): hand-over-hand spin flags become a
*level-synchronous wavefront* — every active sift cursor advances one tree
level per step inside a ``lax.while_loop``, with cursors staggered by start
depth (deepest first).  This is exactly the phased schedule the paper's own
Thm-4 proof reasons about; the stagger guarantees an active cursor always
stays ≥2 levels away from any cursor below it, so the vectorized scatters
are conflict-free and the result equals the paper's sequential execution
order SE (deepest-first).  The InsertSet linked-list splits become sorted
fixed-width rows split by prefix (the paper's own "segment" variant); any
count-partition preserves Thm 2, see the inline notes.

Everything is shape-static (batch capacity ``c_max`` is a compile-time
constant; the actual counts are traced scalars with masks) so the whole
batch application jits to a single XLA program.

Zero-copy pass structure (DESIGN.md §10): the jitted entry points donate
the heap state (``donate_argnums``) so the (capacity,)-sized arrays update
in place instead of being copied every pass, and the host slicing loop
(``apply_sliced_async``) keeps every per-slice result on device — ONE
blocking host transfer per ``apply()`` call, at result consumption.
"""
from __future__ import annotations

from typing import Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels._rows import require_heap_fits

INF = jnp.float32(jnp.inf)
_TINY = float(np.finfo(np.float32).tiny)        # smallest normal f32

# All device→host transfers on the PQ hot path route through this hook so
# tests can count blocking syncs (DESIGN.md §10: at most one per apply()).
_host_fetch = jax.device_get


def _flush_subnormals(x):
    """XLA (CPU and TPU) runs flush-to-zero; comparisons inside the device
    heap see subnormals as 0 while the host oracle doesn't.  Normalize keys
    on entry so both worlds agree."""
    return jnp.where(jnp.abs(x) < _TINY, jnp.zeros_like(x), x)


def require_finite_keys(values) -> None:
    """Reject keys outside the heap's domain (±inf is the empty-slot
    sentinel, NaN breaks the frontier search) — shared by every host
    entry point that accepts keys."""
    if len(values) and not np.all(np.isfinite(np.asarray(values,
                                                         np.float32))):
        raise ValueError(
            "keys must be finite f32: ±inf is the heap's empty-slot "
            "sentinel and NaN breaks the frontier search")


class HeapState(NamedTuple):
    """1-indexed array heap. ``a[0]`` is a scratch slot for masked scatters."""

    a: jax.Array      # (capacity,) float32, +inf marks empty slots
    size: jax.Array   # () int32


def heap_init(capacity: int, values=None) -> HeapState:
    a = jnp.full((capacity,), INF, jnp.float32)
    size = jnp.int32(0)
    if values is not None:
        require_finite_keys(values)
        values = jnp.sort(_flush_subnormals(jnp.asarray(values, jnp.float32)))
        (n,) = values.shape
        if n + 1 > capacity:
            raise ValueError("capacity too small")
        # a sorted array satisfies the heap property (parent idx < child idx)
        a = a.at[1 : n + 1].set(values)
        size = jnp.int32(n)
    return HeapState(a, size)


def _depth(v: jax.Array) -> jax.Array:
    """floor(log2(v)) for v >= 1, via count-leading-zeros."""
    return 31 - jax.lax.clz(jnp.maximum(v, 1).astype(jnp.int32))


def _gather(a: jax.Array, idx: jax.Array, valid: jax.Array) -> jax.Array:
    """a[idx] where valid, +inf elsewhere; idx clipped for safety."""
    safe = jnp.clip(idx, 0, a.shape[0] - 1)
    return jnp.where(valid, a[safe], INF)


# ---------------------------------------------------------------------------
# Phase 1 — combiner: Dijkstra-like frontier search for the k smallest nodes
# ---------------------------------------------------------------------------
def _k_smallest(a: jax.Array, size: jax.Array, n_extract: jax.Array,
                c_max: int) -> Tuple[jax.Array, jax.Array]:
    """Node ids + values of the ``min(n_extract, size)`` smallest heap nodes.

    Returned in ascending value order; padded with (0, +inf).
    The frontier holds candidate nodes whose parents were already taken —
    the heap property makes the running frontier-min the global next-min.
    (The Pallas twin is ``kernels/heap_kmin`` — element-wise identical.)
    """
    F = 2 * c_max + 1
    f_ids = jnp.zeros((F,), jnp.int32).at[0].set(1)
    f_vals = jnp.full((F,), INF, jnp.float32).at[0].set(
        jnp.where(size >= 1, a[1], INF)
    )

    def step(carry, i):
        f_ids, f_vals, nfree = carry
        j = jnp.argmin(f_vals)
        v, val = f_ids[j], f_vals[j]
        active = (i < n_extract) & jnp.isfinite(val)
        l, r = 2 * v, 2 * v + 1
        lval = _gather(a, l, active & (l <= size))
        rval = _gather(a, r, active & (r <= size))
        # replace the taken slot with the left child, append the right child
        f_ids = f_ids.at[j].set(jnp.where(active, l, f_ids[j]))
        f_vals = f_vals.at[j].set(jnp.where(active, lval, f_vals[j]))
        slot = jnp.where(active, nfree, F - 1)
        f_ids = f_ids.at[slot].set(jnp.where(active, r, f_ids[slot]))
        f_vals = f_vals.at[slot].set(jnp.where(active, rval, f_vals[slot]))
        nfree = nfree + active.astype(jnp.int32)
        out = (jnp.where(active, v, 0), jnp.where(active, val, INF))
        return (f_ids, f_vals, nfree), out

    (_, _, _), (ids, vals) = jax.lax.scan(
        step, (f_ids, f_vals, jnp.int32(1)), jnp.arange(c_max, dtype=jnp.int32)
    )
    return ids, vals


# ---------------------------------------------------------------------------
# Phase 2 — combiner: refill extracted slots (inserts first, then heap tail)
# ---------------------------------------------------------------------------
def _refill(a, size, out_ids, insert_vals, k_eff, L, c_max):
    lane = jnp.arange(c_max, dtype=jnp.int32)

    # (a) the L smallest extracted nodes receive the L smallest insert values
    idx = jnp.where(lane < L, out_ids, 0)
    a = a.at[idx].set(jnp.where(lane < L, insert_vals, a[idx]))
    a = a.at[0].set(INF)

    # (b) remaining extracted nodes pull the heap tail — processed in
    # DESCENDING node order so a pulled tail slot is never an unprocessed
    # extracted node (tail position == current size >= any remaining id).
    tail_ids = jnp.where((lane >= L) & (lane < k_eff), out_ids, -1)
    tail_sorted = -jnp.sort(-tail_ids)  # descending, -1 padding last

    def pull(carry, v):
        a, size = carry
        active = v > 0
        last = _gather(a, size, active)
        tgt = jnp.where(active & (v < size), v, 0)
        a = a.at[tgt].set(jnp.where(tgt > 0, last, a[tgt]))
        clr = jnp.where(active, size, 0)
        a = a.at[clr].set(jnp.where(active, INF, a[clr]))
        a = a.at[0].set(INF)
        size = size - active.astype(jnp.int32)
        return (a, size), None

    (a, size), _ = jax.lax.scan(pull, (a, size), tail_sorted)
    return a, size


# ---------------------------------------------------------------------------
# Phase 3 — clients: parallel sift-down wavefront (ExtractMin phase, §4)
# ---------------------------------------------------------------------------
def _sift_wavefront(a, size, starts, active0):
    """Level-synchronous parallel sift-down from ``starts``.

    Cursors staggered by depth (deepest start first — the paper's SE order);
    while two cursors are both active the lower one stays ≥2 levels deeper,
    so each step's two scatters touch pairwise-distinct nodes.
    """
    cap = a.shape[0]
    depths = _depth(starts)
    d_max = jnp.max(jnp.where(active0, depths, 0))
    delay = d_max - depths

    def cond(st):
        return jnp.any(st[3])

    def body(st):
        step, a, pos, active = st
        moving = active & (step >= delay)
        v = jnp.where(moving, pos, 0)
        l, r = 2 * v, 2 * v + 1
        av = a[jnp.clip(v, 0, cap - 1)]
        lv = _gather(a, l, moving & (l <= size))
        rv = _gather(a, r, moving & (r <= size))
        wv = jnp.minimum(lv, rv)
        w = jnp.where(lv <= rv, l, r)
        swap = moving & (wv < av)
        active = active & ~(moving & ~swap)
        sv = jnp.where(swap, v, 0)
        a = a.at[sv].set(jnp.where(swap, wv, a[sv]))
        sw = jnp.where(swap, w, 0)
        a = a.at[sw].set(jnp.where(swap, av, a[sw]))
        a = a.at[0].set(INF)
        pos = jnp.where(swap, w, pos)
        return (step + 1, a, pos, active)

    _, a, _, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), a, starts, active0)
    )
    return a


# ---------------------------------------------------------------------------
# Phase 4 — clients: collective insert along partitioned root→leaf paths
# ---------------------------------------------------------------------------
def _replace_head_sorted(S: jax.Array, x: jax.Array, do: jax.Array) -> jax.Array:
    """Drop S[0], insert x, keep the row sorted (width-C, +inf padded)."""
    C = S.shape[0]
    lane = jnp.arange(C)
    shifted = jnp.concatenate([S[1:], jnp.full((1,), INF, S.dtype)])
    k = jnp.sum(shifted <= x)  # insertion point
    src = jnp.where(lane < k, lane, jnp.maximum(lane - 1, 0))
    merged = jnp.where(lane == k, x, shifted[src])
    return jnp.where(do, merged, S)


def _insert_chunk(a, size, chunk_vals, m_chunk, c_max, max_depth):
    """Insert ``m_chunk`` sorted values at positions size+1 .. size+m_chunk.

    Precondition: all target positions live on ONE tree level (the caller
    splits batches at level boundaries), so the target-ancestor set at every
    depth is a contiguous id range and subtree∩targets counts need no
    level summation.
    """
    C = c_max
    lane = jnp.arange(C, dtype=jnp.int32)
    lo_c = size + 1
    hi_c = size + m_chunk
    d_c = _depth(lo_c)                      # depth of every target
    nonempty = m_chunk > 0

    def tcount(v, d):
        """#targets in subtree(v) for v at depth d (single-level targets)."""
        shift = jnp.maximum(d_c - d, 0)
        vlo = v << shift
        vhi = vlo + (jnp.int32(1) << shift) - 1
        cnt = jnp.maximum(
            0, jnp.minimum(hi_c, vhi) - jnp.maximum(lo_c, vlo) + 1
        )
        return jnp.where(v > 0, cnt, 0)

    # depth-0 state: one active slot (the root) holding all chunk values
    S0 = jnp.where((lane < m_chunk) & nonempty, chunk_vals, INF)
    sets0 = jnp.full((C, C), INF, jnp.float32).at[0].set(S0)

    def level(d, carry):
        a, sets = carry
        d = jnp.int32(d)
        live = nonempty & (d <= d_c)
        lo_d = lo_c >> jnp.maximum(d_c - d, 0)
        hi_d = hi_c >> jnp.maximum(d_c - d, 0)
        v = lo_d + lane                       # slot -> node id
        slot_on = live & (v <= hi_d)
        is_leaf = d == d_c

        minS = sets[:, 0]
        av = a[jnp.clip(jnp.where(slot_on, v, 0), 0, a.shape[0] - 1)]

        # internal existing node: place min(S, a[v]), displace a[v] into S
        do_swap = slot_on & ~is_leaf & (minS < av)
        # leaf target: place min(S) (the set has exactly one value here)
        place = jnp.where(do_swap | (slot_on & is_leaf), minS, av)
        tgt = jnp.where(slot_on & (do_swap | is_leaf), v, 0)
        a = a.at[tgt].set(jnp.where(tgt > 0, place, a[tgt]))
        a = a.at[0].set(INF)
        sets = jax.vmap(_replace_head_sorted)(sets, av, do_swap)

        # split each set by target counts of the two children (prefix split
        # of a sorted row — any count-partition preserves Thm 2 because the
        # running min is placed at every node top-down)
        Lc = tcount(2 * v, d + 1)
        split_on = slot_on & ~is_leaf

        def split_row(S, lcnt):
            left = jnp.where(lane < lcnt, S, INF)
            right_src = jnp.clip(lane + lcnt, 0, C - 1)
            right = jnp.where(lane + lcnt < C, S[right_src], INF)
            return left, right

        left, right = jax.vmap(split_row)(sets, Lc)

        lo_next = lo_c >> jnp.maximum(d_c - (d + 1), 0)
        hi_next = hi_c >> jnp.maximum(d_c - (d + 1), 0)
        # child slot ids are unique per node; out-of-active-range children
        # (and inactive rows) go to a dedicated dump row C so they can never
        # clobber a genuine row (width at depth d+1 is <= m_chunk <= C)
        nxt = jnp.full((C + 1, C), INF, jnp.float32)
        lraw = 2 * v - lo_next
        rraw = 2 * v + 1 - lo_next
        ok_l = split_on & (lraw >= 0) & (lraw <= hi_next - lo_next)
        ok_r = split_on & (rraw >= 0) & (rraw <= hi_next - lo_next)
        lslot = jnp.where(ok_l, lraw, C)
        rslot = jnp.where(ok_r, rraw, C)
        nxt = nxt.at[lslot].set(jnp.where(ok_l[:, None], left, nxt[lslot]))
        nxt = nxt.at[rslot].set(jnp.where(ok_r[:, None], right, nxt[rslot]))
        sets = jnp.where(live & ~is_leaf, nxt[:C], sets)
        return (a, sets)

    a, _ = jax.lax.fori_loop(0, max_depth + 1, level, (a, sets0))
    size = size + jnp.where(nonempty, m_chunk, 0)
    return a, size


# ---------------------------------------------------------------------------
# Composable phase helpers (also used, vmapped, by the sharded queue)
# ---------------------------------------------------------------------------
def _phases12(a, size, n_extract, insert_vals, n_insert, *, c_max: int,
              phase1: Optional[Tuple[jax.Array, jax.Array]] = None):
    """Combiner phases 1–2 + client-phase setup (pure XLA, vmappable).

    Returns ``(a, size, out_vals, k_eff, starts, active, rem, m_left)``:
    the refilled heap, the extracted values (ascending, +inf padded), the
    sift wavefront's start cursors, and the sorted suffix of insert values
    still to be placed by phase 4.
    """
    lane = jnp.arange(c_max, dtype=jnp.int32)
    n_extract = jnp.minimum(jnp.int32(n_extract), c_max)
    n_insert = jnp.minimum(jnp.int32(n_insert), c_max)
    insert_vals = _flush_subnormals(insert_vals.astype(jnp.float32))
    insert_vals = jnp.sort(jnp.where(lane < n_insert, insert_vals, INF))

    if phase1 is None:
        out_ids, out_vals = _k_smallest(a, size, n_extract, c_max)
    else:
        out_ids, out_vals = phase1
    k_eff = jnp.minimum(n_extract, size)
    L = jnp.minimum(k_eff, n_insert)

    a, size = _refill(a, size, out_ids, insert_vals, k_eff, L, c_max)

    starts = jnp.where(lane < k_eff, out_ids, 0)
    active = (lane < k_eff) & (starts >= 1) & (starts <= size)

    m_left = n_insert - L
    rem = _gather(insert_vals, lane + L, lane < m_left)  # sorted suffix
    return a, size, out_vals, k_eff, starts, active, rem, m_left


def _chunk_len(size, left):
    """Length of the next level-chunk: targets ``size+1 ..`` truncated at
    the last id on that tree level.  Elementwise — shared by the scalar
    loop below and the (K,)-vector loop in ``sharded_pq.py``."""
    lo = size + 1
    level_end = (jnp.int32(2) << _depth(lo)) - 1       # last id on lo's level
    return jnp.minimum(left, level_end - lo + 1)


def _phase4(a, size, rem, m_left, insert_fn, *, c_max: int, max_depth: int):
    """Remaining inserts, chunked at level boundaries.

    ``insert_fn(a, size, vals, m) -> (a, size)`` places one sorted
    level-chunk — the pure-XLA `_insert_chunk` or the Pallas kernel.
    (The K-shard variant of this loop lives in ``sharded_pq.py`` — same
    chunk-boundary math via ``_chunk_len``, vectorized over shards.)
    """
    lane = jnp.arange(c_max, dtype=jnp.int32)

    def chunk(_, carry):
        a, size, off, left = carry
        m = _chunk_len(size, left)
        vals = _gather(rem, off + lane, lane < m)
        a, size = insert_fn(a, size, vals, m)
        return (a, size, off + m, left - m)

    a, size, _, _ = jax.lax.fori_loop(
        0, max_depth + 1, chunk, (a, size, jnp.int32(0), m_left)
    )
    return a, size


def _phase4_xla(a, size, rem, m_left, *, c_max: int, max_depth: int):
    """Pure-XLA phase 4 (vmappable — the sharded queue's fallback path)."""
    return _phase4(
        a, size, rem, m_left,
        lambda a, s, v, m: _insert_chunk(a, s, v, m, c_max, max_depth),
        c_max=c_max, max_depth=max_depth)


# ---------------------------------------------------------------------------
# The full batch application (paper §4, COMBINER_CODE + CLIENT_CODE fused
# into one SPMD program — the "clients" are the vector lanes)
# ---------------------------------------------------------------------------
def apply_batch_impl(state: HeapState, n_extract: jax.Array,
                     insert_vals: jax.Array, n_insert: jax.Array,
                     *, c_max: int, use_pallas: bool = False,
                     phase1: Optional[Tuple[jax.Array, jax.Array]] = None,
                     ) -> Tuple[HeapState, jax.Array, jax.Array]:
    """Traceable body of :func:`apply_batch` (phases 1–4, un-jitted).

    ``use_pallas`` routes phases 1, 3 and 4 through the heap kernels
    (``kernels/heap_kmin``, ``heap_sift``, ``heap_insert``) — shard-grid
    kernels dispatched with K=1 here; the K-sharded queue
    (``sharded_pq.py``, DESIGN.md §9–§10) calls the same phase helpers
    across all K shards with ``grid=(K,)`` kernels.

    ``phase1`` optionally supplies a precomputed phase-1 result
    ``(out_ids, out_vals)`` — the first ``n_extract`` smallest nodes,
    ascending, (0, +inf)-padded — so a caller that already ran the
    frontier search (the sharded candidate merge) doesn't pay the
    ``O(c log c)`` scan twice.
    """
    a, size = state
    cap = a.shape[0]
    max_depth = int(np.ceil(np.log2(cap))) + 1

    if phase1 is None and use_pallas:
        from repro.kernels.heap_kmin import k_smallest as _kmin_k
        n_e = jnp.minimum(jnp.int32(n_extract), c_max)
        phase1 = _kmin_k(a, size, n_e, c_max=c_max)

    a, size, out_vals, k_eff, starts, active, rem, m_left = _phases12(
        a, size, n_extract, insert_vals, n_insert, c_max=c_max,
        phase1=phase1)

    # phase 3: parallel sift wavefront from still-valid extracted nodes
    if use_pallas:
        from repro.kernels.heap_sift import sift_wavefront as _sift_k
        a = _sift_k(a, size, starts, active)
    else:
        a = _sift_wavefront(a, size, starts, active)

    # phase 4: remaining inserts, chunked at level boundaries
    if use_pallas:
        from repro.kernels._rows import from_rows, to_rows
        from repro.kernels.heap_insert import insert_chunk_sharded as _ins_k

        # convert to the kernel's row layout with the insert headroom once;
        # re-padding inside the chunk loop would copy the whole heap
        # max_depth times per pass
        a = to_rows(a[None], min_width=cap + c_max, min_rows=2)

        def ins_fn(ah, s, v, m):
            out, ns = _ins_k(ah, jnp.reshape(s, (1,)), v[None],
                             jnp.reshape(m, (1,)), pre_padded=True)
            return out, ns[0]

        a, size = _phase4(a, size, rem, m_left, ins_fn,
                          c_max=c_max, max_depth=max_depth)
        a = from_rows(a, cap)[0]
    else:
        a, size = _phase4_xla(a, size, rem, m_left, c_max=c_max,
                              max_depth=max_depth)

    return HeapState(a, size), out_vals, k_eff


def _apply_batch(state: HeapState, n_extract: jax.Array,
                 insert_vals: jax.Array, n_insert: jax.Array,
                 *, c_max: int,
                 use_pallas: bool = False) -> Tuple[HeapState, jax.Array,
                                                    jax.Array]:
    """Apply a combined batch (jitted — one XLA program).

    Args:
      state: heap state.
      n_extract: () int32 — number of ExtractMin requests (≤ c_max).
      insert_vals: (c_max,) float32 — insert arguments (first n_insert valid).
      n_insert: () int32 — number of Insert requests (≤ c_max).

    Returns:
      (new_state, extracted (c_max,) ascending +inf-padded, k_eff) where
      k_eff = min(n_extract, size) is the number of successful extracts.
    """
    return apply_batch_impl(state, n_extract, insert_vals, n_insert,
                            c_max=c_max, use_pallas=use_pallas)


# ``state`` is DONATED: the (capacity,) heap array updates in place instead
# of being copied every pass (DESIGN.md §10).  Callers must not reuse a
# state after passing it in — the wrapper classes below never do.
apply_batch = jax.jit(_apply_batch, static_argnames=("c_max", "use_pallas"),
                      donate_argnums=(0,))
# Ablation twin (EXPERIMENTS §Ablations): identical program, no donation —
# XLA copies the heap buffers every pass.
apply_batch_undonated = jax.jit(_apply_batch,
                                static_argnames=("c_max", "use_pallas"))


# ---------------------------------------------------------------------------
# Device command queue: fused multi-round application (DESIGN.md §12)
# ---------------------------------------------------------------------------
def _rounds_impl(state: HeapState, n_extracts: jax.Array,
                 insert_rows: jax.Array, n_inserts: jax.Array,
                 *, c_max: int, use_pallas: bool = False,
                 ) -> Tuple[HeapState, jax.Array, jax.Array]:
    """R combining rounds as ONE program: ``lax.scan`` over the round axis.

    ``n_extracts``: (R,) int32; ``insert_rows``: (R, c_max) float32;
    ``n_inserts``: (R,) int32 — a padded command queue of R sequential
    combined batches.  Each scan step runs the full phase-1..4 pipeline of
    :func:`apply_batch_impl` (the Pallas kernels compose unchanged — the
    scan body is exactly the single-round trace), so R rounds cost one
    dispatch instead of R.  Returns ``(state, outs (R, c_max), k_effs
    (R,))`` with per-round extracted values ascending, +inf padded.
    """

    def body(st, rnd):
        ne, vals, ni = rnd
        st, out, k_eff = apply_batch_impl(st, ne, vals, ni, c_max=c_max,
                                          use_pallas=use_pallas)
        return st, (out, k_eff)

    state, (outs, k_effs) = jax.lax.scan(
        body, state, (n_extracts, insert_rows, n_inserts))
    return state, outs, k_effs


# Donated like apply_batch: the heap updates in place across all R rounds.
apply_rounds = jax.jit(_rounds_impl, static_argnames=("c_max", "use_pallas"),
                       donate_argnums=(0,))
apply_rounds_undonated = jax.jit(_rounds_impl,
                                 static_argnames=("c_max", "use_pallas"))


# ---------------------------------------------------------------------------
# Reference oracle (paper batch semantics, sequential numpy)
# ---------------------------------------------------------------------------
def apply_batch_reference(values, n_extract, insert_vals):
    """Set-semantics oracle: extracted = k smallest of the pre-batch heap,
    new multiset = (old \\ extracted) ∪ inserts.  Returns (extracted_sorted,
    new_multiset_sorted)."""
    vals = sorted(values)
    k = min(n_extract, len(vals))
    extracted = vals[:k]
    remaining = vals[k:] + list(insert_vals)
    return extracted, sorted(remaining)


def check_heap_property(a: np.ndarray, size: int) -> bool:
    for v in range(2, size + 1):
        if a[v // 2] > a[v]:
            return False
    return True


# ---------------------------------------------------------------------------
# Host-facing wrappers — sync-free slicing (DESIGN.md §10)
# ---------------------------------------------------------------------------
class AsyncBatchResult:
    """Deferred host view of one ``apply()`` call's extracted values.

    Holds the per-slice device arrays (+inf-padded, ascending per slice)
    and performs ONE blocking device→host transfer, at first
    :meth:`result` call — the device keeps computing while the host is
    free to publish more batches (the scheduler's pipelined combiner).
    The +inf padding doubles as the empty-queue sentinel: a slice that
    asked for ``ne`` extracts but fetched ``k`` finite values reports
    ``ne - k`` ``None`` entries, without shipping ``k_eff`` separately.
    """

    def __init__(self, slice_ne: List[int], slice_vals: List[jax.Array],
                 extra: Optional[Callable[[], object]] = None,
                 on_fetch: Optional[Callable[[object], None]] = None):
        self._ne = slice_ne
        self._vals = slice_vals
        self._extra = extra
        self._on_fetch = on_fetch
        self._out: Optional[list] = None

    def result(self) -> list:
        """Extracted values ascending per slice, ``None``-padded for
        extracts that found the queue empty (cached after first call)."""
        if self._out is None:
            # ``extra`` is evaluated NOW, not at apply time: under
            # pipelined consumption (result() of pass N−1 while pass N is
            # in flight) the fetched sizes then reflect every dispatched
            # slice — exactly the prefix the host mirror has accounted.
            extra_dev = self._extra() if self._extra is not None else None
            vals_h, extra_h = _host_fetch((self._vals, extra_dev))
            out: list = []
            for ne, vals in zip(self._ne, vals_h):
                vals = np.asarray(vals)
                k = int(np.isfinite(vals[:ne]).sum())
                out.extend(vals[:k].tolist())
                out.extend([None] * (ne - k))      # empty-queue extracts
            if self._on_fetch is not None:
                self._on_fetch(extra_h)
            self._out = out
            self._vals = self._extra = self._on_fetch = None
        return self._out


def apply_sliced_async(step, c_max: int, extracts: int, inserts,
                       *, extra=None,
                       on_fetch: Optional[Callable[[object], None]] = None,
                       ) -> AsyncBatchResult:
    """Shared host-side batching loop for the PQ wrappers — sync-free.

    Applies a combined batch of ``extracts`` ExtractMin + ``inserts`` in
    ≤ c_max slices; ``step(ne, buf, ni) -> (vals, k_eff)`` runs one device
    program over one slice (and updates the caller's state).  The loop
    never touches a device value: slice shapes depend only on host counts,
    and the per-slice results stay on device inside the returned
    :class:`AsyncBatchResult`.  ``extra`` (an optional thunk returning a
    device pytree, e.g. the current shard sizes — evaluated at RESULT
    consumption, so it reflects every slice dispatched by then) is fetched
    alongside the values in the one blocking transfer and handed to
    ``on_fetch``.
    """
    inserts = list(inserts)
    require_finite_keys(inserts)
    slice_ne: List[int] = []
    slice_vals: List[jax.Array] = []
    extracts = int(extracts)
    while extracts > 0 or inserts:
        ne = min(extracts, c_max)
        ni = min(len(inserts), c_max)
        buf = np.full((c_max,), np.inf, np.float32)
        buf[:ni] = inserts[:ni]
        vals, _k_eff = step(ne, buf, ni)   # k_eff stays on device, unused
        if ne:
            slice_ne.append(ne)
            slice_vals.append(vals)
        extracts -= ne
        inserts = inserts[ni:]
    return AsyncBatchResult(slice_ne, slice_vals, extra=extra,
                            on_fetch=on_fetch)


def apply_sliced(step, c_max: int, extracts: int, inserts) -> list:
    """Blocking convenience wrapper over :func:`apply_sliced_async`."""
    return apply_sliced_async(step, c_max, extracts, inserts).result()


# ---------------------------------------------------------------------------
# Multi-round host plumbing (DESIGN.md §12): one dispatch, per-round handles
# ---------------------------------------------------------------------------
class _RoundsFetch:
    """ONE blocking transfer shared by every round of a fused dispatch.

    Holds the (R, c_max) device output of an ``apply_rounds`` program (plus
    an optional ``extra`` thunk, e.g. the shard sizes) and fetches it once,
    at the first consuming :meth:`rows` call — so R consumed rounds cost at
    most one host sync between them (the per-consumer budget stays ≤ 1)."""

    def __init__(self, vals_dev, extra: Optional[Callable[[], object]] = None,
                 on_fetch: Optional[Callable[[object], None]] = None):
        self._vals = vals_dev
        self._extra = extra
        self._on_fetch = on_fetch
        self._host: Optional[np.ndarray] = None

    def rows(self) -> np.ndarray:
        if self._host is None:
            extra_dev = self._extra() if self._extra is not None else None
            vals_h, extra_h = _host_fetch((self._vals, extra_dev))
            if self._on_fetch is not None:
                self._on_fetch(extra_h)
            self._host = np.asarray(vals_h)
            self._vals = self._extra = self._on_fetch = None
        return self._host


class RoundResult:
    """Deferred host view of ONE round of a fused multi-round dispatch.

    Same consumer contract as :class:`AsyncBatchResult` — ``result()``
    returns the round's extracted values ascending, ``None``-padded for
    empty-queue extracts — but the blocking transfer is shared across all
    rounds of the dispatch (:class:`_RoundsFetch`): the first consumed
    round pays the one sync, later rounds read the cached host array."""

    def __init__(self, slice_ne: List[int], row_ids: List[int],
                 shared: Optional[_RoundsFetch]):
        self._ne = slice_ne
        self._rows = row_ids
        self._shared = shared
        self._out: Optional[list] = None

    def result(self) -> list:
        if self._out is None:
            rows = self._shared.rows() if self._shared is not None else None
            out: list = []
            for ne, i in zip(self._ne, self._rows):
                vals = np.asarray(rows[i])
                k = int(np.isfinite(vals[:ne]).sum())
                out.extend(vals[:k].tolist())
                out.extend([None] * (ne - k))      # empty-queue extracts
            self._out = out
            self._shared = None
        return self._out


def expand_rounds(rounds, c_max: int):
    """Lower consumer rounds onto ≤ c_max scan rows (host-side, sync-free).

    ``rounds``: sequence of ``(extracts, inserts)`` pairs, one per
    consumer round.  Oversized rounds are sliced exactly like
    :func:`apply_sliced_async` (ne/ni ≤ c_max per row, extracts and
    inserts advancing together), so every row is a valid single-batch
    application and the scan preserves round order.  Returns
    ``(specs, layout)``: ``specs`` is the flat row list ``[(ne, buf,
    ni)]``; ``layout[r]`` is ``(slice_ne, row_ids)`` — the extract counts
    and row indices that reassemble round ``r``'s answer."""
    specs: List[Tuple[int, np.ndarray, int]] = []
    layout: List[Tuple[List[int], List[int]]] = []
    for extracts, inserts in rounds:
        inserts = list(inserts)
        require_finite_keys(inserts)
        extracts = int(extracts)
        if extracts < 0:
            raise ValueError("extracts must be >= 0")
        slice_ne: List[int] = []
        row_ids: List[int] = []
        while extracts > 0 or inserts:
            ne = min(extracts, c_max)
            ni = min(len(inserts), c_max)
            buf = np.full((c_max,), np.inf, np.float32)
            buf[:ni] = inserts[:ni]
            if ne:
                slice_ne.append(ne)
                row_ids.append(len(specs))
            specs.append((ne, buf, ni))
            extracts -= ne
            inserts = inserts[ni:]
        layout.append((slice_ne, row_ids))
    # pad the row count to the next power of two with no-op rows: the
    # scan program recompiles per distinct leading dim, and callers feed
    # burst-sized queues — pow2 bucketing bounds the jit-cache variants
    # to log2(R_max) at ≤ 2× masked body work in the worst case
    if specs:
        target = 1 << (len(specs) - 1).bit_length()
        pad = np.full((c_max,), np.inf, np.float32)
        while len(specs) < target:
            specs.append((0, pad, 0))
    return specs, layout


class BatchedPriorityQueue:
    """Device-resident PQ with batch application (the §4 data structure).

    ``donate=True`` (default) dispatches through the donating jit — the
    heap buffers update in place (zero-copy pass, DESIGN.md §10);
    ``donate=False`` is the copy-per-pass ablation twin.
    """

    def __init__(self, capacity: int, c_max: int, values=None,
                 use_pallas: bool = False, donate: bool = True):
        if c_max < 1:
            raise ValueError("c_max must be >= 1")
        self.c_max = int(c_max)
        self.capacity = int(capacity)
        self.use_pallas = bool(use_pallas)
        if self.use_pallas:
            require_heap_fits(self.capacity)
        self.donate = bool(donate)
        self.state = heap_init(capacity, values)

    def __len__(self) -> int:
        return int(self.state.size)

    def _step(self, ne, buf, ni):
        fn = apply_batch if self.donate else apply_batch_undonated
        self.state, vals, k_eff = fn(
            self.state, jnp.int32(ne), jnp.asarray(buf), jnp.int32(ni),
            c_max=self.c_max, use_pallas=self.use_pallas,
        )
        return vals, k_eff

    def apply_async(self, extracts: int, inserts) -> AsyncBatchResult:
        """Apply a combined batch; the extracted values stay on device
        until ``.result()`` — one blocking host sync per call, not per
        slice.  Batches larger than c_max are applied in c_max slices —
        still one device program per slice."""
        return apply_sliced_async(self._step, self.c_max, extracts, inserts)

    def apply(self, extracts: int, inserts) -> list:
        """Apply a combined batch; returns the extracted values (floats)."""
        return self.apply_async(extracts, inserts).result()

    def apply_rounds_async(self, rounds) -> List[RoundResult]:
        """Apply R sequential combined batches with ONE device dispatch
        (the §12 command queue: a padded (R, c_max) request tensor executed
        by a donated ``lax.scan``).  ``rounds``: [(extracts, inserts)] —
        oversized rounds are sliced onto extra scan rows.  Returns one
        :class:`RoundResult` per round; all rounds share one blocking
        fetch, paid by the first consumed round."""
        specs, layout = expand_rounds(rounds, self.c_max)
        if not specs:
            return [RoundResult(sn, ri, None) for sn, ri in layout]
        ne_arr = jnp.asarray(np.array([s[0] for s in specs], np.int32))
        bufs = jnp.asarray(np.stack([s[1] for s in specs]))
        ni_arr = jnp.asarray(np.array([s[2] for s in specs], np.int32))
        fn = apply_rounds if self.donate else apply_rounds_undonated
        self.state, outs, _k = fn(self.state, ne_arr, bufs, ni_arr,
                                  c_max=self.c_max,
                                  use_pallas=self.use_pallas)
        shared = _RoundsFetch(outs)
        return [RoundResult(sn, ri, shared) for sn, ri in layout]

    def apply_rounds(self, rounds) -> List[list]:
        """Blocking :meth:`apply_rounds_async`: per-round answer lists."""
        return [h.result() for h in self.apply_rounds_async(rounds)]

    def values(self) -> list:
        a = np.asarray(self.state.a)
        n = int(self.state.size)
        return sorted(a[1 : n + 1].tolist())
