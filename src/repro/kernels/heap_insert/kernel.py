"""Paper §4 Insert phase as a shard-grid Pallas TPU kernel (one level-chunk).

The collective insert places ``m`` sorted values at leaf targets
``size+1 .. size+m`` (all on one tree level — the caller splits batches at
level boundaries).  Clients descend level-by-level from the root; each
carries an ``InsertSet`` that is split by the number of target leaves in
each child's subtree.  The shared-memory paper version hands linked-list
splits between threads; the TPU adaptation keeps ALL the per-level client
state as one dense ``(C, C)`` f32 matrix in VMEM (row = client slot at the
current level, entries = that client's sorted InsertSet, +inf padded) and
replaces the pointer hand-off with three *vectorizable* primitives:

* row "replace-head keeping sorted" — predicated vector merge,
* per-row prefix split by target counts — mask + comparison-indexed shift
  (the ``sel`` tensor is (C,C,C) f32: C ≤ 64 keeps it ≤ 1 MiB in VMEM),
* parent-row gather for the next level — a one-hot (C,C) selector
  contracted by a predicated select + reduce (exact for every f32, no
  dynamic gather needed).

Per-slot quantities are (C, 1) columns.  Heap array access per level is
one *contiguous* window ``a[lo_d : lo_d + C]`` (the target-ancestor set
at one depth is an id interval): in the ``(rows, 128)`` layout
(``kernels/_rows.py``) it spans at most two rows, read and written with
masked lane selects — no scatter, no unaligned lane slice.

Shard-grid layout (DESIGN.md §10): the kernel runs over ``grid=(K,)`` —
one program per heap shard, each with its own ``(size_k, m_k)`` scalars in
SMEM and its own ``(cap/128, 128)`` heap block + ``(1, C)`` sorted chunk row
in VMEM.
A shard whose chunk is empty this level (``m_k == 0``) runs the descent
fully predicated-off (identity stores), so ragged per-shard level
boundaries need no host-side control flow.  Descent is top-down over
``max_depth`` levels (a static bound derived from capacity), so the whole
phase is ONE kernel launch for all K shards regardless of batch shape —
the pure-XLA fallback in ``core/batched_pq.py`` is the semantics twin and
the element-wise oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _rows

INF = jnp.inf
# In-kernel InsertSet padding: a FINITE sentinel, so the selector-sums of
# the split and the row-gather stay exact.  Heap values must be < BIG (the
# wrapper rejects larger); the heap array itself still uses +inf for empty.
BIG = 1e30


def _shift_rows(sets, amt):
    """out[j, i] = sets[j, i + amt[j]] (BIG outside the row) — no dynamic
    gather: selector sel[j, k, i] = (k == i + amt[j]), contracted on k
    as a predicated select + reduce (VPU work, exact).  amt: (C, 1)."""
    C = sets.shape[1]
    kk = jax.lax.broadcasted_iota(jnp.int32, (C, C, C), 1)
    ii = jax.lax.broadcasted_iota(jnp.int32, (C, C, C), 2)
    sel = kk == ii + amt[:, :, None]
    out = jnp.sum(jnp.where(sel, sets[:, :, None], 0.0), axis=1)
    src = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1) + amt
    return jnp.where((src < 0) | (src >= C), BIG, out)


def _replace_head_sorted_rows(sets, x, do):
    """Per-row: drop row[0], insert x, keep sorted.  sets (C,C); x, do
    (C, 1) columns."""
    C = sets.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    one = jnp.ones((C, 1), jnp.int32)
    shifted = _shift_rows(sets, one)                    # row[1:] + BIG
    k = jnp.sum((shifted <= x).astype(jnp.int32), axis=1,
                keepdims=True)                          # insertion point
    shifted_r1 = _shift_rows(shifted, -one)             # BIG + row[:-1]
    merged = jnp.where(lane == k, x,
                       jnp.where(lane < k, shifted, shifted_r1))
    return jnp.where(do, merged, sets)


def _window(ref, lo, C: int):
    """(row index r0, flat positions (C, 1)) of the C-wide window at
    ``lo`` inside the two rows r0, r0+1 of a (rows, 128) ref."""
    rows = ref.shape[0]
    r0 = jnp.minimum(lo >> 7, rows - 2)
    pos = lo - r0 * _rows.LANES + jax.lax.broadcasted_iota(
        jnp.int32, (C, 1), 0)
    return r0, pos


def _load_window(ref, lo, C: int):
    """a[lo : lo + C] as a (C, 1) column (masked lane selects over the
    two rows the window spans — no unaligned lane slicing)."""
    r0, pos = _window(ref, lo, C)
    lane = _rows.lane_iota()
    out = jnp.full((C, 1), INF, jnp.float32)
    for t in range(2):
        row = ref[pl.ds(r0 + t, 1), :]
        out = jnp.minimum(out, jnp.min(
            jnp.where(lane + t * _rows.LANES == pos, row, INF), axis=1,
            keepdims=True))
    return out


def _store_window(ref, lo, vals):
    """a[lo : lo + C] = vals, a (C, 1) column."""
    C = vals.shape[0]
    r0, pos = _window(ref, lo, C)
    lane = _rows.lane_iota()
    for t in range(2):
        hit = lane + t * _rows.LANES == pos                 # (C, 128)
        upd = jnp.min(jnp.where(hit, vals, INF), axis=0, keepdims=True)
        any_hit = jnp.max(hit.astype(jnp.int32), axis=0, keepdims=True)
        r = pl.ds(r0 + t, 1)
        ref[r, :] = jnp.where(any_hit > 0, upd, ref[r, :])


def _insert_kernel(size_ref, m_ref, vals_ref, a_ref, out_ref,
                   *, c_max: int, cap: int, max_depth: int):
    shard = pl.program_id(0)
    out_ref[...] = a_ref[...]
    C = c_max
    size = size_ref[shard]
    m = m_ref[shard]
    slot = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)   # per-slot column
    lane_cc = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)

    lo_c = size + 1
    hi_c = size + m
    d_c = _rows.depth(lo_c)
    nonempty = m > 0

    def tcount(v, d):
        """#targets in subtree(v), v at depth d (targets on one level d_c)."""
        shift = jnp.maximum(d_c - d, 0)
        vlo = v << shift
        vhi = vlo + (jnp.int32(1) << shift) - 1
        cnt = jnp.maximum(
            0, jnp.minimum(hi_c, vhi) - jnp.maximum(lo_c, vlo) + 1)
        return jnp.where(v > 0, cnt, 0)

    # row 0 holds the whole sorted chunk; the other rows start empty
    S0 = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
                   < jnp.where(nonempty, m, 0), vals_ref[...], BIG)
    sets0 = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (C, C), 0) == 0,
                      S0, BIG)

    def level(d, sets):
        d = jnp.int32(d)
        live = nonempty & (d <= d_c)
        descend = live & (d != d_c)                 # live and not the leaf
        lo_d = lo_c >> jnp.maximum(d_c - d, 0)
        hi_d = hi_c >> jnp.maximum(d_c - d, 0)
        v = lo_d + slot                             # (C, 1) node per slot
        slot_on = v <= jnp.where(live, hi_d, -1)
        lo_safe = jnp.clip(lo_d, 0, cap - C)
        av = _load_window(out_ref, lo_safe, C)      # a[v] per slot
        minS = sets[:, 0:1]

        do_swap = slot_on & (minS < av) & descend
        place = jnp.where(do_swap | (slot_on & (d == d_c)), minS, av)
        _store_window(out_ref, lo_safe, jnp.where(live, place, av))

        sets = _replace_head_sorted_rows(sets, av, do_swap)

        # children for the next level: child slot j <-> node u = lo_next + j
        lo_next = lo_c >> jnp.maximum(d_c - (d + 1), 0)
        hi_next = hi_c >> jnp.maximum(d_c - (d + 1), 0)
        u = lo_next + slot                          # (C, 1)
        Lc = tcount(2 * v, d + 1)                   # per parent slot
        left = jnp.where(lane_cc < Lc, sets, BIG)
        right = _shift_rows(sets, Lc)

        parent_slot = (u >> 1) - lo_d               # (C, 1)
        # one-hot row gather [child u, parent p, lane] as a predicated
        # select + reduce over p (exact)
        onehot = jax.lax.broadcasted_iota(jnp.int32, (C, C, C), 1) \
            == parent_slot[:, :, None]
        gl = jnp.sum(jnp.where(onehot, left[None], 0.0), axis=1)
        gr = jnp.sum(jnp.where(onehot, right[None], 0.0), axis=1)
        child = jnp.where((u & 1) == 1, gr, gl)
        ok = descend & (u <= hi_next) & (parent_slot >= 0) \
            & (parent_slot < C)
        child = jnp.where(ok, child, BIG)
        return jnp.where(descend, child, sets)

    jax.lax.fori_loop(0, max_depth + 1, level, sets0)


def insert_sharded_vmem(a: jax.Array, size: jax.Array, chunk_vals: jax.Array,
                        m_chunk: jax.Array, *, max_depth: int,
                        interpret: bool = False) -> jax.Array:
    """a: (K, R, 128) f32 in the row layout (``_rows``), R ≥ 2;
    chunk_vals: (K, C) sorted asc, +inf padded; m_chunk: (K,) int32 ≤ C.
    One grid program per shard.

    Requires R·128 ≥ size_k + C (contiguous level windows) — the ops
    wrapper pads.
    """
    K, R, _ = a.shape
    _, C = chunk_vals.shape
    assert C <= 64, "InsertSet matrix is (C,C,C) in the split op; keep C ≤ 64"
    assert R >= 2, "a level window spans two rows"
    kernel = functools.partial(_insert_kernel, c_max=C, cap=R * _rows.LANES,
                               max_depth=max_depth)
    heap = pl.BlockSpec((None, R, _rows.LANES), lambda k: (k, 0, 0),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid=(K,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # size (K,)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # m (K,)
            pl.BlockSpec((None, 1, C), lambda k: (k, 0, 0),
                         memory_space=pltpu.VMEM),   # chunk_vals row
            heap,                                    # heap shard
        ],
        out_specs=heap,
        out_shape=jax.ShapeDtypeStruct((K, R, _rows.LANES), a.dtype),
        interpret=interpret,
    )(size.astype(jnp.int32), m_chunk.astype(jnp.int32),
      chunk_vals.astype(jnp.float32)[:, None, :], a)
