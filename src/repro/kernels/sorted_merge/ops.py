"""Public wrappers for the sorted merge-compact kernel (DESIGN.md §13).

Two bit-exact realizations of the rebuild primitive of the map and the
sketch:

* ``merge_edits_xla`` — the pure-XLA path for a BOUNDED edit (≤ D
  deleted slots, ≤ C inserts), by prefix sums and static shift stages,
  with nothing addressed N-wide.  Vmappable.
* ``merge_compact_sharded`` — the ``grid=(K,)`` Pallas kernel
  (``kernel.py``): one program per map shard, masked row-min
  materialization, no data-dependent addressing.  ``merge_compact`` is
  the K=1 convenience dispatch.

Both produce the SAME bits: the merge moves f32 values without
arithmetic, so kernel ≡ XLA path ≡ numpy ref (``ref.py``) element-wise
for every shard count (tested like ``kernels/label_prop``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels._rows import ceil_to

from .kernel import merge_sharded_vmem

_P_CHUNK = 128      # output positions per kernel iteration (one lane row)
# Per-shard slots the whole-shard-in-VMEM kernel holds; compiled for a v5e
# at this size in tests/test_tpu_compile.py (2x more runs out of VMEM).
MAX_PALLAS_SLOTS = 1 << 14
INF = jnp.inf


def require_pallas_fits(slots: int) -> None:
    """Refuse, at construction, shards the kernel cannot hold in VMEM."""
    if slots > MAX_PALLAS_SLOTS:
        raise ValueError(
            f"use_pallas keeps each shard in VMEM: {slots} slots per shard "
            f"exceed the sorted_merge kernel's limit of {MAX_PALLAS_SLOTS}")


_SCAN_BLOCK = 128    # prefix-sum block: one lane row, one MXU tile


def _prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a row of non-negative ints.

    Sums each 128-slot block by a matmul with a triangular 0/1 matrix and
    adds the prefix of the block totals (the same, one level up), in
    place of the reduce-window ``jnp.cumsum`` lowers to (PERF.md §5).
    Exact while the row's total stays below 2^24: every partial sum is
    then an f32 integer, which the HIGHEST-precision matmul keeps.  The
    merge's rows are 0/1 deletion marks or insert counts, whose totals
    are at most D or C."""
    (n,) = x.shape
    B = _SCAN_BLOCK
    nb = -(-n // B)
    rows = jnp.pad(x.astype(jnp.float32), (0, nb * B - n)).reshape(nb, B)
    tri = (jnp.arange(B)[:, None] <= jnp.arange(B)[None, :]).astype(
        jnp.float32)
    within = jnp.dot(rows, tri, precision=jax.lax.Precision.HIGHEST)
    if nb > 1:
        tot = within[:, -1]
        within = within + (_prefix_sum(tot) - tot)[:, None]
    return within.reshape(-1)[:n].astype(jnp.int32)


def _shifted(a: jax.Array, m: int, fill, left: bool) -> jax.Array:
    """``a`` moved ``m`` slots left (or right), ``fill`` coming in."""
    pad = jnp.full((m,), fill, a.dtype)
    if left:
        return jnp.concatenate([a[m:], pad])
    return jnp.concatenate([pad, a[:-m]])


def _shift_by_bits(keys, vals, shift, n_bits: int, left: bool):
    """Move every entry by its ``shift`` (keys, vals and the shift travel
    together), one power of two per stage: from the low bit up for left
    shifts, from the high bit down for right shifts.  Where the shifts of
    the live entries are non-decreasing along the array and each entry's
    target keeps them apart (a compaction, or an expansion that opens
    gaps), no two live entries meet in any stage.  Dead slots carry
    (+inf, +inf) and shift 0.  Static slices and selects only, so
    nothing is addressed by a computed index."""
    n = keys.shape[0]
    bits = [b for b in range(n_bits) if (1 << b) < n]
    for b in (bits if left else bits[::-1]):
        m = 1 << b
        leaving = (shift & m) != 0
        shift_in = _shifted(shift, m, 0, left)
        arriving = (shift_in & m) != 0
        keys = jnp.where(arriving, _shifted(keys, m, INF, left),
                         jnp.where(leaving, INF, keys))
        vals = jnp.where(arriving, _shifted(vals, m, INF, left),
                         jnp.where(leaving, INF, vals))
        shift = jnp.where(arriving, shift_in, jnp.where(leaving, 0, shift))
    return keys, vals


def _shift_dtype(bound: int):
    """The narrowest integer type the stages need: a byte up to 127."""
    return jnp.int8 if bound < (1 << 7) else jnp.int32


def merge_edits_xla(a_keys: jax.Array, a_vals: jax.Array,
                    a_size: jax.Array, d_slots: jax.Array,
                    b_keys: jax.Array, b_vals: jax.Array,
                    b_count: jax.Array):
    """Merge-compact of a BOUNDED edit, without N-wide addressing.

    a_keys/a_vals: (N,) f32 run, strictly increasing in ``[0, a_size)``
    (slots past ``a_size`` are ignored); d_slots: (D,) int32 slots of A to
    drop, any entry ≥ N dropping nothing; b_keys/b_vals: (C,) f32 sorted
    insert run, first ``b_count`` valid, no key shared with the kept A.
    Returns ``(m_keys, m_vals)`` (N,) f32, (+inf, +inf)-padded: the same
    bits as ``ref.merge_compact_reference`` with keep = live and not
    dropped.  D may be 0 (an insert-only merge, as the sketch's).

    At most D deletions and C insertions move every survivor by at most
    D slots left, then C slots right, so the merge is a prefix sum of
    the deletion row, ``bit_length(D)`` left-shift stages, a C-query
    search of the compacted run, a prefix sum of the C-wide insert
    histogram, ``bit_length(C)`` right-shift stages and one C-wide write
    of the inserts into the gaps.  Only the D- and C-wide writes address
    by index.
    """
    (n,) = a_keys.shape
    (d,) = d_slots.shape
    (c,) = b_keys.shape
    sdt = _shift_dtype(max(d, c))
    slot = jnp.arange(n, dtype=jnp.int32)
    live = slot < a_size
    n_live = a_size
    if d:
        # deletions: a 0/1 row; its prefix sum is each survivor's left
        # shift
        drop = jnp.zeros((n,), jnp.int32).at[d_slots].set(1, mode="drop")
        left = _prefix_sum(drop)
        live = live & (drop == 0)
        n_live = a_size - jnp.where(
            a_size > 0, left[jnp.clip(a_size - 1, 0, n - 1)], 0)
    keys = jnp.where(live, a_keys, INF)
    vals = jnp.where(live, a_vals, INF)
    if d:
        keys, vals = _shift_by_bits(keys, vals,
                                    jnp.where(live, left, 0).astype(sdt),
                                    d.bit_length(), left=True)
    # inserts: survivor k moves right by the number of inserts below it
    b_valid = jnp.arange(c) < b_count
    at = jnp.searchsorted(keys, b_keys, side="left").astype(jnp.int32)
    hist = jnp.zeros((n,), jnp.int32).at[jnp.where(b_valid, at, n)].add(
        1, mode="drop")
    right = jnp.where(slot < n_live, _prefix_sum(hist), 0).astype(sdt)
    keys, vals = _shift_by_bits(keys, vals, right, c.bit_length(),
                                left=False)
    # the inserts fill the gaps: insert j lands past j earlier inserts
    tb = jnp.where(b_valid, at + jnp.arange(c, dtype=jnp.int32), n)
    return (keys.at[tb].set(b_keys, mode="drop"),
            vals.at[tb].set(b_vals, mode="drop"))


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_compact_sharded(a_keys: jax.Array, a_vals: jax.Array,
                          a_keep: jax.Array, b_keys: jax.Array,
                          b_vals: jax.Array, b_count: jax.Array,
                          *, interpret: Optional[bool] = None):
    """Merge-compact on all K shards via ONE ``grid=(K,)`` kernel.

    a_keys/a_vals/a_keep: (K, N); b_keys/b_vals: (K, C); b_count: (K,).
    Pads N up to the kernel's output tile (+inf keys, keep=0 — padding
    slots never match an output rank) and strips it again, so the result
    is shape- and shard-count-independent.
    """
    if interpret is None:
        interpret = default_interpret()
    K, n = a_keys.shape
    n_pad = ceil_to(max(n, 1), _P_CHUNK)
    if n_pad != n:
        pad = ((0, 0), (0, n_pad - n))
        a_keys = jnp.pad(a_keys, pad, constant_values=jnp.inf)
        a_vals = jnp.pad(a_vals, pad, constant_values=jnp.inf)
        a_keep = jnp.pad(a_keep.astype(jnp.int32), pad)
    m_keys, m_vals = merge_sharded_vmem(
        a_keys, a_vals, a_keep.astype(jnp.int32), b_keys, b_vals,
        b_count, p_chunk=min(_P_CHUNK, n_pad), interpret=interpret)
    return m_keys[:, :n], m_vals[:, :n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_compact(a_keys: jax.Array, a_vals: jax.Array, a_keep: jax.Array,
                  b_keys: jax.Array, b_vals: jax.Array, b_count: jax.Array,
                  *, interpret: Optional[bool] = None):
    """K=1 shard-grid dispatch of :func:`merge_compact_sharded`."""
    mk, mv = merge_compact_sharded(
        a_keys[None], a_vals[None], a_keep[None], b_keys[None],
        b_vals[None], jnp.reshape(b_count, (1,)), interpret=interpret)
    return mk[0], mv[0]
