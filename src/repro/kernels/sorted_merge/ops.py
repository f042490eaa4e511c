"""Public wrappers for the sorted merge-compact kernel (DESIGN.md §13).

Two bit-exact realizations of the map's rebuild primitive:

* ``merge_compact_xla`` — the pure-XLA twin (rank computation by
  broadcast-compare + cumsum, materialization by predicated scatter with
  a scratch slot).  Vmappable; used as the CPU/fallback path by the
  batched map and as the semantics anchor of the parity tests.
* ``merge_compact_sharded`` — the ``grid=(K,)`` Pallas kernel
  (``kernel.py``): one program per map shard, masked row-min
  materialization, no data-dependent addressing.  ``merge_compact`` is
  the K=1 convenience dispatch.

Both produce the SAME bits: the merge moves f32 values without
arithmetic, so kernel ≡ XLA twin ≡ numpy ref element-wise for every
shard count (tested like ``kernels/label_prop``).
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


def merge_compact_xla(a_keys: jax.Array, a_vals: jax.Array,
                      a_keep: jax.Array, b_keys: jax.Array,
                      b_vals: jax.Array, b_count: jax.Array):
    """Pure-XLA twin of one merge-compact (element-wise identical).

    a_keys/a_vals: (N,) f32 sorted run with arbitrary ``a_keep`` mask;
    b_keys/b_vals: (C,) f32 sorted insert run, first ``b_count`` valid.
    Returns ``(m_keys, m_vals)`` (N,) f32, (+inf, +inf)-padded.  Same
    preconditions as the kernel: kept-A and valid-B strictly increasing,
    no shared keys, merged length ≤ N.
    """
    (n,) = a_keys.shape
    (c,) = b_keys.shape
    keep = a_keep.astype(bool)
    b_valid = jnp.arange(c) < b_count
    ex = jnp.cumsum(keep.astype(jnp.int32)) - keep.astype(jnp.int32)
    ra = ex + jnp.sum((b_valid[None, :] & (b_keys[None, :]
                                           < a_keys[:, None]))
                      .astype(jnp.int32), axis=1)
    rb = jnp.arange(c, dtype=jnp.int32) + jnp.sum(
        (keep[None, :] & (a_keys[None, :] < b_keys[:, None]))
        .astype(jnp.int32), axis=1)
    # predicated scatter: every masked-off lane writes the scratch slot n
    # with the SAME (+inf) payload, so duplicate indices stay defined
    ta = jnp.clip(jnp.where(keep, ra, n), 0, n)
    tb = jnp.clip(jnp.where(b_valid, rb, n), 0, n)
    m_keys = jnp.full((n + 1,), INF, jnp.float32)
    m_vals = jnp.full((n + 1,), INF, jnp.float32)
    m_keys = m_keys.at[ta].set(jnp.where(keep, a_keys, INF))
    m_vals = m_vals.at[ta].set(jnp.where(keep, a_vals, INF))
    m_keys = m_keys.at[tb].set(jnp.where(b_valid, b_keys, INF))
    m_vals = m_vals.at[tb].set(jnp.where(b_valid, b_vals, INF))
    return m_keys[:n], m_vals[:n]


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
