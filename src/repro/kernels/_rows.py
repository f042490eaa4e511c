"""Layout helpers shared by the structure kernels.  The heap kernels
hold a 1-D array per shard as ``(rows, 128)`` VMEM tiles.

Mosaic tiles the last two dimensions of a VMEM block by (8, 128), so a
``(K, cap)`` heap stack cannot be block-sliced one shard row at a time,
and it cannot load a single element at a data-dependent lane offset.
Stored as ``(K, cap/128, 128)`` instead, a shard is one ``(rows, 128)``
block, and element ``i`` is lane ``i % 128`` of row ``i // 128``: a
dynamic one-row load followed by a lane select.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
INF = jnp.inf
# Per-shard heap slots the three heap kernels (kmin, sift, insert) hold:
# each program keeps its whole shard block in VMEM, double-buffered in and
# out; compiled for a v5e at this size in tests/test_tpu_compile.py.
MAX_HEAP_CAPACITY = 1 << 20


def require_heap_fits(capacity: int) -> None:
    """Refuse, at construction, a heap the VMEM-resident kernels cannot
    hold (rather than failing mid-run on the device)."""
    if capacity > MAX_HEAP_CAPACITY:
        raise ValueError(
            f"use_pallas keeps each heap shard in VMEM: per-shard capacity "
            f"{capacity} exceeds the heap kernels' limit of "
            f"{MAX_HEAP_CAPACITY} slots")


def ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def to_rows(a: jax.Array, min_width: int = 0, min_rows: int = 1
            ) -> jax.Array:
    """(K, n) -> (K, R, 128), +inf padded to ``R·128 ≥ max(n, min_width)``
    and ``R ≥ min_rows``."""
    K, n = a.shape
    width = max(ceil_to(max(n, min_width, 1), LANES), min_rows * LANES)
    if width != n:
        a = jnp.concatenate(
            [a, jnp.full((K, width - n), INF, a.dtype)], axis=1)
    return a.reshape(K, width // LANES, LANES)


def from_rows(a3: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`to_rows`: (K, R, 128) -> (K, n)."""
    K = a3.shape[0]
    return a3.reshape(K, -1)[:, :n]


def lane_iota(width: int = LANES) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)


def load1(ref, idx):
    """Element ``idx`` of a ``(rows, 128)`` f32 ref (scalar)."""
    row = ref[pl.ds(idx >> 7, 1), :]
    return jnp.min(jnp.where(lane_iota() == (idx & (LANES - 1)), row, INF))


def load_pair(ref, idx):
    """Elements ``idx`` and ``idx + 1`` for an EVEN ``idx`` — both sit in
    one row, so one load serves a heap node's two children."""
    row = ref[pl.ds(idx >> 7, 1), :]
    col = idx & (LANES - 1)
    lane = lane_iota()
    return (jnp.min(jnp.where(lane == col, row, INF)),
            jnp.min(jnp.where(lane == col + 1, row, INF)))


def store1(ref, idx, val):
    """Write ``val`` at element ``idx`` (read-modify-write of its row)."""
    r = pl.ds(idx >> 7, 1)
    ref[r, :] = jnp.where(lane_iota() == (idx & (LANES - 1)), val, ref[r, :])


def col_to_row(x: jax.Array) -> jax.Array:
    """(P, 1) column -> (1, P) row through one square transpose."""
    p = x.shape[0]
    return jnp.broadcast_to(x, (p, p)).T[0:1, :]


def depth(v):
    """``floor(log2(max(v, 1)))`` of a scalar int32 by comparisons (the
    scalar unit has no count-leading-zeros)."""
    d = jnp.int32(0)
    for b in range(1, 31):
        d = d + (v >= (1 << b)).astype(jnp.int32)
    return d
