"""Public wrappers for the collective-insert kernel (single + shard-grid)."""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import _rows, default_interpret

from .kernel import insert_sharded_vmem


@functools.partial(jax.jit, static_argnames=("interpret",))
def insert_chunk(a: jax.Array, size: jax.Array, chunk_vals: jax.Array,
                 m_chunk: jax.Array, *,
                 interpret: Optional[bool] = None):
    """Collective insert of one level-chunk (paper §4 Insert phase).

    a: (cap,) f32 heap (1-indexed, a[0]=+inf); chunk_vals: (C,) sorted asc,
    +inf-padded; m_chunk: () int32 ≤ C; all targets size+1..size+m on one
    level.  Returns (new_a, new_size).  (K=1 shard-grid dispatch.)
    """
    out, new_size = insert_chunk_sharded(
        a[None], jnp.reshape(size, (1,)), chunk_vals[None],
        jnp.reshape(m_chunk, (1,)), interpret=interpret)
    return out[0], new_size[0]


@functools.partial(jax.jit, static_argnames=("interpret", "pre_padded"))
def insert_chunk_sharded(a: jax.Array, size: jax.Array,
                         chunk_vals: jax.Array, m_chunk: jax.Array, *,
                         interpret: Optional[bool] = None,
                         pre_padded: bool = False):
    """All-shards level-chunk insert as ONE ``grid=(K,)`` kernel
    (DESIGN.md §10).

    a: (K, cap) f32 heap shards; chunk_vals: (K, C) sorted asc, +inf
    padded; m_chunk: (K,) int32 ≤ C (a shard may be empty this chunk);
    per shard, all targets size_k+1..size_k+m_k lie on one tree level.
    Returns (new_a (K, cap), new_size (K,)).

    ``pre_padded=True``: ``a`` is already the kernel's ``(K, R, 128)``
    row layout (``_rows.to_rows``) with ≥ C slots of +inf headroom past
    every shard's size (the kernel streams one contiguous C-wide level
    window), and the result comes back in it — lets a chunk LOOP convert
    once instead of re-padding the whole heap stack every iteration.
    """
    if interpret is None:
        interpret = default_interpret()
    C = chunk_vals.shape[1]
    a3 = a if pre_padded else _rows.to_rows(a, min_width=a.shape[1] + C,
                                            min_rows=2)
    max_depth = int(math.ceil(math.log2(a3.shape[1] * _rows.LANES))) + 1
    out = insert_sharded_vmem(a3, size, chunk_vals, m_chunk,
                              max_depth=max_depth, interpret=interpret)
    if not pre_padded:
        out = _rows.from_rows(out, a.shape[1])
    return out, size + m_chunk
