"""Public wrappers for the sift-wavefront kernel (single-heap + shard-grid)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import _rows, default_interpret

from .kernel import sift_sharded_vmem


@functools.partial(jax.jit, static_argnames=("interpret",))
def sift_wavefront(a: jax.Array, size: jax.Array, starts: jax.Array,
                   active: jax.Array, *,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Parallel sift-down from ``starts`` (paper §4 ExtractMin phase).

    a: (cap,) f32 — 1-indexed heap, ``a[0] == +inf`` scratch slot.
    size: () int32; starts: (c,) int32 node ids; active: (c,) bool.
    Returns the updated heap array.  (K=1 shard-grid dispatch.)
    """
    return sift_wavefront_sharded(a[None], jnp.reshape(size, (1,)),
                                  starts[None], active[None],
                                  interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def sift_wavefront_sharded(a: jax.Array, size: jax.Array, starts: jax.Array,
                           active: jax.Array, *,
                           interpret: Optional[bool] = None) -> jax.Array:
    """All-shards sift wavefront as ONE ``grid=(K,)`` kernel (DESIGN.md §10).

    a: (K, cap) f32 — K 1-indexed heap shards; size: (K,) int32;
    starts/active: (K, c).  Returns the updated (K, cap) heap stack.
    """
    if interpret is None:
        interpret = default_interpret()
    out = sift_sharded_vmem(_rows.to_rows(a), size, starts, active,
                            interpret=interpret)
    return _rows.from_rows(out, a.shape[1])
