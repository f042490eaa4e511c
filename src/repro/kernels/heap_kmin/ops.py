"""Public wrappers for the frontier-search (k-smallest) kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import _rows, default_interpret

from .kernel import kmin_sharded_vmem


@functools.partial(jax.jit, static_argnames=("c_max", "interpret"))
def k_smallest(a: jax.Array, size: jax.Array, n_extract: jax.Array, *,
               c_max: int, interpret: Optional[bool] = None):
    """Ids + values of the ``min(n_extract, size)`` smallest heap nodes
    (paper §4 combiner phase 1), ascending, (0, +inf)-padded.

    a: (cap,) f32 — 1-indexed heap; size/n_extract: () int32.
    Returns (ids (c_max,), vals (c_max,)).  (K=1 shard-grid dispatch.)
    """
    ids, vals = k_smallest_sharded(a[None], jnp.reshape(size, (1,)),
                                   n_extract, c_max=c_max,
                                   interpret=interpret)
    return ids[0], vals[0]


@functools.partial(jax.jit, static_argnames=("c_max", "interpret"))
def k_smallest_sharded(a: jax.Array, size: jax.Array, n_extract: jax.Array,
                       *, c_max: int, interpret: Optional[bool] = None):
    """Per-shard frontier search as ONE ``grid=(K,)`` kernel (DESIGN.md §10).

    a: (K, cap) f32 heap shards; size: (K,) int32; n_extract: () int32
    (the combined batch's global extract count).  Returns
    (ids (K, c_max) int32, vals (K, c_max) f32).
    """
    if interpret is None:
        interpret = default_interpret()
    return kmin_sharded_vmem(_rows.to_rows(a), size, n_extract,
                             c_max=c_max, interpret=interpret)
