"""Paper §4 combiner phase 1 as a shard-grid Pallas TPU kernel.

The combiner's first job is the Dijkstra-like *frontier search*: find the
``min(n_extract, size)`` smallest nodes of the array heap.  The frontier
holds candidate nodes whose parents were already taken; the heap property
makes the running frontier-min the global next-min, so ``c_max`` dependent
steps of (argmin over ``F = 2·c_max+1`` lanes, then two child loads)
produce the answer in ascending order.

The pure-XLA version (``core/batched_pq._k_smallest``) runs this as a
``lax.scan`` of ``c_max`` argmin steps and is vmapped K times by the
sharded queue — every step materializes the full frontier in HBM-visible
buffers and the vmap multiplies the fusion barriers.  Here the whole
search is ONE kernel over ``grid=(K,)`` (DESIGN.md §10): per shard the
frontier lives in registers across a ``fori_loop``, each step does one
row load from the shard's heap block (a node's two children share a row
of the ``(rows, 128)`` layout, ``kernels/_rows.py``) and two scalar SMEM
stores of the (id, value) answer — no intermediate HBM traffic, no vmap.

Determinism: ``jnp.argmin`` takes the first minimum, exactly as the XLA
twin, so both paths emit identical candidate lists — load-bearing for the
sharded queue, which reuses the merged candidates as each shard's phase-1
result (prefix-stability, see ``sharded_pq.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _rows

INF = jnp.inf


def _kmin_kernel(ne_ref, size_ref, a_ref, ids_ref, vals_ref,
                 *, c_max: int, cap: int):
    shard = pl.program_id(0)
    ne = ne_ref[0]
    size = size_ref[shard]
    F = 2 * c_max + 1
    lane = _rows.lane_iota(_rows.ceil_to(F, _rows.LANES))  # lanes >= F inert

    root = jnp.where(size >= 1, _rows.load1(a_ref, 1), INF)
    f_ids = jnp.where(lane == 0, 1, 0)
    f_vals = jnp.where(lane == 0, root, INF)

    def step(i, carry):
        f_ids, f_vals, nfree = carry
        # argmin, first minimum (as jnp.argmin): the XLA twin's tie order
        val = jnp.min(f_vals)
        j = jnp.min(jnp.where(f_vals == val, lane, F))
        v = jnp.max(jnp.where(lane == j, f_ids, 0))
        active = (i < ne) & (val < INF)
        l, r = 2 * v, 2 * v + 1
        lraw, rraw = _rows.load_pair(a_ref, jnp.clip(l, 0, cap - 2))
        lval = jnp.where(active & (l <= size), lraw, INF)
        rval = jnp.where(active & (r <= size), rraw, INF)
        # replace the taken slot with the left child, append the right child
        jt = jnp.where(active, j, -1)
        f_ids = jnp.where(lane == jt, l, f_ids)
        f_vals = jnp.where(lane == jt, lval, f_vals)
        st = jnp.where(active, nfree, -1)
        f_ids = jnp.where(lane == st, r, f_ids)
        f_vals = jnp.where(lane == st, rval, f_vals)
        nfree = nfree + active.astype(jnp.int32)
        ids_ref[shard, i] = jnp.where(active, v, 0)
        vals_ref[shard, i] = jnp.where(active, val, INF)
        return f_ids, f_vals, nfree

    jax.lax.fori_loop(0, c_max, step, (f_ids, f_vals, jnp.int32(1)))


def kmin_sharded_vmem(a: jax.Array, size: jax.Array, n_extract: jax.Array,
                      *, c_max: int, interpret: bool = False):
    """a: (K, R, 128) f32 heap shards in the row layout (``_rows``);
    size: (K,) int32; n_extract: () int32 (global — the same batch is
    combined across shards).  Returns (ids (K, c_max) int32, vals (K,
    c_max) f32), ascending per shard, (0, +inf)-padded.  One grid program
    per shard; the answers are scalar SMEM writes."""
    K, R, _ = a.shape
    kernel = functools.partial(_kmin_kernel, c_max=c_max,
                               cap=R * _rows.LANES)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(K,),
        in_specs=[
            smem,                                    # n_extract (1,)
            smem,                                    # size (K,)
            pl.BlockSpec((None, R, _rows.LANES), lambda k: (k, 0, 0),
                         memory_space=pltpu.VMEM),   # heap shard
        ],
        out_specs=[smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((K, c_max), jnp.int32),
            jax.ShapeDtypeStruct((K, c_max), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=False),
        interpret=interpret,
    )(jnp.reshape(n_extract.astype(jnp.int32), (1,)),
      size.astype(jnp.int32), a)
