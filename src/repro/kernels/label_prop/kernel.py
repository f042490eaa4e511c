"""Connected-component label propagation as a shard-grid Pallas TPU kernel.

The dynamic-graph application (paper §5.1, DESIGN.md §8.3/§11) answers
``connected(u, v)`` by comparing component labels that are rebuilt from the
device-resident edge buffer after update batches.  The rebuild is the
classic scatter-min + pointer-jumping iteration (Shiloach–Vishkin style):

    s   = scatter-min over edges of min(l[u], l[v])    (hooking)
    l'  = min(s, l[s])                                 (pointer jump)

iterated to a fixpoint.  One iteration is a pure, shard-count-independent
function — the Pallas kernel below computes it over ``grid=(K,)`` with the
vertex set partitioned into K contiguous blocks (DESIGN.md §10 shard-grid
recipe, mirroring ``kernels/heap_kmin``): program ``k`` owns vertices
``[k·B, (k+1)·B)`` and produces exactly that block of ``l'``.

Key layout decisions:

* the full label array and the full edge endpoint arrays are broadcast to
  every program as whole-array VMEM inputs; only the OUTPUT is
  block-partitioned, so no cross-program communication is needed.
* gathers (``l[u]``) and the scatter-min both lower to broadcast-compare
  reductions over a ``(e_chunk, ·)`` tile — VPU-friendly masked minima with
  no data-dependent addressing, the portable TPU substitute for arbitrary
  gather/scatter.  Edges stream through a ``fori_loop`` in chunks of
  ``e_chunk``, so the live working set is O(e_chunk · n + B · n) i32 —
  with e_chunk=256 that prices the compiled kernel at roughly n ≲ 8K
  vertices per the ~16 MiB VMEM budget (the §5.1 workload scale).
  Million-vertex graphs need a second tiling level over the vertex axis
  of the masks (a future revision); the XLA twin has no such bound.
* the pointer jump reads the OLD labels (``l[s]``, not ``s[s]``): ``s`` is
  only materialized block-locally, while the old labels are a kernel input
  every program holds.  Jumping through old labels preserves monotone
  convergence (labels only decrease and ``l[x] ≤ x`` is invariant) and
  makes the iteration identical for every K — load-bearing for the
  kernel-vs-ref bit-exactness tests.

Determinism: min-reductions are order-independent, so the kernel, the XLA
twin (``ops.label_step_xla``) and the numpy oracle (``ref.py``) agree
element-wise for every shard count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._rows import col_to_row

# larger than any vertex id (labels live in [0, n_pad) with n_pad < 2^30);
# a plain Python int so the kernel closes over no traced constants
BIG = 1 << 30
_V_TILE = 128       # lanes per vertex tile: block and chunk alignment


def _gather_i32(table: jax.Array, idx: jax.Array) -> jax.Array:
    """table[idx] for a (1, n_pad) i32 row and an (E, 1) index column,
    via broadcast-compare masked min → (E, 1).

    ``idx`` values must lie in [0, n_pad).  Exactly one lane of the
    ``(E, n_pad)`` mask hits per row, so the row-min IS the gather — no
    data-dependent addressing (TPU-portable, see module docstring).
    """
    vrow = jax.lax.broadcasted_iota(jnp.int32, table.shape, 1)
    return jnp.min(jnp.where(idx == vrow, table, BIG), axis=1, keepdims=True)


def _row_to_col(x: jax.Array) -> jax.Array:
    """(1, W) row -> (W, 1) column through one sublane-tile transpose."""
    return jnp.broadcast_to(x, (8, x.shape[1])).T[:, 0:1]


def _label_step_kernel(labels_ref, eu_ref, ev_ref, out_ref,
                       *, block: int, e_cap: int, e_chunk: int, jump: bool):
    k = pl.program_id(0)
    base = pl.multiple_of(k * block, _V_TILE)
    labels = labels_ref[...]                       # (1, n_pad) i32, full row
    own = base + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    s = labels_ref[:, pl.ds(base, block)]          # owned block of l

    def chunk(c, s):
        off = pl.multiple_of(c * e_chunk, e_chunk)
        eu = _row_to_col(eu_ref[:, pl.ds(off, e_chunk)])   # (EC, 1) i32
        ev = _row_to_col(ev_ref[:, pl.ds(off, e_chunk)])
        m = jnp.minimum(_gather_i32(labels, eu), _gather_i32(labels, ev))
        # scatter-min of m into the owned vertex block (masked column-min;
        # padding edges are (0,0) self-loops — a no-op contribution)
        cu = jnp.min(jnp.where(eu == own, m, BIG), axis=0, keepdims=True)
        cv = jnp.min(jnp.where(ev == own, m, BIG), axis=0, keepdims=True)
        return jnp.minimum(s, jnp.minimum(cu, cv))

    out_ref[...] = jax.lax.fori_loop(0, e_cap // e_chunk, chunk, s)
    if not jump:
        return

    # pointer jump through the OLD labels (see module docstring), one
    # 128-vertex tile at a time to bound the gather mask
    def jump_tile(j, _):
        o = pl.multiple_of(j * _V_TILE, _V_TILE)
        sj = out_ref[:, pl.ds(o, _V_TILE)]
        g = col_to_row(_gather_i32(labels, _row_to_col(sj)))
        out_ref[:, pl.ds(o, _V_TILE)] = jnp.minimum(sj, g)
        return 0

    jax.lax.fori_loop(0, block // _V_TILE, jump_tile, 0)


def label_step_sharded_vmem(labels: jax.Array, eu: jax.Array, ev: jax.Array,
                            *, n_shards: int, e_chunk: int,
                            interpret: bool = False,
                            jump: bool = True) -> jax.Array:
    """One scatter-min + pointer-jump iteration as ONE ``grid=(K,)`` kernel
    (``jump=False``: the scatter-min alone, one hop of propagation).

    labels: (n_pad,) i32 with n_pad divisible by ``n_shards`` into blocks
    of a multiple of 128 vertices; eu/ev: (e_cap,) i32 edge endpoints,
    (0, 0)-padded, e_cap divisible by ``e_chunk`` (a multiple of 128).
    Returns the next label array, (n_pad,) i32.  All three arrays enter
    as (1, ·) lane rows, so every dynamic slice is lane-tile aligned.
    """
    (n_pad,) = labels.shape
    (e_cap,) = eu.shape
    assert n_pad % n_shards == 0 and e_cap % e_chunk == 0
    block = n_pad // n_shards
    assert block % _V_TILE == 0 and e_chunk % _V_TILE == 0
    kernel = functools.partial(_label_step_kernel, block=block,
                               e_cap=e_cap, e_chunk=e_chunk, jump=jump)
    out = pl.pallas_call(
        kernel,
        grid=(n_shards,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),   # labels (full)
            pl.BlockSpec(memory_space=pltpu.VMEM),   # eu (full)
            pl.BlockSpec(memory_space=pltpu.VMEM),   # ev (full)
        ],
        out_specs=pl.BlockSpec((1, block), lambda k: (0, k),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        compiler_params=pltpu.CompilerParams(has_side_effects=False),
        interpret=interpret,
    )(labels.astype(jnp.int32)[None], eu.astype(jnp.int32)[None],
      ev.astype(jnp.int32)[None])
    return out[0]
