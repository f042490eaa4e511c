"""Sorted merge-compact as a shard-grid Pallas TPU kernel (DESIGN.md §13).

The batched ordered map (``core/batched_map.py``) stores each shard as a
sorted unique-key array.  One combining pass nets a mixed
insert/delete/assign batch down to (a) a ``keep`` mask over the current
array (deletions) and (b) a short sorted run of brand-new pairs
(insertions); the pass then rebuilds the shard with ONE *merge-compact*:

    out = sort(A[keep] ∪ B[:b_count])         (pad tail with (+inf, +inf))

Because both runs are sorted and share no key, the merge needs no sort at
all — only *ranks*:

    ra_i = #kept-A before i           + #valid-B with key <  A_i
    rb_j = j                          + #kept-A with key  <  B_j

are exactly each element's output position, and they are injective (kept-A
keys are strictly increasing, so are valid-B keys, and cross-run ties are
impossible).  The kernel computes the ranks with broadcast-compare
reductions (the exclusive count of kept-A is a rotate-and-add prefix sum)
and materializes the output with masked minima over an output-position
tile — the same no-data-dependent-addressing recipe as
``kernels/label_prop`` (exactly one candidate matches each output
position, so the masked min IS the gather; unmatched positions come out
``+inf``, which is precisely the padding contract).

Layout: ``grid=(K,)`` with one program per map shard (DESIGN.md §10 shard
grid).  Each program reads its own ``(N,)`` key/value/keep blocks and
``(C,)`` insert-run blocks and writes its own ``(N,)`` output blocks — no
cross-program communication.  Output positions stream through a
``fori_loop`` in chunks of ``p_chunk`` rows, so the live mask working set
is O(p_chunk · N) — with p_chunk=256 that prices the compiled kernel at
roughly N ≲ 8K slots per shard under the ~16 MiB VMEM budget (the map's
benchmark scale); the XLA path has no such bound.

Determinism: the merge moves f32 bits without arithmetic and min-
reductions over a single live candidate are exact, so the kernel, the XLA
path (``ops.merge_edits_xla``, for bounded edits) and the numpy oracle
(``ref.py``) agree element-wise for every shard count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._rows import col_to_row

INF = jnp.inf


def _prefix_sum(x):
    """Inclusive prefix sum along the lanes of a (1, N) int32 row: log2(N)
    rotate-and-add steps (the TPU lowering has no cumsum)."""
    n = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    s = 1
    while s < n:
        x = x + jnp.where(lane >= s, pltpu.roll(x, s, 1), 0)
        s *= 2
    return x


def _merge_kernel(bcnt_ref, ak_ref, av_ref, keep_ref, bk_ref, bv_ref,
                  mk_ref, mv_ref, *, n: int, c: int, p_chunk: int):
    shard = pl.program_id(0)
    b_count = bcnt_ref[shard]
    ak = ak_ref[...]                              # (1, n) f32 sorted run A
    av = av_ref[...]
    keep = keep_ref[...] != 0                     # (1, n) survivors of A
    bk = bk_ref[...]                              # (c, 1) f32 sorted run B
    bv = bv_ref[...]
    lane_b = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    b_valid = lane_b < b_count

    # output rank of every kept-A element (a row) and every valid-B
    # element (a column); strictly increasing within each run, no
    # cross-run ties → injective
    keep_i = keep.astype(jnp.int32)
    ex = _prefix_sum(keep_i) - keep_i
    ra = ex + jnp.sum((b_valid & (bk < ak)).astype(jnp.int32), axis=0,
                      keepdims=True)                          # (1, n)
    rb = lane_b + jnp.sum((keep & (ak < bk)).astype(jnp.int32), axis=1,
                          keepdims=True)                      # (c, 1)

    def chunk(ci, _):
        base = pl.multiple_of(ci * p_chunk, p_chunk)
        # masked min gather: at most one candidate per output position
        p_col = base + jax.lax.broadcasted_iota(jnp.int32, (p_chunk, 1), 0)
        ma = keep & (ra == p_col)                             # (P, n)
        ka = jnp.min(jnp.where(ma, ak, INF), axis=1, keepdims=True)
        va = jnp.min(jnp.where(ma, av, INF), axis=1, keepdims=True)
        p_row = base + jax.lax.broadcasted_iota(jnp.int32, (1, p_chunk), 1)
        mb = b_valid & (rb == p_row)                          # (c, P)
        kb = jnp.min(jnp.where(mb, bk, INF), axis=0, keepdims=True)
        vb = jnp.min(jnp.where(mb, bv, INF), axis=0, keepdims=True)
        mk_ref[:, pl.ds(base, p_chunk)] = jnp.minimum(col_to_row(ka), kb)
        mv_ref[:, pl.ds(base, p_chunk)] = jnp.minimum(col_to_row(va), vb)
        return 0

    jax.lax.fori_loop(0, n // p_chunk, chunk, 0)


def merge_sharded_vmem(a_keys: jax.Array, a_vals: jax.Array,
                       a_keep: jax.Array, b_keys: jax.Array,
                       b_vals: jax.Array, b_count: jax.Array,
                       *, p_chunk: int, interpret: bool = False):
    """Merge-compact all K shards as ONE ``grid=(K,)`` kernel.

    a_keys/a_vals: (K, N) f32 with N divisible by ``p_chunk`` (128); a_keep:
    (K, N) int32 0/1; b_keys/b_vals: (K, C) f32 sorted runs; b_count:
    (K,) int32.  Returns ``(m_keys, m_vals)`` each (K, N) f32,
    (+inf, +inf)-padded past the merged length.

    Run A enters as (1, N) rows and run B as (C, 1) columns, so each
    rank is a reduction along the axis it already lies on.
    """
    K, n = a_keys.shape
    c = b_keys.shape[1]
    assert n % p_chunk == 0
    kernel = functools.partial(_merge_kernel, n=n, c=c, p_chunk=p_chunk)
    row = pl.BlockSpec((None, 1, n), lambda k: (k, 0, 0),
                       memory_space=pltpu.VMEM)
    col = pl.BlockSpec((None, c, 1), lambda k: (k, 0, 0),
                       memory_space=pltpu.VMEM)
    mk, mv = pl.pallas_call(
        kernel,
        grid=(K,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # b_count (K,)
            row,                                     # a_keys shard
            row,                                     # a_vals shard
            row,                                     # a_keep shard
            col,                                     # b_keys shard
            col,                                     # b_vals shard
        ],
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct((K, 1, n), jnp.float32),
            jax.ShapeDtypeStruct((K, 1, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(has_side_effects=False),
        interpret=interpret,
    )(b_count.astype(jnp.int32), a_keys.astype(jnp.float32)[:, None],
      a_vals.astype(jnp.float32)[:, None],
      a_keep.astype(jnp.int32)[:, None],
      b_keys.astype(jnp.float32)[:, :, None],
      b_vals.astype(jnp.float32)[:, :, None])
    return mk[:, 0], mv[:, 0]
