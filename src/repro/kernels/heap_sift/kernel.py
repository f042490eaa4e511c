"""Paper §4 ExtractMin phase as a shard-grid Pallas TPU kernel.

The batched PQ's hot loop is the *parallel sift-down wavefront*: ``c``
cursors (one per extracted node) walk disjoint root-to-leaf paths of the
array heap, swapping a parent with its smaller child.  On the shared-memory
host of the paper this uses hand-over-hand per-node spin locks; the TPU
adaptation (DESIGN.md §2) is the *level-synchronous* schedule the paper's
own Thm-4 proof reasons about: at global step ``t`` exactly the cursors with
``t >= delay_i`` advance one level, where ``delay_i = d_max - depth(start_i)``
staggers the cursors so two active cursors are always ≥ 2 levels apart —
the per-step loads/stores are then provably conflict-free and the result
equals the paper's sequential execution SE (deepest-first).

Kernel layout (DESIGN.md §10 — the shard-grid revision):

* the kernel runs over ``grid=(K,)`` — one program per heap shard of the
  K-sharded queue (``sharded_pq.py``).  The heap stack is held in the
  ``(K, cap/128, 128)`` row layout (``kernels/_rows.py``), block-sliced so
  each program sees only its own shard's ``(cap/128, 128)`` block in VMEM;
  the input and output blocks are double-buffered, which bounds the
  per-shard capacity (``_rows.MAX_HEAP_CAPACITY``).  ``K=1`` recovers the
  single-heap kernel and is what ``BatchedPriorityQueue`` uses via the ops
  wrapper.
* per-shard ``size`` / ``starts`` / ``active`` live in SMEM, indexed by
  ``pl.program_id(0)`` (scalar-unit reads).
* cursor state (pos, active, delay) lives in SMEM scratch; each step does
  two row loads and two row read-modify-writes per cursor (the children of
  a node share one row) — scalar-unit work: the paper's phase is
  latency- not throughput-bound, and fusing the whole wavefront in one
  kernel removes the per-level host round-trip of the pure-XLA version).
* conditional stores write to slot 0 when inactive: the heap is 1-indexed
  and ``a[0]`` is the designated +inf scratch slot, so "store INF to 0" is
  the identity — branch-free predication without ``lax.cond``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _rows

INF = jnp.inf


def _sift_kernel(size_ref, starts_ref, active_ref, a_ref, out_ref,
                 pos_s, act_s, delay_s, *, c: int, cap: int):
    # one program per shard: scalars are rows of the (K, ...) SMEM inputs;
    # the cursor state lives in SMEM scratch (scalar-unit reads/writes)
    shard = pl.program_id(0)
    # copy the shard's heap block into the output buffer, then mutate in place
    out_ref[...] = a_ref[...]
    size = size_ref[shard]

    def init(i, carry):
        d_max, n_act = carry
        st, ac = starts_ref[shard, i], active_ref[shard, i] != 0
        pos_s[i] = st
        act_s[i] = ac.astype(jnp.int32)
        return (jnp.maximum(d_max, jnp.where(ac, _rows.depth(st), 0)),
                n_act + ac.astype(jnp.int32))

    d_max, n_act = jax.lax.fori_loop(0, c, init, (jnp.int32(0), jnp.int32(0)))

    def init_delay(i, _):
        delay_s[i] = d_max - _rows.depth(pos_s[i])
        return 0

    jax.lax.fori_loop(0, c, init_delay, 0)

    def cursor(i, carry):
        step, n_act = carry
        v = pos_s[i]
        moving = (act_s[i] != 0) & (step >= delay_s[i])
        vc = jnp.where(moving, v, 0)
        l, r = 2 * vc, 2 * vc + 1
        av = _rows.load1(out_ref, vc)
        lraw, rraw = _rows.load_pair(out_ref, jnp.minimum(l, cap - 2))
        lv = jnp.where(moving & (l <= size) & (l < cap), lraw, INF)
        rv = jnp.where(moving & (r <= size) & (r < cap), rraw, INF)
        wv = jnp.minimum(lv, rv)
        w = jnp.where(lv <= rv, l, r)
        swap = moving & (wv < av)
        # predicated swap through the a[0] = +inf scratch slot
        _rows.store1(out_ref, jnp.where(swap, vc, 0),
                     jnp.where(swap, wv, INF))
        _rows.store1(out_ref, jnp.where(swap, w, 0),
                     jnp.where(swap, av, INF))
        pos_s[i] = jnp.where(swap, w, v)
        stop = moving & ~swap
        act_s[i] = jnp.where(stop, 0, act_s[i])
        return step, n_act - stop.astype(jnp.int32)

    def body(carry):
        step, n_act = carry
        _, n_act = jax.lax.fori_loop(0, c, cursor, (step, n_act))
        return step + 1, n_act

    def cond(carry):
        return carry[1] > 0

    jax.lax.while_loop(cond, body, (jnp.int32(0), n_act))


def sift_sharded_vmem(a: jax.Array, size: jax.Array, starts: jax.Array,
                      active: jax.Array, *, interpret: bool = False):
    """a: (K, R, 128) f32 1-indexed heaps in the row layout (``_rows``),
    a[k, 0]=+inf; size: (K,) int32; starts/active: (K, c) int32.  One
    grid program per shard."""
    K, R, _ = a.shape
    _, c = starts.shape
    kernel = functools.partial(_sift_kernel, c=c, cap=R * _rows.LANES)
    heap = pl.BlockSpec((None, R, _rows.LANES), lambda k: (k, 0, 0),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid=(K,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # size (K,)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # starts (K, c)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # active (K, c)
            heap,                                    # heap shard
        ],
        out_specs=heap,
        out_shape=jax.ShapeDtypeStruct((K, R, _rows.LANES), a.dtype),
        scratch_shapes=[pltpu.SMEM((c,), jnp.int32)] * 3,
        compiler_params=pltpu.CompilerParams(
            has_side_effects=False),
        interpret=interpret,
    )(size.astype(jnp.int32), starts.astype(jnp.int32),
      active.astype(jnp.int32), a)
