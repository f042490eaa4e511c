"""Flash-attention Pallas TPU kernel (blockwise online softmax).

Tiling (per DESIGN.md §5 / TPU memory hierarchy):

* grid = (B, H, nq, nk); the last axis is sequential ("arbitrary"), so the
  f32 accumulators (m, l, acc) live in VMEM scratch and are carried across
  the kv sweep for a fixed (b, h, iq).
* q block   (1, 1, block_q, hd)   — VMEM, revisited nk times (stays resident)
* k/v block (1, 1, block_k, hd)   — VMEM, streamed from HBM
* GQA is expressed in the BlockSpec ``index_map``: head h reads kv head
  ``h // group`` — no host-side K/V replication, so HBM traffic for K/V is
  divided by the group size exactly as on the MXU target.
* block_q / block_k default to 128 — MXU native tile (128×128) and the f32
  VMEM footprint per core is
  ``block_q*hd (q) + 2*block_k*hd (kv) + block_q*(hd+2) (acc,m,l)`` ≈ 132 KiB
  at hd=128 — far under the ~16 MiB VMEM budget, leaving room for the
  compiler's double buffering of the streamed kv blocks.

Causal / sliding-window handling: blocks fully above the diagonal or fully
outside the window are *skipped* via ``pl.when`` (no MXU work is issued);
partially-masked blocks apply the mask at f32.

The softcap (gemma2) is ``cap * tanh(s / cap)`` applied pre-mask, matching
``ref.attention_reference``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30  # python float: jnp scalars would be captured consts in Pallas


def _attn_kernel(q_ref, k_ref, v_ref, o_ref,           # blocks
                 m_scr, l_scr, acc_scr,                # VMEM scratch
                 *, scale: float, cap: float, causal: bool, window: int,
                 block_q: int, block_k: int, kv_len: int, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # --- static-shape block bounds (dynamic in grid ids, static in shape) --
    q_lo = iq * block_q + q_offset           # absolute position of q row 0
    k_lo = ik * block_k

    # skip blocks with no unmasked element:
    #   causal:  k_lo > q_hi                 (fully above the diagonal)
    #   window:  k_hi < q_lo - window + 1    (fully left of the window)
    run = jnp.bool_(True)
    if causal:
        run &= k_lo <= q_lo + block_q - 1
    if window:
        run &= k_lo + block_k - 1 >= q_lo - window + 1

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, hd_v)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if cap:
            s = jnp.float32(cap) * jnp.tanh(s / jnp.float32(cap))

        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < kv_len                            # kv padding
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,              # (B, H, Sq, hd)
    k: jax.Array,              # (B, K, Skv, hd)
    v: jax.Array,              # (B, K, Skv, hd_v)
    *,
    causal: bool,
    window: int = 0,
    scale: Optional[float] = None,
    cap: float = 0.0,
    kv_len: Optional[int] = None,    # valid kv prefix (pre-padding length)
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Kernel entry in (B, heads, seq, hd) layout; seq dims must be multiples
    of the block sizes (the ops wrapper pads)."""
    B, H, Sq, hd = q.shape
    _, K, Skv, _ = k.shape
    hd_v = v.shape[-1]
    G = H // K
    scale = hd ** -0.5 if scale is None else scale
    kv_len = Skv if kv_len is None else kv_len
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)
    nq, nk = Sq // block_q, Skv // block_k

    kernel = functools.partial(
        _attn_kernel, scale=scale, cap=cap, causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_len=kv_len, q_offset=q_offset)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd_v),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd_v),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd_v), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),        # m — running max
            pltpu.VMEM((block_q,), jnp.float32),        # l — running denom
            pltpu.VMEM((block_q, hd_v), jnp.float32),   # acc — weighted V sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
