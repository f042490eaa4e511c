"""Chunked gated linear-recurrence Pallas TPU kernels.

Two recurrences power the sub-quadratic architectures:

* **RWKV-6** (rwkv6-3b): matrix state S ∈ R^{dk×dv} per head with
  *data-dependent per-channel* decay w_t — the sequential scan does
  O(S·dk·dv) FMA work with a state round-trip per token.  The kernel uses
  the *chunked factored* formulation: for a chunk of T tokens,

      la_t   = Σ_{j≤t} log w_j                  (cumsum, (T,dk))
      q̃_t   = r_t ∘ exp(la_{t-1})              (≤ 1 — safe)
      k̃_s   = k_s ∘ exp(-la_s)                 (≥ 1 — see note)
      intra  = tril(q̃ k̃ᵀ, -1) + diag(Σ_c r∘u∘k)
      y      = intra @ v + q̃ @ S0
      S_new  = diag(exp(la_T)) S0 + (k ∘ exp(la_T - la))ᵀ @ v   (≤ 1 — safe)

  turning the token scan into three MXU matmuls per chunk:
  (T,dk)×(dk,T), (T,T)×(T,dv), (T,dk)×(dk,dv).  The only growing factor is
  exp(-la_s) inside a chunk; with chunk T=64 the validity domain is
  Σ_chunk |log w| ≲ 80 per channel (f32 overflow at e^88) — trained RWKV
  decays sit at |log w| ≈ 0.02–2, giving ≥ 40× headroom.  The sweep test
  samples decays across this domain and asserts allclose vs the exact scan.

* **RG-LRU** (recurrentgemma-2b): *diagonal* state h ∈ R^R,
  h_t = a_t h_t-1 + b_t.  The kernel keeps h in VMEM scratch and walks the
  chunk with an in-register fori_loop (exact — no factored rescaling), so
  HBM traffic is exactly one read of (a, b) and one write of h per token:
  the op is memory-bound and the kernel hits the streaming roofline.

Grid layout (both): (B, H|nR, nT) with the chunk axis **sequential**
("arbitrary") so the running state lives in VMEM scratch across chunks.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# RWKV-6 chunked kernel
# ---------------------------------------------------------------------------
def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                  y_ref, sT_ref, s_scr, *, chunk: int):
    it = pl.program_id(2)
    nt = pl.num_programs(2)
    T = chunk

    @pl.when(it == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)          # (T, dk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)          # (T, dv)
    w = w_ref[0, 0].astype(jnp.float32)          # (T, dk) decay ∈ (0,1]
    u = u_ref[0].astype(jnp.float32)             # (dk,)
    s0 = s_scr[...]                              # (dk, dv)

    logw = jnp.log(w)
    la = jnp.cumsum(logw, axis=0)                # (T, dk): la_t
    la_prev = la - logw                          # exclusive cumsum: la_{t-1}
    laT = la[T - 1]                              # (dk,)

    qt = r * jnp.exp(la_prev)                    # ≤ |r|
    kt = k * jnp.exp(-la)                        # validity domain: see module doc

    s = jax.lax.dot_general(qt, kt, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (T, T)
    row = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    s = jnp.where(col < row, s, 0.0)             # strictly lower triangular
    diag = jnp.sum(r * u[None, :] * k, axis=-1)  # (T,) current-token bonus
    s = s + jnp.where(col == row, diag[:, None], 0.0)

    y = jax.lax.dot_general(s, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y + jax.lax.dot_general(qt, s0, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    k_end = k * jnp.exp(laT[None, :] - la)       # ≤ |k|
    s_new = jnp.exp(laT)[:, None] * s0 + jax.lax.dot_general(
        k_end, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_scr[...] = s_new

    @pl.when(it == nt - 1)
    def _flush():
        sT_ref[0, 0] = s_new.astype(sT_ref.dtype)


def rwkv6_scan_bhsd(
    r: jax.Array,       # (B, H, S, hd)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,       # (H, hd)
    state0: jax.Array,  # (B, H, hd, hd) f32
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    B, H, S, hd = r.shape
    assert S % chunk == 0, (S, chunk)
    nt = S // chunk

    kernel = functools.partial(_rwkv6_kernel, chunk=chunk)
    seq_spec = pl.BlockSpec((1, 1, chunk, hd), lambda b, h, t: (b, h, t, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, H, nt),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, hd), lambda b, h, t: (h, 0)),          # u
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, t: (b, h, 0, 0)),  # S0
        ],
        out_specs=[
            seq_spec,                                               # y
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, t: (b, h, 0, 0)),  # S_T
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u, state0)


# ---------------------------------------------------------------------------
# RG-LRU diagonal kernel
# ---------------------------------------------------------------------------
def _rglru_kernel(a_ref, b_ref, h0_ref, y_ref, hT_ref, h_scr, *, chunk: int):
    it = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(it == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)             # (T, Rb)
    b = b_ref[0].astype(jnp.float32)

    def step(t, carry):
        h, y = carry
        h = a[t] * h + b[t]
        y = jax.lax.dynamic_update_slice_in_dim(y, h[None], t, axis=0)
        return h, y

    h0 = h_scr[...]
    y0 = jnp.zeros_like(a)
    h, y = jax.lax.fori_loop(0, chunk, step, (h0, y0))
    y_ref[0] = y.astype(y_ref.dtype)
    h_scr[...] = h

    @pl.when(it == nt - 1)
    def _flush():
        hT_ref[0] = h.astype(hT_ref.dtype)


def rglru_scan_bsr(
    a: jax.Array,       # (B, S, R)
    b: jax.Array,       # (B, S, R)
    h0: jax.Array,      # (B, R)
    *,
    chunk: int = 256,
    block_r: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    B, S, R = a.shape
    assert S % chunk == 0, (S, chunk)
    block_r = min(block_r, R)
    assert R % block_r == 0, (R, block_r)
    nt, nr = S // chunk, R // block_r

    kernel = functools.partial(_rglru_kernel, chunk=chunk)
    seq_spec = pl.BlockSpec((1, chunk, block_r), lambda b_, j, t: (b_, t, j))
    vec_spec = pl.BlockSpec((1, block_r), lambda b_, j, t: (b_, j))
    return pl.pallas_call(
        kernel,
        grid=(B, nr, nt),
        in_specs=[seq_spec, seq_spec, vec_spec],
        out_specs=[seq_spec, vec_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, R), jnp.float32),
            jax.ShapeDtypeStruct((B, R), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_r,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0)
