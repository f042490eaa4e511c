"""Public wrapper: (B, S, H, hd) layout, padding, backend dispatch.

On non-TPU backends (this CPU container) the Pallas kernel runs in
``interpret=True`` mode — the kernel body executes step-by-step on CPU,
which validates the TPU program's semantics without TPU hardware.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret

from .kernel import flash_attention_bhsd


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "cap", "q_offset",
                     "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,              # (B, Sq, H, hd)
    k: jax.Array,              # (B, Skv, K, hd)
    v: jax.Array,              # (B, Skv, K, hd_v)
    *,
    causal: bool = True,
    window: int = 0,
    scale: Optional[float] = None,
    cap: float = 0.0,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention in model layout (B, S, heads, hd) → (B, Sq, H, hd_v)."""
    if interpret is None:
        interpret = default_interpret()
    B, Sq, H, hd = q.shape
    _, Skv, K, _ = k.shape
    hd_v = v.shape[-1]

    block_q = min(block_q, max(Sq, 1))
    block_k = min(block_k, max(Skv, 1))
    pad_q = (-Sq) % block_q
    pad_k = (-Skv) % block_k

    qt = jnp.moveaxis(q, 2, 1)          # (B, H, Sq, hd)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    o = flash_attention_bhsd(
        qt, kt, vt, causal=causal, window=window, scale=scale, cap=cap,
        kv_len=Skv, q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=interpret)
    o = jnp.moveaxis(o, 1, 2)[:, :Sq]   # (B, Sq, H, hd_v)
    return o
