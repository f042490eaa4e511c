"""Public wrappers for the linear-scan kernels (model layout + padding)."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret

from .kernel import rglru_scan_bsr, rwkv6_scan_bhsd


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(
    r: jax.Array,       # (B, S, H, hd)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,       # decay in (0, 1]
    u: jax.Array,       # (H, hd)
    state0: jax.Array,  # (B, H, hd, hd) f32
    *,
    chunk: int = 64,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked RWKV-6 recurrence.  Returns (y (B,S,H,hd) f32, final_state)."""
    if interpret is None:
        interpret = default_interpret()
    B, S, H, hd = r.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk

    def t(x):
        x = jnp.moveaxis(x, 2, 1).astype(jnp.float32)   # (B, H, S, hd)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return x

    rt, kt, vt = t(r), t(k), t(v)
    wt = jnp.moveaxis(w, 2, 1).astype(jnp.float32)
    if pad:
        # pad decay with 1.0 (identity) so padded steps don't touch the state
        wt = jnp.pad(wt, ((0, 0), (0, 0), (0, pad), (0, 0)),
                     constant_values=1.0)

    y, sT = rwkv6_scan_bhsd(rt, kt, vt, wt, u.astype(jnp.float32),
                            state0.astype(jnp.float32),
                            chunk=chunk, interpret=interpret)
    y = jnp.moveaxis(y, 1, 2)[:, :S]
    return y, sT


@functools.partial(jax.jit, static_argnames=("chunk", "block_r", "interpret"))
def rglru_scan(
    a: jax.Array,       # (B, S, R)
    b: jax.Array,
    h0: jax.Array,      # (B, R)
    *,
    chunk: int = 256,
    block_r: int = 512,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Diagonal linear recurrence h_t = a_t h_{t-1} + b_t (RG-LRU)."""
    if interpret is None:
        interpret = default_interpret()
    B, S, R = a.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if R % block_r:
        block_r = R                                  # fall back to one block
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    if pad:
        af = jnp.pad(af, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        bf = jnp.pad(bf, ((0, 0), (0, pad), (0, 0)))
    hs, hT = rglru_scan_bsr(af, bf, h0.astype(jnp.float32),
                            chunk=chunk, block_r=block_r, interpret=interpret)
    return hs[:, :S], hT
