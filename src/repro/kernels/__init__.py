"""Pallas TPU kernels for the perf-critical compute hot-spots.

Each subpackage ships three artifacts:

* ``kernel.py`` — the ``pl.pallas_call`` + ``BlockSpec`` TPU kernel (the
  *target* artifact; tiled for VMEM/MXU),
* ``ops.py``    — the jit'd public wrapper (layout handling, padding, and
  the interpret-mode choice of :func:`default_interpret`),
* ``ref.py``    — the pure-``jnp`` oracle the tests ``assert_allclose``
  against.

Kernels:
  flash_attention — blockwise online-softmax attention (GQA, causal,
                    sliding window, logit softcap).  Prefill/train hot spot.
  linear_scan     — chunked gated linear recurrences: RWKV-6 (matrix state,
                    data-dependent per-channel decay) and RG-LRU (diagonal).
  heap_kmin       — paper §4 combiner phase 1: the frontier search.
  heap_sift       — paper §4 ExtractMin phase: the parallel sift-down
                    wavefront over a VMEM-resident array heap.
  heap_insert     — paper §4 Insert phase: level-synchronous collective
                    insert with InsertSet split rows.
  sorted_merge    — the ordered map's and the sketch's merge-compact
                    rebuild (and its bounded-edit XLA merge).
  label_prop      — one scatter-min + pointer-jump connectivity step.
"""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Whether a kernel called with ``interpret=None`` runs interpreted.

    The one place the choice is made: compiled by Mosaic on a TPU, the
    Pallas interpreter on the CPU backend (the test tier).  Any other
    backend is refused — silently interpreting there would hide what the
    TPU compiler rejects and run orders of magnitude slower.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {backend!r}")
