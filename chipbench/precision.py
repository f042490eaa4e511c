"""Rounding of host values to a stated precision (the plain references
store keys and values at the precision the configuration states; the
control stores them one step lower)."""
from __future__ import annotations

import ml_dtypes
import numpy as np

TINY = float(np.finfo(np.float32).tiny)
DTYPES = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}
# the nearest precision below each stated one: the control's
LOWER = {"float32": "bfloat16"}


def rounder(precision: str):
    """``f(array) -> float64 array`` rounded through ``precision``, with
    subnormals flushed to zero as the device does."""
    dt = DTYPES[precision]

    def f(a):
        r = np.asarray(a, np.float64).astype(dt).astype(np.float64)
        return np.where(np.abs(r) < TINY, 0.0, r)

    return f
