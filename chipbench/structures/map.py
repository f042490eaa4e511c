"""The ordered map (registry name ``map``): preload, requests, plain
reference and final state.

Keys are record ids 1..N as exact f32, values f32 record handles.  The
reference is a dict with the map's per-operation semantics (``assign``
and ``delete`` answer "was present", ``insert`` "was absent", ``lookup``
the value or None) and the served executor's batch rule: a batch applies
its updates in arrival order, then answers its reads.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

import precision as _precision
import traffic as _traffic

READS = {"lookup"}


def preload(cfg: Dict, seed: int) -> Dict[str, np.ndarray]:
    """Records 1..N with seeded handles."""
    n = int(cfg["keys"]["count"])
    rng = np.random.default_rng([seed, 0])
    return {"keys": np.arange(1, n + 1, dtype=np.float32),
            "vals": _traffic.draw_values(cfg["values"], rng, n)}


def make_kwargs(cfg: Dict, data: Dict[str, np.ndarray]) -> Dict[str, Any]:
    return dict(capacity=int(cfg["capacity"]), c_max=int(cfg["c_max"]),
                n_shards=int(cfg["n_shards"]),
                key_range=tuple(float(x) for x in cfg["key_range"]),
                items=list(zip(data["keys"].tolist(),
                               data["vals"].tolist())))


def request_input(method: str, key: float, value: float) -> Any:
    if method in ("lookup", "delete"):
        return key
    if method in ("insert", "assign"):
        return (key, value)
    raise ValueError(f"the map reference has no method {method!r}")


def fetch_state(ds) -> Dict[str, np.ndarray]:
    """The live (key, value) pairs in shard order, as the device holds
    them (the shard concatenation is globally sorted)."""
    import jax

    keys, vals, size = jax.device_get((ds.state.keys, ds.state.vals,
                                       ds.state.size))
    return {"keys": np.concatenate([keys[k, :size[k]]
                                    for k in range(len(size))]),
            "vals": np.concatenate([vals[k, :size[k]]
                                    for k in range(len(size))])}


class Reference:
    """A dict from key to value, every number rounded through
    ``precision`` on the way in."""

    def __init__(self, data: Dict[str, np.ndarray], precision: str,
                 cfg: Dict):
        self.q = _precision.rounder(precision)
        self.d = dict(zip(self.q(data["keys"]).tolist(),
                          self.q(data["vals"]).tolist()))

    def _one(self, x: float) -> float:
        return float(self.q(x))

    def step(self, methods: Sequence[str], inputs: Sequence[Any]
             ) -> List[Any]:
        out: List[Any] = [None] * len(methods)
        d = self.d
        for i, (m, x) in enumerate(zip(methods, inputs)):
            if m in READS:
                continue
            key = self._one(x if m == "delete" else x[0])
            present = key in d
            if m == "assign":
                if present:
                    d[key] = self._one(x[1])
            elif m == "insert":
                if not present:
                    d[key] = self._one(x[1])
            elif m == "delete":
                d.pop(key, None)
            else:
                raise ValueError(f"unknown update {m!r}")
            out[i] = (not present) if m == "insert" else present
        for i, (m, x) in enumerate(zip(methods, inputs)):
            if m in READS:
                out[i] = d.get(self._one(x))
        return out

    def state(self) -> Dict[str, np.ndarray]:
        keys = np.fromiter(self.d.keys(), np.float64, len(self.d))
        vals = np.fromiter(self.d.values(), np.float64, len(self.d))
        order = np.argsort(keys, kind="stable")
        return {"keys": keys[order], "vals": vals[order]}


def state_mismatch(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]
                   ) -> int:
    """Entries that differ in key or value, plus the difference in size."""
    n = min(len(got["keys"]), len(want["keys"]))
    bad = ((np.asarray(got["keys"][:n], np.float64) != want["keys"][:n])
           | (np.asarray(got["vals"][:n], np.float64) != want["vals"][:n]))
    return int(bad.sum()) + abs(len(got["keys"]) - len(want["keys"]))
