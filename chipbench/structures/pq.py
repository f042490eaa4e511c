"""The sharded priority queue (registry name ``pq``): preload, requests,
plain reference and final state.

The reference is a binary heap with the queue's stated batch rule: a
combined batch is applied in slices of up to ``c_max`` extracts and
``c_max`` inserts, extracts and inserts advancing together; each slice's
extracts take the smallest keys present before the slice, ascending, and
the batch's ``extract_min`` operations receive them in arrival order
(None once the queue is empty); an ``insert`` answers None.
"""
from __future__ import annotations

import heapq
from typing import Any, Dict, List, Sequence

import numpy as np

import precision as _precision
import traffic as _traffic



def preload(cfg: Dict, seed: int) -> Dict[str, np.ndarray]:
    """``records`` keys drawn uniformly from the configured key space."""
    rng = np.random.default_rng([seed, 0])
    return {"vals": _traffic.draw_keys({"distribution": "uniform"},
                                       cfg["keys"], rng,
                                       int(cfg["records"]))}


def make_kwargs(cfg: Dict, data: Dict[str, np.ndarray]) -> Dict[str, Any]:
    return dict(capacity=int(cfg["capacity"]), c_max=int(cfg["c_max"]),
                n_shards=int(cfg["n_shards"]), values=data["vals"])


def request_input(method: str, key: float, value: float) -> Any:
    if method == "insert":
        return key
    if method == "extract_min":
        return None
    raise ValueError(f"the pq reference has no method {method!r}")


def fetch_state(ds) -> Dict[str, np.ndarray]:
    """Every live key, ascending."""
    import jax

    a, size = jax.device_get((ds.state.a, ds.state.size))
    return {"vals": np.sort(np.concatenate(
        [a[k, 1:size[k] + 1] for k in range(len(size))]))}


class Reference:
    def __init__(self, data: Dict[str, np.ndarray], precision: str,
                 cfg: Dict):
        self.q = _precision.rounder(precision)
        self.c = int(cfg["c_max"])
        self.h = self.q(data["vals"]).tolist()
        heapq.heapify(self.h)

    def step(self, methods: Sequence[str], inputs: Sequence[Any]
             ) -> List[Any]:
        ne = sum(m == "extract_min" for m in methods)
        ins = self.q([x for m, x in zip(methods, inputs)
                      if m == "insert"]).tolist()
        if ne + len(ins) != len(methods):
            raise ValueError(f"unknown pq method in {set(methods)}")
        taken: List[Any] = []
        while ne > 0 or ins:
            k_e, k_i = min(ne, self.c), min(len(ins), self.c)
            taken.extend(heapq.heappop(self.h) if self.h else None
                         for _ in range(k_e))
            for v in ins[:k_i]:
                heapq.heappush(self.h, v)
            ne -= k_e
            ins = ins[k_i:]
        it = iter(taken)
        return [next(it) if m == "extract_min" else None for m in methods]

    def state(self) -> Dict[str, np.ndarray]:
        return {"vals": np.sort(np.asarray(self.h, np.float64))}


def state_mismatch(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]
                   ) -> int:
    """Positions of the sorted contents that differ, plus the difference
    in size."""
    n = min(len(got["vals"]), len(want["vals"]))
    bad = np.asarray(got["vals"][:n], np.float64) != want["vals"][:n]
    return int(bad.sum()) + abs(len(got["vals"]) - len(want["vals"]))
