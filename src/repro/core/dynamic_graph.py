"""Dynamic graph connectivity — the paper's §5.1 read-dominated workload.

Interface matches the paper's data type: ``insert(u,v)`` / ``delete(u,v)``
updates and the read-only ``connected(u,v)``.

Substitution recorded in DESIGN.md §8.3: instead of Holm et al.'s polylog
fully-dynamic forest (pointer-heavy, no TPU analogue) we keep an explicit
edge set on the host and maintain connected-component labels on device via
vectorized label propagation + pointer jumping (`O(E log V)` work per
rebuild, rebuilt lazily once per batch).  Reads are answered by ONE
vectorized gather/compare over the label array — this is where parallel
combining harvests its "free cycles" (the read batch costs one device call
regardless of batch size, while a global lock pays one call per read).

This is the HOST tier (and the benchmark baseline).  The device-resident
tier — edges in a donated device buffer, shard-grid label-propagation
kernels, an insert-only union-find fast path — is ``device_graph.py``
(DESIGN.md §11).
"""
from __future__ import annotations

from functools import partial
from typing import Any, List, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=("n",))
def _components(u: jax.Array, v: jax.Array, n: int) -> jax.Array:
    """Connected-component labels via scatter-min + pointer jumping."""
    labels0 = jnp.arange(n, dtype=jnp.int32)

    def cond(st):
        return st[1]

    def body(st):
        l, _ = st
        m = jnp.minimum(l[u], l[v])
        l2 = l.at[u].min(m).at[v].min(m)
        l2 = l2[l2]
        l2 = l2[l2]
        return (l2, jnp.any(l2 != l))

    l, _ = jax.lax.while_loop(cond, body, (labels0, jnp.bool_(True)))
    return l


@jax.jit
def _connected_batch(labels: jax.Array, us: jax.Array, vs: jax.Array) -> jax.Array:
    return labels[us] == labels[vs]


class DynamicGraph:
    """Sequential dynamic graph with a vectorized batched read path."""

    read_only: Set[str] = {"connected"}

    def __init__(self, n_vertices: int):
        self.n = int(n_vertices)
        self.edges: Set[Tuple[int, int]] = set()
        self._labels: Any = None      # device array, lazily rebuilt
        self._dirty = True

    # -- updates -------------------------------------------------------------
    def insert(self, u: int, v: int) -> bool:
        e = (min(u, v), max(u, v))
        if e in self.edges or u == v:
            return False
        self.edges.add(e)
        self._dirty = True
        return True

    def delete(self, u: int, v: int) -> bool:
        e = (min(u, v), max(u, v))
        if e not in self.edges:
            return False
        self.edges.remove(e)
        self._dirty = True
        return True

    # -- reads ---------------------------------------------------------------
    def _refresh(self) -> None:
        """Lazy-but-correct label rebuild.

        ``insert``/``delete`` return before refreshing — the labels are
        only rebuilt here, on the read path.  The dirty flag is cleared
        BEFORE building from a snapshot of the edge set: an update that
        lands mid-rebuild (a concurrent direct caller, or a reentrant
        update from a monkeypatched device call) re-marks ``_dirty`` and
        the loop rebuilds again.  The previous revision cleared the flag
        AFTER the rebuild, silently losing such updates — ``connected()``
        then read stale labels forever (regression-tested in
        test_core_apps.py).
        """
        while self._dirty:
            self._dirty = False
            edges = list(self.edges)           # snapshot, pre-clear ordering
            m = max(1, len(edges))
            pad = 1 << (m - 1).bit_length()    # pow2 padding limits recompiles
            uv = np.zeros((pad, 2), np.int32)  # padding = (0,0) self-loops
            if edges:
                uv[: len(edges)] = edges
            self._labels = _components(jnp.asarray(uv[:, 0]),
                                       jnp.asarray(uv[:, 1]), n=self.n)

    def connected(self, u: int, v: int) -> bool:
        self._refresh()
        lab = self._labels
        return bool(lab[u] == lab[v])

    def read_batch(self, methods: Sequence[str],
                   inputs: Sequence[Any]) -> List[Any]:
        """Answer a batch of ``connected`` queries with one device call."""
        assert all(m == "connected" for m in methods)
        self._refresh()
        us = jnp.asarray([i[0] for i in inputs], jnp.int32)
        vs = jnp.asarray([i[1] for i in inputs], jnp.int32)
        return np.asarray(_connected_batch(self._labels, us, vs)).tolist()

    # -- generic apply (Lock / RW-Lock / FC wrappers) --------------------------
    def apply(self, method: str, input: Any = None) -> Any:
        if method == "insert":
            return self.insert(*input)
        if method == "delete":
            return self.delete(*input)
        if method == "connected":
            return self.connected(*input)
        raise ValueError(f"unknown method {method!r}")
