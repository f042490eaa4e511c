"""Public wrappers for the label-propagation kernel (DESIGN.md §11).

Three layers:

* ``label_step`` — one scatter-min + pointer-jump iteration, dispatched as
  the ``grid=(K,)`` Pallas kernel over a K-way vertex partition (padding
  the vertex set to K equal blocks and the edge list to the kernel's
  streaming chunk size).  ``label_step_xla`` is the bit-exact pure-XLA
  twin (scatter ``.at[].min`` + gather) used as the CPU/fallback path and
  by the union-find fast path.
* ``connected_components`` — the fixpoint loop: iterate the step until the
  labels stop changing.  Labels converge to the component-min id (labels
  only decrease, ``l[x] ≤ x`` is invariant, and the min vertex of every
  component is a fixpoint of both the hook and the jump).
* ``merge_labels`` — the insert-only *union-find fast path* (DESIGN.md
  §11): given a valid component labeling and a small batch of new edges,
  run the fixpoint on the CONTRACTED graph whose vertices are the current
  labels and whose edges are the label pairs of the new edges, then
  compose.  O(b log n) work for b new edges instead of the full
  O(E log n) rebuild — the common case in a read-dominated workload.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels._rows import ceil_to

from .kernel import label_step_sharded_vmem

_E_CHUNK = 128      # edges per streaming chunk (one lane tile)
_V_ALIGN = 128      # vertex block alignment (one lane tile)
# Sizes the kernel holds with the label table and the edge list in VMEM;
# compiled for a v5e at these sizes in tests/test_tpu_compile.py.
MAX_PALLAS_VERTICES = 1 << 14
MAX_PALLAS_EDGES = 1 << 18


def require_pallas_fits(n_vertices: int, n_edges: int = 0) -> None:
    """Refuse, at construction, graphs the kernel cannot hold in VMEM."""
    if n_vertices > MAX_PALLAS_VERTICES or n_edges > MAX_PALLAS_EDGES:
        raise ValueError(
            f"use_pallas keeps the labels and edges in VMEM: {n_vertices} "
            f"vertices / {n_edges} edges exceed the label_prop kernel's "
            f"limit of {MAX_PALLAS_VERTICES} / {MAX_PALLAS_EDGES}")


def label_step_xla(labels: jax.Array, eu: jax.Array,
                   ev: jax.Array) -> jax.Array:
    """Pure-XLA twin of one kernel iteration (element-wise identical)."""
    labels = labels.astype(jnp.int32)
    m = jnp.minimum(labels[eu], labels[ev])
    s = labels.at[eu].min(m).at[ev].min(m)
    return jnp.minimum(s, labels[s])


@functools.partial(jax.jit, static_argnames=("n_shards", "interpret"))
def label_step(labels: jax.Array, eu: jax.Array, ev: jax.Array, *,
               n_shards: int = 1,
               interpret: Optional[bool] = None) -> jax.Array:
    """One label-propagation iteration via the ``grid=(K,)`` kernel.

    labels: (n,) i32; eu/ev: (E,) i32 endpoints with invalid/padding edges
    sanitized to (0, 0) self-loops.  Pads the vertex set to ``n_shards``
    equal aligned blocks (padding vertices label themselves and touch no
    edge) and the edge list to the kernel chunk size, then strips the
    padding — the result is shard-count independent.
    """
    if interpret is None:
        interpret = default_interpret()
    (n,) = labels.shape
    (e,) = eu.shape
    block = ceil_to(-(-n // n_shards), _V_ALIGN)
    n_pad = block * n_shards
    e_pad = ceil_to(max(e, 1), _E_CHUNK)
    labels_p = jnp.concatenate(
        [labels.astype(jnp.int32),
         jnp.arange(n, n_pad, dtype=jnp.int32)])
    eu_p = jnp.zeros((e_pad,), jnp.int32).at[:e].set(eu.astype(jnp.int32))
    ev_p = jnp.zeros((e_pad,), jnp.int32).at[:e].set(ev.astype(jnp.int32))
    out = label_step_sharded_vmem(labels_p, eu_p, ev_p, n_shards=n_shards,
                                  e_chunk=min(_E_CHUNK, e_pad),
                                  interpret=interpret)
    return out[:n]


def _fixpoint(step_fn, labels0: jax.Array) -> jax.Array:
    def cond(st):
        return st[1]

    def body(st):
        l, _ = st
        l2 = step_fn(l)
        return l2, jnp.any(l2 != l)

    l, _ = jax.lax.while_loop(cond, body, (labels0, jnp.bool_(True)))
    return l


def _cc_collective(eu: jax.Array, ev: jax.Array, *, n: int, placement):
    """Mesh-parallel fixpoint (DESIGN.md §18): EDGES split across the D
    devices, one full label table per device.

    Each iteration every device scatter-mins its edge block into its
    label copy, a ``pmin`` merges the D partial tables, and the pointer
    jump runs replicated.  Per-iteration this equals
    :func:`label_step_xla` element-wise — min is associative and
    commutative, so min-reducing per-block scatters then pmin-reducing
    across blocks is the same table as one global scatter-min — hence
    the fixpoint (and its iteration count) is bit-identical to the
    stacked trace.  The whole while_loop lives INSIDE one shard_map
    body: D devices, one program, no per-iteration re-dispatch.
    """
    from jax.sharding import PartitionSpec as P

    ax = placement.axis
    d = placement.n_devices
    (e,) = eu.shape
    e_pad = ceil_to(max(e, 1), d)
    # pad with (0, 0) self-loops — the sanitized-edge no-op
    eu_p = jnp.zeros((e_pad,), jnp.int32).at[:e].set(eu.astype(jnp.int32))
    ev_p = jnp.zeros((e_pad,), jnp.int32).at[:e].set(ev.astype(jnp.int32))

    def body(eu_blk, ev_blk):
        def step(l):
            m = jnp.minimum(l[eu_blk], l[ev_blk])
            s = l.at[eu_blk].min(m).at[ev_blk].min(m)
            s = jax.lax.pmin(s, ax)
            return jnp.minimum(s, l[s])

        return _fixpoint(step, jnp.arange(n, dtype=jnp.int32))

    fn = jax.shard_map(body, mesh=placement.mesh,
                       in_specs=(P(ax), P(ax)), out_specs=P(),
                       check_vma=False)
    return fn(eu_p, ev_p)


@functools.partial(jax.jit,
                   static_argnames=("n", "n_shards", "use_pallas",
                                    "interpret", "placement"))
def connected_components(eu: jax.Array, ev: jax.Array, *, n: int,
                         n_shards: int = 1, use_pallas: bool = False,
                         interpret: Optional[bool] = None,
                         placement=None) -> jax.Array:
    """Component-min labels of the graph on [0, n) with the given edges.

    eu/ev: (E,) i32 endpoints, invalid slots sanitized to (0, 0).
    ``use_pallas`` iterates the shard-grid kernel; otherwise the XLA twin.
    Both paths are bit-exact per iteration, hence at the fixpoint.
    ``placement`` (static): a ``MeshPlacement`` runs the edge-partitioned
    collective fixpoint (:func:`_cc_collective`) instead; ``None``/
    stacked keeps the single-device trace.
    """
    if placement is not None and placement.is_mesh:
        return _cc_collective(eu, ev, n=n, placement=placement)
    labels0 = jnp.arange(n, dtype=jnp.int32)
    if use_pallas:
        step = functools.partial(label_step, eu=eu, ev=ev,
                                 n_shards=n_shards, interpret=interpret)
        return _fixpoint(lambda l: step(l), labels0)
    return _fixpoint(lambda l: label_step_xla(l, eu, ev), labels0)


@functools.partial(jax.jit, static_argnames=("n",))
def merge_labels(labels: jax.Array, eu: jax.Array, ev: jax.Array, *,
                 n: int) -> jax.Array:
    """Union-find fast path: fold a batch of NEW edges into valid labels.

    ``labels`` must be a component-min labeling of the graph WITHOUT the
    new edges.  Runs the fixpoint on the contracted graph (vertices =
    current labels, edges = label pairs of the new edges — b edges, not
    E) and composes: new_label[x] = p[labels[x]].  Invalid edge slots must
    be (0, 0) self-loops (a no-op on the contracted graph too).
    """
    labels = labels.astype(jnp.int32)
    ceu = labels[eu.astype(jnp.int32)]
    cev = labels[ev.astype(jnp.int32)]
    p0 = jnp.arange(n, dtype=jnp.int32)
    p = _fixpoint(lambda p: label_step_xla(p, ceu, cev), p0)
    return p[labels]
