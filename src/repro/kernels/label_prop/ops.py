"""Public wrappers for the label-propagation kernel (DESIGN.md §11).

Three layers:

* ``label_step`` — one scatter-min + pointer-jump iteration, dispatched as
  the ``grid=(K,)`` Pallas kernel over a K-way vertex partition (padding
  the vertex set to K equal blocks and the edge list to the kernel's
  streaming chunk size); ``jump=False`` leaves out the pointer jump, one
  hop of propagation.  ``label_step_xla`` is the bit-exact pure-XLA twin
  (scatter ``.at[].min`` + gather) used as the CPU/fallback path.
* ``connected_components`` — the fixpoint loop: iterate the step until the
  labels stop changing.  Labels converge to the component-min id (labels
  only decrease, ``l[x] ≤ x`` is invariant, and the min vertex of every
  component is a fixpoint of both the hook and the jump).
* ``spanning_forest`` — the same labels by one hop a step (no jump), so a
  vertex's last step of change is its hop distance to its component's
  min, plus the breadth-first spanning forest those distances define:
  the device graph's rebuild from scratch (``core/device_graph.py``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

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


@functools.partial(jax.jit, static_argnames=("n_shards", "interpret",
                                             "jump"))
def label_step(labels: jax.Array, eu: jax.Array, ev: jax.Array, *,
               n_shards: int = 1, interpret: Optional[bool] = None,
               jump: bool = True) -> jax.Array:
    """One label-propagation iteration via the ``grid=(K,)`` kernel
    (``jump=False``: the scatter-min alone).

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
                                  interpret=interpret, jump=jump)
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


@functools.partial(jax.jit,
                   static_argnames=("n", "n_shards", "use_pallas",
                                    "interpret"))
def connected_components(eu: jax.Array, ev: jax.Array, *, n: int,
                         n_shards: int = 1, use_pallas: bool = False,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Component-min labels of the graph on [0, n) with the given edges.

    eu/ev: (E,) i32 endpoints, invalid slots sanitized to (0, 0).
    ``use_pallas`` iterates the shard-grid kernel; otherwise the XLA twin.
    Both paths are bit-exact per iteration, hence at the fixpoint.
    """
    labels0 = jnp.arange(n, dtype=jnp.int32)
    if use_pallas:
        step = functools.partial(label_step, eu=eu, ev=ev,
                                 n_shards=n_shards, interpret=interpret)
        return _fixpoint(lambda l: step(l), labels0)
    return _fixpoint(lambda l: label_step_xla(l, eu, ev), labels0)


def _hop_xla(l: jax.Array, eu: jax.Array, ev: jax.Array) -> jax.Array:
    """One hop of min-label propagation: the scatter-min, no jump."""
    m = jnp.minimum(l[eu], l[ev])
    return l.at[eu].min(m).at[ev].min(m)


def spanning_forest(eu: jax.Array, ev: jax.Array, *, n: int,
                    n_shards: int = 1, use_pallas: bool = False,
                    interpret: Optional[bool] = None, placement=None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Component-min labels plus a breadth-first spanning forest.

    Min-label propagation one hop a step: after step k a vertex holds the
    least id within k hops, so the step at which its label last fell is
    its hop distance ``d`` to its component's min.  Every other vertex
    has an edge to a vertex one hop nearer; the least index of those
    edges is its parent edge.  Parents point strictly nearer the min, so
    the parent edges form a spanning forest rooted at the mins.

    eu/ev: (E,) i32 endpoints, invalid slots sanitized to (0, 0) (never a
    parent: both ends are one vertex).  Returns ``(labels, parent,
    iterations)``: ``parent`` (n,) i32 is an index into eu/ev, or E at a
    component's min; ``iterations`` counts the steps, the last of which
    changes nothing.  ``use_pallas`` runs each step as the ``grid=(K,)``
    kernel without its jump.  ``placement`` (static): a ``MeshPlacement``
    splits the edges across its D devices and ``pmin``-merges the tables
    each step (DESIGN.md §18), the same tables as one device's, step for
    step, because min is associative and commutative.
    """
    (e,) = eu.shape

    def forest(eu_blk, ev_blk, base, merge, hop):
        def body(st):
            l, d, k, _ = st
            s = merge(hop(l, eu_blk, ev_blk))
            fell = s < l
            return s, jnp.where(fell, k + 1, d), k + 1, jnp.any(fell)

        l, d, k, _ = jax.lax.while_loop(
            lambda st: st[3], body,
            (jnp.arange(n, dtype=jnp.int32), jnp.zeros((n,), jnp.int32),
             jnp.int32(0), jnp.bool_(True)))
        du, dv = d[eu_blk], d[ev_blk]
        idx = base + jnp.arange(eu_blk.shape[0], dtype=jnp.int32)
        parent = merge(jnp.full((n,), e, jnp.int32)
                       .at[eu_blk].min(jnp.where(du == dv + 1, idx, e))
                       .at[ev_blk].min(jnp.where(dv == du + 1, idx, e)))
        return l, parent, k

    if placement is None or not placement.is_mesh:
        hop = _hop_xla
        if use_pallas:
            hop = functools.partial(label_step, n_shards=n_shards,
                                    interpret=interpret, jump=False)
        return forest(eu, ev, 0, lambda t: t, hop)
    from jax.sharding import PartitionSpec as P

    ax = placement.axis
    d = placement.n_devices
    e_pad = ceil_to(max(e, 1), d)
    # pad with (0, 0) self-loops — never a parent
    eu_p = jnp.zeros((e_pad,), jnp.int32).at[:e].set(eu.astype(jnp.int32))
    ev_p = jnp.zeros((e_pad,), jnp.int32).at[:e].set(ev.astype(jnp.int32))

    def body(eu_blk, ev_blk):
        base = jax.lax.axis_index(ax) * (e_pad // d)
        return forest(eu_blk, ev_blk, base,
                      lambda t: jax.lax.pmin(t, ax), _hop_xla)

    fn = jax.shard_map(body, mesh=placement.mesh, in_specs=(P(ax), P(ax)),
                       out_specs=(P(), P(), P()), check_vma=False)
    return fn(eu_p, ev_p)
