"""Shard placement layer (DESIGN.md §18) — where do the K shards live?

Every sharded structure in this repo stacks its K shards on a leading
axis (``ShardedBatchedPQ``'s (K, capacity) heap stack, ``ShardedMap``'s
(K, capacity+1) key/value tables).  A *placement* decides where that
leading axis lives:

* :class:`StackedPlacement` — all K shard rows on ONE device, the
  layout every PR before this one used.  ``put`` is the identity and
  the fused passes trace exactly the pre-placement code, so this is the
  bit-exact regression anchor.
* :class:`MeshPlacement` — the K rows are split across the ``axis``
  dimension of a 1-D :class:`jax.sharding.Mesh` (``D`` devices, ``K %
  D == 0``, ``K/D`` rows per device) and the fused passes run as
  :func:`jax.shard_map` bodies whose K-way
  merges are collectives (``all_gather`` of per-shard frontiers,
  ``psum`` of sizes, ``pmin`` of label tables).

Both placements keep the GLOBAL array shapes identical — (K, capacity)
either way — so all host-side code (routing twins, the sync-free
occupancy mirror, ``expand_rounds`` lowering, snapshot/restore, result
handles) is placement-oblivious.  Placements are frozen, hashable
dataclasses so the jitted entry points can take them as static
arguments: ``placement=None``/``StackedPlacement`` traces the original
single-device program, ``MeshPlacement`` traces the shard_map twin.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
from jax.sharding import Mesh, PartitionSpec as P


@dataclass(frozen=True)
class StackedPlacement:
    """All K shard rows on one device's arrays (the default layout).

    The identity placement: ``put`` returns its argument and the fused
    passes dispatch to the original (pre-placement) trace, byte for
    byte — this class exists so "no placement given" is a value the
    registry, scheduler and benches can name and compare against.
    """

    is_mesh = False

    @property
    def n_devices(self) -> int:
        return 1

    def validate(self, n_shards: int) -> None:
        """Any K stacks on one device."""

    def put(self, tree):
        return tree

    def describe(self) -> str:
        return "stacked"


@dataclass(frozen=True)
class MeshPlacement:
    """K shard rows split across ``mesh``'s ``axis`` dimension.

    ``mesh`` must be a 1-D mesh (or one whose ``axis`` dimension is the
    only one the structure shards over) — build one with
    :func:`repro.launch.mesh.make_combining_mesh`.  Hashable (Mesh is),
    so instances are valid jit static arguments; two placements over
    equal meshes trace to the same compiled program.
    """

    mesh: Mesh
    axis: str = "shard"

    def __post_init__(self):
        if self.axis not in self.mesh.axis_names:
            raise ValueError(
                f"mesh has axes {self.mesh.axis_names}, no {self.axis!r}")

    is_mesh = True

    @property
    def n_devices(self) -> int:
        return int(self.mesh.shape[self.axis])

    def validate(self, n_shards: int) -> None:
        d = self.n_devices
        if n_shards % d:
            raise ValueError(
                f"n_shards={n_shards} must be divisible by the mesh's "
                f"{self.axis!r} size {d} (every device holds K/D shard "
                f"rows)")

    def specs(self, tree):
        """Leading-axis-K partition spec for every array leaf."""
        return jax.tree.map(
            lambda x: P(self.axis, *(None,) * (jnp_ndim(x) - 1)), tree)

    def put(self, tree):
        """device_put the leading-K leaves across the mesh.

        Reuses ``launch/sharding.to_named`` for the NamedSharding
        construction (the launch layer's spec-tree helper) — imported
        lazily so building a StackedPlacement structure never pays the
        launch-stack import.
        """
        from repro.launch.sharding import to_named
        return jax.device_put(tree, to_named(self.specs(tree), self.mesh))

    def describe(self) -> str:
        return f"mesh(D={self.n_devices}, axis={self.axis!r})"


def jnp_ndim(x) -> int:
    return getattr(x, "ndim", 0)


def resolve_placement(placement):
    """``None`` → :class:`StackedPlacement`; placements pass through."""
    if placement is None:
        return StackedPlacement()
    if not isinstance(placement, (StackedPlacement, MeshPlacement)):
        raise TypeError(f"not a placement: {placement!r}")
    return placement


def as_static(placement) -> "MeshPlacement | None":
    """The value the jitted entry points take as their static
    ``placement`` argument: ``None`` for the stacked layout (so the
    pre-placement jit cache keys — and traces — are unchanged) and the
    :class:`MeshPlacement` itself otherwise."""
    p = resolve_placement(placement)
    return p if p.is_mesh else None
