"""Device-resident batched dynamic graph (DESIGN.md §11) — the §5.1
read-dominated application rebuilt to the sharded-PQ tier's standard.

The host tier (``dynamic_graph.DynamicGraph``) keeps the edge set in a
Python ``set`` and rebuilds the full component labeling in pure XLA after
every update batch.  This engine moves the edges — and the refresh
bookkeeping — onto the device, so one combining pass costs one fused
update program plus one fused read program with a single blocking fetch,
mirroring the batched-PQ architecture (``sharded_pq.py``, DESIGN.md §10):

* **edge buffer** — a fixed-capacity endpoint-array pair plus a validity
  mask (CSR-free: connectivity needs the edge multiset, not adjacency).
  One ``update_pass`` applies ≤ ``c_max`` MIXED insert/delete requests
  with sequential arrival-order semantics: per-lane results come from the
  last-earlier-same-edge chain rule, while the buffer takes only the NET
  effect per edge class (removals free slots, additions claim them by
  prefix-sum rank; transient insert+delete pairs never touch memory).
* **device-resident dirty tracking** — the state carries a pending-edge
  buffer, a ``dirty_full`` flag and a rebuild counter.  The update pass
  appends netted-in edges to the pending buffer and raises ``dirty_full``
  when an edge is netted OUT (or the pending buffer overflows); the host
  never has to look at the masks to decide how to refresh.
* **fused read pass** — ``connected`` batches run refresh + gather/compare
  as ONE program: a ``lax.cond`` picks the full scatter-min +
  pointer-jumping rebuild (``kernels/label_prop``, over a ``grid=(K,)``
  vertex partition when ``use_pallas=True``) when ``dirty_full``, the
  contracted-graph **union-find fast path** (``merge_labels``, O(b log n)
  for b pending inserts) when only inserts happened — the common case in
  a read-dominated workload — and the identity when labels are current.
* **sync-free update publishing** — ``update_batch_async`` leaves the
  per-request result masks on device; they ride the next read's single
  blocking fetch (or are fetched at ``result()``).  A combining pass of
  updates + reads therefore costs one blocking transfer, the same
  contract as the PQ's ``apply_async`` (regression-tested with the
  ``_host_fetch`` counting hook).

Every jitted pass **donates** the graph state, so the buffers update in
place (zero-copy, DESIGN.md §10); ``donate=False`` is the copy-per-pass
ablation twin.  The wrapper keeps a host mirror of the live edge count —
exact after every resolved fetch, a conservative upper bound in between —
for the capacity guard and the pow2 compaction bound of full rebuilds.
The wrapper is not thread-safe; confine each instance to one thread at a
time (the read-optimized combiner provides exactly that serialization).
"""
from __future__ import annotations

from typing import (Any, Dict, List, NamedTuple, Optional, Sequence, Set,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.label_prop import (connected_components, merge_labels,
                                      require_pallas_fits)

from . import placement as _placement
from . import substrate
from .faults import make_guard

# All device→host transfers on the graph hot path route through this hook
# so tests can count blocking syncs (same idiom as batched_pq._host_fetch).
_host_fetch = jax.device_get


class GraphState(NamedTuple):
    """Device-resident dynamic graph: edge buffer + labels + dirty state.

    The edge arrays and the pending buffer carry one extra SCRATCH slot at
    index ``capacity`` (resp. ``pend_cap``) — the graph twin of the heap's
    ``a[0]`` scratch: predicated scatters route every inactive lane there,
    so an active lane can never collide with an inactive write-back
    (duplicate scatter indices with different values are undefined)."""

    eu: jax.Array          # (capacity+1,) int32 — endpoint min (junk if ~valid)
    ev: jax.Array          # (capacity+1,) int32 — endpoint max
    valid: jax.Array       # (capacity+1,) bool_ — [capacity] stays False
    labels: jax.Array      # (n,) int32 — component-min labels (maybe stale)
    pend: jax.Array        # (2, pend_cap+1) int32 — inserted, not yet merged
    n_pend: jax.Array      # () int32
    dirty_full: jax.Array  # () bool_ — labels need a full rebuild
    n_full: jax.Array      # () int32 — full-rebuild counter (fast-path test)


# ---------------------------------------------------------------------------
# Jitted combining passes (donated state: zero-copy buffer updates)
# ---------------------------------------------------------------------------
def _update_impl(state: GraphState, buv: jax.Array, is_ins: jax.Array,
                 nb: jax.Array) -> Tuple[GraphState, jax.Array]:
    """Apply ≤ c_max MIXED insert/delete requests as ONE fused pass.

    ``buv``: (2, c) endpoints; ``is_ins``: (c,) bool op selector; ``nb``:
    live lane count.  Per-lane results follow sequential arrival-order
    semantics: a lane's edge is "present before" iff the LAST earlier
    lane touching the same edge was an insert (an op's outcome fully
    determines presence regardless of its own success), falling back to
    buffer presence for the class's first lane.  The buffer takes the NET
    effect per edge class (the class's last lane decides final presence).

    Dirty tracking is device-resident: netted-in edges append to the
    pending buffer (the union-find fast path's work list); any netted-out
    edge — or a pending-buffer overflow — raises ``dirty_full`` and
    clears the pending list (the full rebuild covers the buffer anyway).

    Returns ``(state, ok)`` — the per-request results stay on device
    until fetched (see ``AsyncUpdateResult``)."""
    eu, ev, valid, labels, pend, n_pend, dirty_full, n_full = state
    cap = eu.shape[0] - 1                             # [cap] is scratch
    c = buv.shape[1]
    pend_cap = pend.shape[1] - 1                      # [:, pend_cap] scratch
    lane = jnp.arange(c, dtype=jnp.int32)
    u = jnp.minimum(buv[0], buv[1])
    v = jnp.maximum(buv[0], buv[1])
    act = (lane < nb) & (u != v)      # self-loops are never stored: insert
    #                                   and delete of one both report False
    match = (valid[None, :] & (eu[None, :] == u[:, None])
             & (ev[None, :] == v[:, None]))           # (c, capacity+1)
    in_buf = jnp.any(match, axis=1)
    slot = jnp.argmax(match, axis=1)                  # unique if in_buf

    same = (u[:, None] == u[None, :]) & (v[:, None] == v[None, :])
    earlier = same & act[None, :] & (lane[None, :] < lane[:, None])
    has_prev = jnp.any(earlier, axis=1)
    prev_idx = jnp.argmax(jnp.where(earlier, lane[None, :], -1), axis=1)
    present_before = jnp.where(has_prev, is_ins[prev_idx], in_buf)
    ok = act & jnp.where(is_ins, ~present_before, present_before)

    is_last = act & ~jnp.any(same & act[None, :]
                             & (lane[None, :] > lane[:, None]), axis=1)
    rem = is_last & ~is_ins & in_buf                  # netted out
    add = is_last & is_ins & ~in_buf                  # netted in

    # predicated scatters: inactive lanes write the scratch slot, so they
    # can never collide with an active lane's target
    tgt = jnp.where(rem, slot, cap)
    valid = valid.at[tgt].set(jnp.where(rem, False, valid[tgt]))

    free = ~valid & (jnp.arange(cap + 1) < cap)       # post-removal slots
    rank = jnp.cumsum(add.astype(jnp.int32)) - 1
    # device-side overflow clamp (the host guard refuses earlier; this
    # keeps the scatter in-bounds even if the mirror were wrong)
    add = add & (rank < jnp.sum(free.astype(jnp.int32)))
    free_idx = jnp.nonzero(free, size=c, fill_value=cap)[0]
    tgt = jnp.where(add, free_idx[jnp.clip(rank, 0, c - 1)], cap)
    eu = eu.at[tgt].set(jnp.where(add, u, eu[tgt]))
    ev = ev.at[tgt].set(jnp.where(add, v, ev[tgt]))
    valid = valid.at[tgt].set(jnp.where(add, True, valid[tgt]))
    valid = valid.at[cap].set(False)                  # scratch stays dead

    # -- device-resident dirty tracking
    n_add = jnp.sum(add.astype(jnp.int32))
    go_full = dirty_full | jnp.any(rem) | (n_pend + n_add > pend_cap)
    app = add & ~go_full
    ptgt = jnp.where(app, jnp.clip(n_pend + rank, 0, pend_cap - 1),
                     pend_cap)
    pend = pend.at[0, ptgt].set(jnp.where(app, u, pend[0, ptgt]))
    pend = pend.at[1, ptgt].set(jnp.where(app, v, pend[1, ptgt]))
    n_pend = jnp.where(go_full, 0, n_pend + n_add)
    state = GraphState(eu, ev, valid, labels, pend, n_pend, go_full, n_full)
    return state, ok


def _read_impl(state: GraphState, uv: jax.Array, *, n: int, e_bound: int,
               n_shards: int, use_pallas: bool, placement=None
               ) -> Tuple[GraphState, jax.Array]:
    """Fused refresh + gather/compare: ONE program per read batch.

    A ``lax.cond`` tree picks the refresh: full rebuild when
    ``dirty_full`` (edges compacted to the static pow2 ``e_bound`` ≥ the
    live count — padding repeats slot 0, an invalid-slot self-loop or a
    duplicate edge, both no-ops for scatter-min), the contracted-graph
    merge when only pending inserts exist, identity otherwise.  The
    rebuild counter increments exactly on the full branch.

    ``placement`` (static): a ``MeshPlacement`` runs the full rebuild as
    the edge-partitioned collective fixpoint (DESIGN.md §18 — D devices
    scatter-min disjoint edge blocks, ``pmin`` merges the tables).  The
    contracted-graph fast path and the update passes stay replicated:
    ``GraphState`` has no K axis to split, and ``merge_labels`` touches
    b ≤ 2·c_max edges — there is nothing to scale out there."""
    eu, ev, valid, labels, pend, n_pend, dirty_full, n_full = state
    pend_w = pend.shape[1]                 # pend_cap + 1 (scratch included;
    #                                        sanitized by the n_pend mask)

    def full(labels):
        idx = jnp.nonzero(valid, size=e_bound, fill_value=0)[0]
        okslot = valid[idx]
        seu = jnp.where(okslot, eu[idx], 0)
        sev = jnp.where(okslot, ev[idx], 0)
        return connected_components(seu, sev, n=n, n_shards=n_shards,
                                    use_pallas=use_pallas,
                                    placement=placement)

    def fast(labels):
        lane = jnp.arange(pend_w, dtype=jnp.int32)
        pu = jnp.where(lane < n_pend, pend[0], 0)
        pv = jnp.where(lane < n_pend, pend[1], 0)
        return merge_labels(labels, pu, pv, n=n)

    labels = jax.lax.cond(
        dirty_full, full,
        lambda l: jax.lax.cond(n_pend > 0, fast, lambda x: x, l),
        labels)
    n_full = n_full + dirty_full.astype(jnp.int32)
    state = GraphState(eu, ev, valid, labels, pend, jnp.int32(0),
                       jnp.bool_(False), n_full)
    return state, labels[uv[0]] == labels[uv[1]]


# ``state`` is DONATED on every pass — the edge buffer, labels and dirty
# state update in place (DESIGN.md §10/§11); the ``*_undonated`` twins are
# the copy-per-pass ablation (EXPERIMENTS §Ablations).
update_pass = jax.jit(_update_impl, donate_argnums=(0,))
update_pass_undonated = jax.jit(_update_impl)


def _update_rounds_impl(state: GraphState, buv: jax.Array, is_ins: jax.Array,
                        nb: jax.Array) -> Tuple[GraphState, jax.Array]:
    """R sequential ≤ c_max update slices as ONE ``lax.scan`` program
    (DESIGN.md §12): ``buv`` (R, 2, c), ``is_ins`` (R, c), ``nb`` (R,).
    Each scan step is the full fused mixed-op pass, so a batch spanning R
    slices costs one dispatch instead of R.  Returns ``(state, oks
    (R, c))``."""

    def body(st, rnd):
        st, ok = _update_impl(st, rnd[0], rnd[1], rnd[2])
        return st, ok

    state, oks = jax.lax.scan(body, state, (buv, is_ins, nb))
    return state, oks


update_rounds = jax.jit(_update_rounds_impl, donate_argnums=(0,))
update_rounds_undonated = jax.jit(_update_rounds_impl)
_READ_STATIC = ("n", "e_bound", "n_shards", "use_pallas", "placement")
read_pass = jax.jit(_read_impl, static_argnames=_READ_STATIC,
                    donate_argnums=(0,))
read_pass_undonated = jax.jit(_read_impl, static_argnames=_READ_STATIC)

# megapass row tags (DESIGN.md §17)
MEGA_UPDATE, MEGA_READ = 0, 1


def _mixed_rounds_impl(state: GraphState, tags: jax.Array, buv: jax.Array,
                       flags: jax.Array, nb: jax.Array, *, n: int,
                       e_bound: int, n_shards: int, use_pallas: bool,
                       placement=None) -> Tuple[GraphState, jax.Array]:
    """R heterogeneous update/read rounds as ONE ``lax.scan`` program
    (DESIGN.md §17): per row, a ``lax.cond`` on the round tag picks the
    fused mixed-op update pass or the fused refresh+gather read pass.
    ``tags`` (R,), ``buv`` (R, 2, c) endpoints (update) or query pairs
    (read), ``flags`` (R, c) insert selectors (ignored on read rows),
    ``nb`` (R,) live lane counts (update rows only — reads answer every
    lane and the host masks by count).  ``e_bound`` is one conservative
    static compaction bound covering every read row in the pass.
    Returns ``(state, oks (R, c))`` — update rows stack their ok masks,
    read rows their connectivity answers, into the per-round slots."""

    def body(st, rnd):
        tag, ruv, rfl, rnb = rnd

        def upd(s):
            return _update_impl(s, ruv, rfl, rnb)

        def rd(s):
            return _read_impl(s, ruv, n=n, e_bound=e_bound,
                              n_shards=n_shards, use_pallas=use_pallas,
                              placement=placement)

        st, ok = jax.lax.cond(tag == MEGA_READ, rd, upd, st)
        return st, ok

    state, oks = jax.lax.scan(body, state, (tags, buv, flags, nb))
    return state, oks


mixed_rounds_pass = jax.jit(_mixed_rounds_impl, static_argnames=_READ_STATIC,
                            donate_argnums=(0,))
mixed_rounds_pass_undonated = jax.jit(_mixed_rounds_impl,
                                      static_argnames=_READ_STATIC)


@jax.jit
def _connected_pairs(labels: jax.Array, uv: jax.Array) -> jax.Array:
    """Lean read: labels known-current, no refresh machinery dispatched."""
    return labels[uv[0]] == labels[uv[1]]


@jax.jit
def _copy_state(state: GraphState) -> GraphState:
    """Snapshot copy of the WHOLE state as ONE fused program: eight
    per-array ``.copy()`` calls cost eight XLA:CPU dispatches per guarded
    pass — enough to blow the §Robustness ≤10% overhead budget on the
    graph's short read passes."""
    return jax.tree_util.tree_map(jnp.copy, state)


def _pow2(m: int) -> int:
    return 1 << max(0, (m - 1).bit_length())


class AsyncUpdateResult:
    """Deferred host view of one update batch's per-request results.

    The ok masks stay on device until the first :meth:`result` call — or,
    cheaper, until the owning graph's next read pass fetches them inside
    its single blocking transfer (``update masks ride the read fetch``,
    the graph twin of the PQ's one-sync contract).  Resolution also
    re-tightens the owner's live-edge-count mirror to the exact value.

    Elimination bookkeeping (DESIGN.md §12): the dispatch carries ONE lane
    per distinct edge class (the class's LAST op); every other op's result
    is reconstructed host-side at resolve time from the device lane's
    answer via the arrival-order chain rule.  The lane's answer encodes
    buffer presence (``present = ok XOR is_ins``), the chain walks the
    class's ops in arrival order (an op's outcome fully determines
    presence for the next), and self-loop lanes were answered ``False``
    at dispatch without any device work.
    """

    def __init__(self, owner: "DeviceGraph", masks: List[jax.Array],
                 n_ops: int, classes: List[List[Tuple[int, bool]]],
                 lane_counts: List[int], c_max: int):
        self._owner: Optional["DeviceGraph"] = owner
        self.masks = masks
        self._n_ops = n_ops
        self._classes = classes          # per device lane, dispatch order
        self._lane_counts = lane_counts  # live lanes per dispatched row
        self._c_max = c_max
        self._out: Optional[List[bool]] = None

    def _resolve(self, masks_h) -> None:
        """Apply fetched masks to the owner's mirrors (owner-ordered) and
        chain-reconstruct every op's arrival-order result."""
        if masks_h:
            rows = np.concatenate(
                [np.asarray(m).reshape(-1, self._c_max) for m in masks_h],
                axis=0)
            ok_dev = np.concatenate(
                [rows[r, :nb] for r, nb in enumerate(self._lane_counts)]) \
                if self._lane_counts else np.zeros((0,), bool)
        else:
            ok_dev = np.zeros((0,), bool)
        out = np.zeros((self._n_ops,), bool)    # self-loops stay False
        adds = removals = lane_inserts = 0
        for lane, ops in enumerate(self._classes):
            is_ins_last = ops[-1][1]
            okl = bool(ok_dev[lane])
            adds += okl and is_ins_last
            removals += okl and not is_ins_last
            lane_inserts += is_ins_last
            # lane answer -> buffer presence before the class's first op
            present = (not okl) if is_ins_last else okl
            for idx, ins in ops:
                out[idx] = (not present) if ins else present
                present = ins            # outcome determines presence
        owner = self._owner
        if owner is not None:
            owner._n_edges += adds - removals
            owner._outstanding_ins -= lane_inserts
        self._out = out.tolist()
        self._owner = None
        self.masks = []

    def result(self) -> List[bool]:
        """Per-request results in arrival order (cached after first call)."""
        if self._out is None:
            self._owner._resolve_through(self)
        return self._out


class _GraphMegaFetch:
    """One shared blocking fetch for every handle of one megapass.

    The dispatch leaves the stacked (R, c_max) per-round outputs on
    device; the FIRST handle resolved — update or read, in any order —
    triggers the single ``_host_fetch`` (which also drains any older
    outstanding ``update_batch_async`` handles, preserving the one-fetch
    contract), then resolves every megapass update round's inner handle
    in dispatch order so the mirrors re-tighten exactly once."""

    def __init__(self, owner: "DeviceGraph", oks: jax.Array):
        self._owner: Optional["DeviceGraph"] = owner
        self._oks = oks
        self._upd: List[Tuple[AsyncUpdateResult, int, int]] = []
        self._rows: Optional[np.ndarray] = None

    def rows(self) -> np.ndarray:
        if self._rows is None:
            got = self._owner._resolve_through(None, extra=self._oks)
            rows = np.asarray(got)
            for inner, lo, hi in self._upd:
                if inner._out is None:
                    inner._resolve([rows[lo:hi]])
            self._rows = rows
            self._owner = self._oks = None
            self._upd = []
        return self._rows


class _MegaUpdateHandle:
    """Megapass update-round handle: resolves through the shared fetch
    (the inner ``AsyncUpdateResult`` is NOT in ``_unresolved`` — its
    mask rows live in the megapass output stack, not a separate device
    array)."""

    def __init__(self, shared: _GraphMegaFetch, inner: AsyncUpdateResult):
        self._shared, self._inner = shared, inner

    def result(self) -> List[bool]:
        if self._inner._out is None:
            self._shared.rows()
        return self._inner._out


class _GraphReadRound:
    """Megapass read-round handle: one bool per query pair, masked out
    of the round's (c_max,) output rows by per-row live counts."""

    def __init__(self, shared: _GraphMegaFetch, row_lo: int,
                 counts: List[int]):
        self._shared, self._row_lo, self._counts = shared, row_lo, counts

    def result(self) -> List[bool]:
        rows = self._shared.rows()
        out: List[bool] = []
        for r, nc in enumerate(self._counts):
            out.extend(bool(x) for x in rows[self._row_lo + r, :nc])
        return out


# ---------------------------------------------------------------------------
# Host-facing wrapper
# ---------------------------------------------------------------------------
class DeviceGraph(substrate.BatchedStructure):
    """Device-resident dynamic graph with batched combining passes.

    Args:
      n_vertices: vertex-set size (ids are [0, n)).
      edge_capacity: fixed device edge-buffer capacity.  The host guard is
        conservative: an update batch is refused when ``live-bound +
        batch-inserts`` could exceed capacity even if duplicates would
        dedup — size the buffer with ≥ c_max headroom over the expected
        live edge count.
      c_max: combined update-batch capacity per pass (compile-time
        constant; larger batches are applied in c_max slices).
      n_shards: vertex-partition shard count K of the label-propagation
        kernel grid.
      use_pallas: run full label rebuilds through the ``grid=(K,)``
        Pallas kernel (DESIGN.md §11) instead of the XLA twin.
      donate: zero-copy (donated) passes (default); False is the
        copy-per-pass ablation twin.
      placement: shard layout for the full label rebuild (DESIGN.md
        §18) — a ``MeshPlacement`` partitions the compacted edge list
        across D devices and min-merges the label tables with ``pmin``
        (bit-exact per iteration, min being associative/commutative).
        Updates and the contracted-graph fast path stay replicated
        (``GraphState`` is flat — there is no K axis to place).  Not
        combinable with ``use_pallas``.
      edges: optional initial edge list, (m, 2) vertex pairs — loaded in
        bulk on the host (canonicalized, deduplicated, self-loops
        dropped); the first read runs a full label rebuild.

    Interface-compatible with ``DynamicGraph`` (``insert``/``delete``/
    ``connected``/``read_batch``/``apply``) plus the batched entry points
    (``insert_batch``/``delete_batch``/``update_batch``/
    ``update_batch_async``/``connected_batch``) that the read-optimized
    combiner uses: one fused pass per ≤ c_max update slice, one fused
    refresh+read pass per read batch, one blocking fetch per pass.
    """

    structure = "graph"
    read_only: Set[str] = {"connected"}
    supports_megapass = True
    supports_placement = True

    def __init__(self, n_vertices: int, *, edge_capacity: int = 4096,
                 c_max: int = 64, n_shards: int = 1,
                 use_pallas: bool = False, donate: bool = True,
                 fault_plan=None, guard=None, placement=None, edges=None):
        if n_vertices < 1:
            raise ValueError("n_vertices must be >= 1")
        if c_max < 1:
            raise ValueError("c_max must be >= 1")
        if edge_capacity < c_max:
            raise ValueError("edge_capacity must be >= c_max")
        self.n = int(n_vertices)
        self.capacity = int(edge_capacity)
        self.c_max = int(c_max)
        self.n_shards = int(n_shards)
        self.use_pallas = bool(use_pallas)
        self.donate = bool(donate)
        self.placement = _placement.resolve_placement(placement)
        self._pstatic = _placement.as_static(self.placement)
        if self._pstatic is not None and self.use_pallas:
            raise ValueError(
                "use_pallas is not supported under MeshPlacement: the "
                "grid=(K,) label kernel assumes the whole vertex "
                "partition in one device's address space (DESIGN.md §18)")
        if self.use_pallas:
            require_pallas_fits(self.n, self.capacity)
        pend_cap = 2 * self.c_max
        eu, ev, valid = self._initial_edges(edges)
        # +1: the scratch slot for predicated scatters (see GraphState)
        self.state = GraphState(
            eu=jnp.asarray(eu),
            ev=jnp.asarray(ev),
            valid=jnp.asarray(valid),
            labels=jnp.arange(self.n, dtype=jnp.int32),
            pend=jnp.zeros((2, pend_cap + 1), jnp.int32),
            n_pend=jnp.int32(0),
            dirty_full=jnp.bool_(bool(valid.any())),
            n_full=jnp.int32(0),
        )
        # live-edge-count mirror: exact after every resolved fetch; the
        # bound adds inserts whose result masks are still on device
        self._n_edges = int(valid.sum())
        self._outstanding_ins = 0
        # elimination instrumentation (DESIGN.md §12): ops answered by the
        # host chain rule instead of a device lane
        self.eliminated_ops = 0
        self._unresolved: List[AsyncUpdateResult] = []
        # True iff an update pass was dispatched since the last fused
        # read — False means the device labels are known-current and a
        # read can take the lean gather/compare dispatch
        self._maybe_stale = False
        # full-rebuild compaction bound: a pow2 ≥ the live count, with
        # hysteresis (grow on demand, shrink only on a 4x drop) so a
        # live count oscillating across a pow2 boundary doesn't recompile
        # the fused read pass every few batches
        self._e_bound = 1
        self.fault_plan = fault_plan
        self._guard = make_guard(fault_plan, guard)

    def _initial_edges(self, edges):
        """Host edge buffer (eu, ev, valid), each capacity+1 long, holding
        the distinct non-loop edges of ``edges`` as (min, max) pairs."""
        eu = np.zeros((self.capacity + 1,), np.int32)
        ev = np.zeros((self.capacity + 1,), np.int32)
        valid = np.zeros((self.capacity + 1,), np.bool_)
        if edges is None or len(edges) == 0:
            return eu, ev, valid
        e = np.asarray(edges, np.int64).reshape(-1, 2)
        if e.min() < 0 or e.max() >= self.n:
            raise ValueError(f"edge endpoints must lie in [0, {self.n})")
        u, v = e.min(axis=1), e.max(axis=1)
        code = np.unique(u[u != v] * self.n + v[u != v])
        m = code.size
        if m > self.capacity:
            raise ValueError(f"{m} distinct edges exceed edge_capacity "
                             f"{self.capacity}")
        eu[:m], ev[:m], valid[:m] = code // self.n, code % self.n, True
        return eu, ev, valid

    # -- transactional dispatch (DESIGN.md §15) -------------------------------
    def _snapshot(self):
        """Device-side copies (never donated — restore survives the
        failed pass consuming the live buffers) + every host mirror the
        guarded thunks mutate.  ``_unresolved`` is NOT snapshotted: mask
        arrays are separate device outputs, and a handle is only
        appended after its dispatch commits."""
        st = _copy_state(self.state)
        return (st, self._n_edges, self._outstanding_ins,
                self._maybe_stale, self._e_bound)

    def _restore(self, snap) -> None:
        (self.state, self._n_edges, self._outstanding_ins,
         self._maybe_stale, self._e_bound) = snap

    def __len__(self) -> int:
        """Live edge count (exact: resolves any outstanding updates)."""
        self._resolve_through(None)
        return self._n_edges

    def _live_bound(self) -> int:
        return self._n_edges + self._outstanding_ins

    def _rebuild_bound(self) -> int:
        """Static compaction bound for the fused read pass (hysteresis —
        see ``_e_bound``); always ≥ the live-edge upper bound."""
        lb = max(1, self._live_bound())
        if lb > self._e_bound or 4 * lb <= self._e_bound:
            self._e_bound = _pow2(lb)
        return self._e_bound

    # -- updates -------------------------------------------------------------
    def _edge_array(self, edges) -> np.ndarray:
        """(2, len) int32 endpoint array, vertex ids range-checked."""
        arr = np.asarray(edges, np.int64).reshape(-1, 2).T
        if arr.size and (arr.min() < 0 or arr.max() >= self.n):
            raise ValueError("vertex id out of range")
        return arr.astype(np.int32)

    def update_batch_async(self, methods: Sequence[str],
                           inputs: Sequence[Any]) -> AsyncUpdateResult:
        """Apply a combined MIXED update batch, arrival order preserved.

        Elimination pre-pass (DESIGN.md §12): the host nets the batch down
        to ONE op per distinct edge class — the class's LAST op, which
        alone decides the buffer's net effect — and answers every other op
        at resolve time via the arrival-order chain rule (self-loops never
        dispatch at all).  The surviving lanes go to the device as ONE
        fused pass when they fit a single ≤ c_max slice, or ONE
        ``update_rounds`` scan program over all R slices otherwise — a
        duplicate-heavy contended batch costs one short dispatch either
        way.  NO blocking transfer: the result masks stay on device and
        ride the next read's fetch."""
        for m in methods:
            if m not in ("insert", "delete"):
                raise ValueError(f"unknown update method {m!r}")
        arr = self._edge_array(list(inputs))
        n_ops = arr.shape[1]
        if n_ops == 0:
            # nothing dispatched: the labels stay known-current (keep the
            # lean read path) and the handle resolves trivially
            handle = AsyncUpdateResult(self, [], 0, [], [], self.c_max)
            handle._out = []
            return handle
        # -- elimination pre-pass: group ops by normalized edge class
        by_edge: Dict[Tuple[int, int], List[Tuple[int, bool]]] = {}
        for i in range(n_ops):
            u, v = int(arr[0, i]), int(arr[1, i])
            if u == v:
                continue                 # self-loop: always False, no lane
            by_edge.setdefault((min(u, v), max(u, v)), []).append(
                (i, methods[i] == "insert"))
        classes = list(by_edge.values())   # first-touch (arrival) order
        d = len(classes)
        self.eliminated_ops += n_ops - d
        if d == 0:                         # all self-loops: pure host
            handle = AsyncUpdateResult(self, [], n_ops, [], [], self.c_max)
            handle._resolve([])
            return handle
        lane_ins = sum(ops[-1][1] for ops in classes)
        # guard the WHOLE batch before dispatching any slice: a mid-loop
        # refusal would leave already-applied slices in the buffer with
        # the host mirrors (and _maybe_stale) never updated
        if self._live_bound() + lane_ins > self.capacity:
            raise ValueError(
                f"edge capacity {self.capacity} exceeded: "
                f"≤{self._live_bound()} live edges "
                f"+ {lane_ins} distinct-edge inserts")
        # pow2-pad the round count (no-op rows, nb=0): the scan program
        # recompiles per distinct leading dim and batch sizes are
        # workload-driven — bucketing bounds the jit-cache variants
        n_live_rounds = -(-d // self.c_max)
        n_rounds = 1 << (n_live_rounds - 1).bit_length()
        buv = np.zeros((n_rounds, 2, self.c_max), np.int32)
        sel = np.zeros((n_rounds, self.c_max), bool)
        lane_counts: List[int] = []
        for r in range(n_rounds):
            chunk = classes[r * self.c_max : (r + 1) * self.c_max]
            for j, ops in enumerate(chunk):
                i_last = ops[-1][0]
                buv[r, :, j] = arr[:, i_last]
                sel[r, j] = ops[-1][1]
            lane_counts.append(len(chunk))
        def commit():
            # mirror mutations live inside the guarded thunk so a
            # transactional restore rewinds them with the device state
            if n_rounds == 1:
                fn = update_pass if self.donate else update_pass_undonated
                self.state, ok = fn(self.state, jnp.asarray(buv[0]),
                                    jnp.asarray(sel[0]), jnp.int32(d))
                masks = [ok]
            else:
                fn = update_rounds if self.donate \
                    else update_rounds_undonated
                nb = np.asarray(lane_counts, np.int32)
                self.state, oks = fn(self.state, jnp.asarray(buv),
                                     jnp.asarray(sel), jnp.asarray(nb))
                masks = [oks]
            self._outstanding_ins += lane_ins
            self._maybe_stale = True
            return masks

        if self._guard is None:
            masks = commit()
        else:
            masks = self._guard.run(commit, self._snapshot, self._restore,
                                    site="graph.update_pass")
        handle = AsyncUpdateResult(self, masks, n_ops, classes,
                                   lane_counts, self.c_max)
        self._unresolved.append(handle)
        return handle

    def _resolve_through(self, handle: Optional[AsyncUpdateResult],
                         extra=None):
        """Fetch (once) the masks of EVERY unresolved update handle plus
        ``extra``, then apply them to the mirrors in dispatch order.
        Resolving one handle resolves all outstanding ones — their masks
        are already determined on device, and one combined fetch is
        exactly the sync the contract budgets.  ``handle`` only
        distinguishes \"this handle was already resolved\" (no-op)."""
        todo = list(self._unresolved)
        if handle is not None and handle not in todo:
            todo = []                      # already resolved
        if not todo and extra is None:
            return None
        fetched = _host_fetch(([h.masks for h in todo], extra))
        for h, masks_h in zip(todo, fetched[0]):
            h._resolve(masks_h)
            self._unresolved.remove(h)
        return fetched[1]

    # ``update_batch`` / generic ``apply`` inherit from BatchedStructure

    def occupancy_mirror(self):
        return {"n_edges": self._n_edges,
                "outstanding_ins": self._outstanding_ins}

    def insert_batch(self, edges: Sequence[Tuple[int, int]]) -> List[bool]:
        """Insert a batch of edges; per-edge "was new" results."""
        return self.update_batch(["insert"] * len(edges), edges)

    def delete_batch(self, edges: Sequence[Tuple[int, int]]) -> List[bool]:
        """Delete a batch of edges; per-edge "was present" results."""
        return self.update_batch(["delete"] * len(edges), edges)

    def insert(self, u: int, v: int) -> bool:
        return self.insert_batch([(u, v)])[0]

    def delete(self, u: int, v: int) -> bool:
        return self.delete_batch([(u, v)])[0]

    # -- reads ---------------------------------------------------------------
    def connected_batch(self, pairs: Sequence[Tuple[int, int]]) -> List[bool]:
        """Answer a batch of connectivity queries with ONE device program
        and ONE blocking fetch: the fused refresh+gather pass when an
        update was dispatched since the last read (its fetch also resolves
        every outstanding update handle), the lean gather/compare dispatch
        when the labels are known-current (queries padded to a power of
        two to bound recompiles)."""
        arr = self._edge_array(pairs)
        npairs = arr.shape[1]
        if not npairs:
            return []
        uv = np.zeros((2, _pow2(npairs)), np.int32)
        uv[:, :npairs] = arr
        if not (self._maybe_stale or self._unresolved):
            ans = _connected_pairs(self.state.labels, jnp.asarray(uv))
            return np.asarray(_host_fetch(ans))[:npairs].tolist()
        def commit():
            # cleared BEFORE the dispatch: a reentrant update re-marks it
            # (the lazy-but-correct refresh ordering, cf. DynamicGraph);
            # the fused read DONATES state too, so it is guarded like an
            # update (a failed refresh must restore labels + dirty state)
            self._maybe_stale = False
            fn = read_pass if self.donate else read_pass_undonated
            self.state, ans = fn(self.state, jnp.asarray(uv), n=self.n,
                                 e_bound=self._rebuild_bound(),
                                 n_shards=self.n_shards,
                                 use_pallas=self.use_pallas,
                                 placement=self._pstatic)
            return ans

        if self._guard is None:
            ans = commit()
        else:
            ans = self._guard.run(commit, self._snapshot, self._restore,
                                  site="graph.read_pass")
        got = self._resolve_through(None, extra=ans)
        return np.asarray(got)[:npairs].tolist()

    def connected(self, u: int, v: int) -> bool:
        return self.connected_batch([(u, v)])[0]

    def read_batch(self, methods: Sequence[str],
                   inputs: Sequence[Any]) -> List[Any]:
        assert all(m == "connected" for m in methods)
        return self.connected_batch(inputs)

    # -- megapass (DESIGN.md §17) --------------------------------------------
    def mixed_rounds(self, rounds):
        """R heterogeneous update/read rounds as ONE donated scan program.

        Each update round gets the same elimination pre-pass as
        ``update_batch_async`` (one lane per distinct edge class, host
        chain rule for the rest); each round's lanes pack into ≤ c_max
        rows of the tagged (R, 2, c_max) row stack, pow2-padded with
        no-op UPDATE rows (nb=0 — a pad READ row would dispatch refresh
        machinery and bump the rebuild counter).  The capacity guard
        covers the WHOLE megapass conservatively (live bound + every
        round's distinct-edge inserts) before anything dispatches, and
        one static ``e_bound`` ≥ that bound serves every read row.  NO
        blocking transfer at dispatch: all handles share one fetch
        (:class:`_GraphMegaFetch`)."""
        rounds = [(kind, list(methods), list(inputs))
                  for kind, methods, inputs in rounds]
        c = self.c_max
        row_tags: List[int] = []
        row_buv: List[np.ndarray] = []
        row_flags: List[np.ndarray] = []
        row_nb: List[int] = []
        plans: List[Tuple] = []
        total_lane_ins = 0
        for kind, methods, inputs in rounds:
            if kind == "update":
                for m in methods:
                    if m not in ("insert", "delete"):
                        raise ValueError(f"unknown update method {m!r}")
                arr = self._edge_array(inputs)
                n_ops = arr.shape[1]
                by_edge: Dict[Tuple[int, int],
                              List[Tuple[int, bool]]] = {}
                for i in range(n_ops):
                    u, v = int(arr[0, i]), int(arr[1, i])
                    if u == v:
                        continue
                    by_edge.setdefault((min(u, v), max(u, v)), []).append(
                        (i, methods[i] == "insert"))
                classes = list(by_edge.values())
                d = len(classes)
                self.eliminated_ops += n_ops - d
                if d == 0:                    # empty / all self-loops
                    handle = AsyncUpdateResult(self, [], n_ops, [], [], c)
                    handle._resolve([])
                    plans.append(("done", handle))
                    continue
                lane_ins = sum(ops[-1][1] for ops in classes)
                total_lane_ins += lane_ins
                row_lo = len(row_tags)
                lane_counts: List[int] = []
                for r in range(-(-d // c)):
                    chunk = classes[r * c : (r + 1) * c]
                    buv_r = np.zeros((2, c), np.int32)
                    sel_r = np.zeros((c,), bool)
                    for j, ops in enumerate(chunk):
                        buv_r[:, j] = arr[:, ops[-1][0]]
                        sel_r[j] = ops[-1][1]
                    row_tags.append(MEGA_UPDATE)
                    row_buv.append(buv_r)
                    row_flags.append(sel_r)
                    row_nb.append(len(chunk))
                    lane_counts.append(len(chunk))
                inner = AsyncUpdateResult(self, [], n_ops, classes,
                                          lane_counts, c)
                plans.append(("update", row_lo, len(row_tags), inner))
            elif kind == "read":
                if any(m != "connected" for m in methods):
                    raise ValueError("graph read rounds take 'connected'")
                arr = self._edge_array(inputs)
                npairs = arr.shape[1]
                if npairs == 0:
                    plans.append(("done", substrate._DoneReads([])))
                    continue
                row_lo = len(row_tags)
                counts: List[int] = []
                for r in range(-(-npairs // c)):
                    chunk = arr[:, r * c : (r + 1) * c]
                    uv = np.zeros((2, c), np.int32)
                    uv[:, :chunk.shape[1]] = chunk
                    row_tags.append(MEGA_READ)
                    row_buv.append(uv)
                    row_flags.append(np.zeros((c,), bool))
                    row_nb.append(chunk.shape[1])
                    counts.append(chunk.shape[1])
                plans.append(("read", row_lo, counts))
            else:
                raise ValueError(f"unknown round kind {kind!r} "
                                 f"(want 'update' or 'read')")
        # whole-megapass capacity guard, BEFORE any dispatch (atomic
        # refusal: conservative — deletes inside the pass could free
        # slots, but the refusal contract trades that for exactness)
        if self._live_bound() + total_lane_ins > self.capacity:
            raise ValueError(
                f"edge capacity {self.capacity} exceeded: "
                f"≤{self._live_bound()} live edges "
                f"+ {total_lane_ins} distinct-edge inserts (megapass)")
        if not row_tags:                      # nothing dispatches
            return [p[1] for p in plans]
        # staleness after the pass, from LIVE rows (pads are no-ops):
        # an update row after the last read row leaves labels stale
        has_read = MEGA_READ in row_tags
        last_read = max((i for i, t in enumerate(row_tags)
                         if t == MEGA_READ), default=-1)
        upd_after = any(t == MEGA_UPDATE
                        for t in row_tags[last_read + 1:])
        # pow2-pad the row count with no-op UPDATE rows
        target = 1 << (len(row_tags) - 1).bit_length()
        while len(row_tags) < target:
            row_tags.append(MEGA_UPDATE)
            row_buv.append(np.zeros((2, c), np.int32))
            row_flags.append(np.zeros((c,), bool))
            row_nb.append(0)

        def commit():
            self._outstanding_ins += total_lane_ins
            self._maybe_stale = (upd_after if has_read
                                 else self._maybe_stale or upd_after)
            # one conservative static compaction bound for every read
            # row, through the same pow2 hysteresis as _rebuild_bound
            lb = max(1, self._live_bound())
            if lb > self._e_bound or 4 * lb <= self._e_bound:
                self._e_bound = _pow2(lb)
            fn = (mixed_rounds_pass if self.donate
                  else mixed_rounds_pass_undonated)
            self.state, oks = fn(
                self.state, jnp.asarray(row_tags, jnp.int32),
                jnp.asarray(np.stack(row_buv)),
                jnp.asarray(np.stack(row_flags)),
                jnp.asarray(row_nb, jnp.int32),
                n=self.n, e_bound=self._e_bound,
                n_shards=self.n_shards, use_pallas=self.use_pallas,
                placement=self._pstatic)
            return oks

        if self._guard is None:
            oks = commit()
        else:
            oks = self._guard.run(commit, self._snapshot, self._restore,
                                  site="graph.mixed_rounds")
        shared = _GraphMegaFetch(self, oks)
        handles: List[Any] = []
        for plan in plans:
            if plan[0] == "done":
                handles.append(plan[1])
            elif plan[0] == "update":
                _, lo, hi, inner = plan
                shared._upd.append((inner, lo, hi))
                handles.append(_MegaUpdateHandle(shared, inner))
            else:
                _, lo, counts = plan
                handles.append(_GraphReadRound(shared, lo, counts))
        return handles

    # -- debug / test helpers -------------------------------------------------
    def full_rebuilds(self) -> int:
        """Device-side full-rebuild counter (the union-find fast-path
        regression hook: insert-only traffic must not bump it)."""
        return int(np.asarray(self.state.n_full))

    def edges(self) -> Set[Tuple[int, int]]:
        """Host copy of the live edge set (test/debug; one fetch)."""
        eu, ev, valid = _host_fetch((self.state.eu, self.state.ev,
                                     self.state.valid))
        valid = np.asarray(valid)
        return set(zip(np.asarray(eu)[valid].tolist(),
                       np.asarray(ev)[valid].tolist()))


# ---------------------------------------------------------------------------
# Registration (DESIGN.md §16) — factories + op generators + adaptive hooks
# ---------------------------------------------------------------------------
from . import read_opt as _read_opt
from .dynamic_graph import DynamicGraph as _DynamicGraph

_N_DEFAULT = 24


def _gen_update(rng, k, ctx):
    """Pool-biased edge batches: 60% revisit a known edge (deletes and
    duplicate inserts actually collide), insert/delete at 65/35."""
    pool = ctx.setdefault("edges", [])
    n = ctx.get("n", _N_DEFAULT)
    methods, inputs = [], []
    for _ in range(k):
        if pool and rng.random() < 0.6:
            u, v = pool[int(rng.integers(len(pool)))]
        else:
            u = int(rng.integers(n))
            v = int(rng.integers(n))
            pool.append((u, v))
        methods.append("insert" if rng.random() < 0.65 else "delete")
        inputs.append((u, v))
    return methods, inputs


def _gen_read(rng, k, ctx):
    n = ctx.get("n", _N_DEFAULT)
    return (["connected"] * k,
            [(int(rng.integers(n)), int(rng.integers(n)))
             for _ in range(k)])


def _refusal_batch(ds: DeviceGraph):
    """capacity + 1 distinct fresh edge classes: the whole-batch edge
    bound must refuse before any slice dispatches."""
    need = ds.capacity + 1
    pairs = [(u, v) for u in range(ds.n) for v in range(u + 1, ds.n)]
    assert len(pairs) >= need, "vertex count too small for refusal probe"
    return (["insert"] * need, pairs[:need])


def _make(n: int = _N_DEFAULT, edge_capacity: int = 256, c_max: int = 8,
          n_shards: int = 2, **kw) -> DeviceGraph:
    return DeviceGraph(n, edge_capacity=edge_capacity, c_max=c_max,
                       n_shards=n_shards, **kw)


def _make_host(ds: DeviceGraph) -> _DynamicGraph:
    host = _DynamicGraph(ds.n)
    for u, v in sorted(ds.edges()):
        host.insert(u, v)
    return host


def _edge_set(obj):
    edges = obj.edges() if callable(obj.edges) else obj.edges
    return {(min(u, v), max(u, v)) for u, v in edges}


def _dump_compare(ds: DeviceGraph, oracle) -> None:
    got, want = _edge_set(ds), _edge_set(oracle)
    assert got == want, (sorted(got), sorted(want))


substrate.register(substrate.StructureSpec(
    name="graph",
    module="repro.core.device_graph",
    title="dynamic connectivity graph",
    make=_make,
    make_host=_make_host,
    gen_update=_gen_update,
    gen_read=_gen_read,
    new_ctx=lambda: {"n": _N_DEFAULT},
    dump_compare=_dump_compare,
    compact=_read_opt._compact_graph,
    refusal_batch=_refusal_batch,
    megapass=True,
    bench="benchmarks.bench_graph",
    bench_smoke=("--vertices", "300", "--reads", "50", "100",
                 "--threads", "1", "4", "--ops", "60"),
    extras={"serve_kw": dict(c_max=64, n_shards=4),
            # ctor accepts placement= (DESIGN.md §18); serve.py keys
            # --mesh-shards eligibility off this marker, and the
            # placement tests pin it to the class attribute
            "placement": True},
))
