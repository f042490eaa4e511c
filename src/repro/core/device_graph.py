"""Device-resident batched dynamic graph (DESIGN.md §11) — the §5.1
read-dominated application rebuilt to the sharded-PQ tier's standard.

The host tier (``dynamic_graph.DynamicGraph``) keeps the edge set in a
Python ``set`` and rebuilds the full component labeling in pure XLA after
every update batch.  This engine moves the edges, a spanning forest of
them and the refresh bookkeeping onto the device, so one combining pass
costs one fused update program plus one fused read program with a single
blocking fetch, mirroring the batched-PQ architecture (``sharded_pq.py``,
DESIGN.md §10):

* **edge buffer** — a fixed-capacity endpoint-array pair plus a validity
  mask (CSR-free: connectivity needs the edge multiset, not adjacency)
  and a stack of its free slots.  One ``update_pass`` applies ≤ ``c_max``
  MIXED insert/delete requests with sequential arrival-order semantics:
  per-lane results come from the last-earlier-same-edge chain rule, while
  the buffer takes only the NET effect per edge class (removals push
  their slots on the free stack, additions pop theirs; transient
  insert+delete pairs never touch memory).
* **spanning forest** — a forest bit per edge slot and, per vertex, its
  forest parent and the slot of the edge to it.  After every refresh the
  forest's trees are the graph's components and each vertex's label is
  its tree's root.  A removed forest edge is cut at once (its child
  becomes a root and joins a short cut list); a removed edge outside the
  forest changes no component, and an insert only joins the pending
  list.  The host never looks at the masks to decide how to refresh.
* **fused read pass** — ``connected`` batches run refresh + gather/compare
  as ONE program.  A ``lax.switch`` on the state picks the refresh:
  *keep* (labels current), *merge* (pending inserts only: each one that
  joins two trees re-roots the shallower side at its endpoint and hangs
  it under the other, relabelling that tree), *repair* (forest edges
  were cut: the cut-off subtrees are found by walking down the forest,
  the live edges leaving them are the crossing candidates, and they link
  the pieces as a merge does), or *scratch* (after a bulk load, a
  pending or cut list that overflowed, or a repair past its static caps
  :data:`MOVED_CAP`/:data:`CROSS_CAP`): the breadth-first
  ``spanning_forest`` fixpoint over every live edge, which also picks a
  new forest.
* **sync-free update publishing** — ``update_batch_async`` leaves the
  per-request result masks on device; they ride the next read's single
  blocking fetch (or are fetched at ``result()``), together with the
  refresh counters.  A combining pass of updates + reads therefore costs
  one blocking transfer, the same contract as the PQ's ``apply_async``
  (regression-tested with the ``_host_fetch`` counting hook).

Every jitted pass **donates** the graph state, so the buffers update in
place (zero-copy, DESIGN.md §10); ``donate=False`` is the copy-per-pass
ablation twin.  The wrapper keeps a host mirror of the live edge count —
exact after every resolved fetch, a conservative upper bound in between —
for the capacity guard and the pow2 compaction bound of the scratch
fixpoint.  The wrapper is not thread-safe; confine each instance to one
thread at a time (the read-optimized combiner provides exactly that
serialization).
"""
from __future__ import annotations

from typing import (Any, Dict, List, NamedTuple, Optional, Sequence, Set,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.label_prop import require_pallas_fits, spanning_forest
from repro.kernels.sorted_merge.ops import _prefix_sum

from . import placement as _placement
from . import substrate
from .faults import make_guard
from .tracing import TRACER

# All device→host transfers on the graph hot path route through this hook
# so tests can count blocking syncs (same idiom as batched_pq._host_fetch).
_host_fetch = jax.device_get

# refresh branches, in the order of the read's ``lax.switch``; also the
# first four entries of ``GraphState.stats``
KEEP, MERGE, REPAIR, SCRATCH = 0, 1, 2, 3
BRANCHES = ("keep", "merge", "repair", "scratch")
# the other entries of ``stats``: repairs that overflowed a cap and ran
# from scratch, and the steps of the last repair's walk down the forest
# or of the last scratch fixpoint (counting the last, which finds
# nothing)
OVERFLOW, ITERS = 4, 5
# vertices a repair may find below its cuts, and live edges leaving them
# (crossing candidates); past either it runs from scratch
MOVED_CAP = 64
CROSS_CAP = 4096
# the prefix sums that compact the buffer's masks are exact below 2^24
MAX_EDGE_CAPACITY = (1 << 24) - 1


class GraphState(NamedTuple):
    """Device-resident dynamic graph: edge buffer, spanning forest, labels
    and refresh state.

    The edge arrays carry one extra SCRATCH slot at index ``capacity`` —
    the graph twin of the heap's ``a[0]`` scratch: predicated scatters
    route every inactive lane there, so an active lane can never collide
    with an inactive write-back (duplicate scatter indices with different
    values are undefined).  The vertex-indexed arrays and the lists drop
    inactive lanes instead (``mode="drop"``, an index past the end).

    Forest invariant (after every refresh): ``in_f`` marks exactly the
    slots ``pslot[v]`` of the vertices that have a parent; slot
    ``pslot[v]`` holds the live edge ``{v, par[v]}``; the trees are the
    components; ``labels[v]`` is ``v``'s root.  Between refreshes the
    update pass only cuts: a removed forest edge's child gets
    ``par = self``, ``pslot = capacity`` and a place on the cut list."""

    eu: jax.Array       # (capacity+1,) int32 endpoint min (junk if ~valid)
    ev: jax.Array       # (capacity+1,) int32 endpoint max
    valid: jax.Array    # (capacity+1,) bool_; [capacity] stays False
    in_f: jax.Array     # (capacity+1,) bool_: the slot's edge is in the forest
    free: jax.Array     # (capacity,) int32 free slots, a stack
    n_free: jax.Array   # () int32 entries of ``free`` in use
    labels: jax.Array   # (n,) int32 root of each vertex's tree
    par: jax.Array      # (n,) int32 forest parent (self at a root)
    pslot: jax.Array    # (n,) int32 slot of the edge to par, or capacity
    pend: jax.Array     # (list_cap,) int32 slots inserted since a refresh
    n_pend: jax.Array   # () int32; past list_cap some went unlisted
    cut: jax.Array      # (list_cap,) int32 vertices cut off their parent
    n_cut: jax.Array    # () int32; past list_cap some went unlisted
    fresh: jax.Array    # () bool_: no forest yet, refresh from scratch
    stats: jax.Array    # (6,) int32 refreshes by branch, OVERFLOW, ITERS


# ---------------------------------------------------------------------------
# Jitted combining passes (donated state: zero-copy buffer updates)
# ---------------------------------------------------------------------------
def _first_set(mask: jax.Array, k: int, fill: int
               ) -> Tuple[jax.Array, jax.Array]:
    """``(indices of the first k set entries of mask, padded with fill,
    number set)``: a prefix sum and k binary searches, so nothing
    mask-wide is scattered (``jnp.nonzero`` scatters every entry)."""
    csum = _prefix_sum(mask)
    want = jnp.arange(1, k + 1, dtype=jnp.int32)
    idx = jnp.searchsorted(csum, want, side="left").astype(jnp.int32)
    return jnp.where(want <= csum[-1], idx, fill), csum[-1]


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

    Netted-out edges push their slots on the free stack; a netted-out
    forest edge also cuts its child off (see ``GraphState``).  Netted-in
    edges pop slots and join the pending list.  Lists that overflow keep
    counting, which sends the next refresh to scratch.

    Returns ``(state, ok)`` — the per-request results stay on device
    until fetched (see ``AsyncUpdateResult``)."""
    st = state
    cap = st.eu.shape[0] - 1                          # [cap] is scratch
    n = st.par.shape[0]
    list_cap = st.pend.shape[0]
    c = buv.shape[1]
    lane = jnp.arange(c, dtype=jnp.int32)
    u = jnp.minimum(buv[0], buv[1])
    v = jnp.maximum(buv[0], buv[1])
    act = (lane < nb) & (u != v)      # self-loops are never stored: insert
    #                                   and delete of one both report False
    match = (st.valid[None, :] & (st.eu[None, :] == u[:, None])
             & (st.ev[None, :] == v[:, None]))        # (c, capacity+1)
    in_buf = jnp.any(match, axis=1)
    slot = jnp.argmax(match, axis=1).astype(jnp.int32)  # unique if in_buf

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

    # removals: predicated scatters (inactive lanes write the scratch
    # slot, so they can never collide with an active lane's target)
    tgt = jnp.where(rem, slot, cap)
    valid = st.valid.at[tgt].set(jnp.where(rem, False, st.valid[tgt]))
    cut_f = rem & st.in_f[slot]
    in_f = st.in_f.at[tgt].set(jnp.where(rem, False, st.in_f[tgt]))
    child = jnp.where(st.pslot[u] == slot, u, v)      # owns the forest edge
    ctgt = jnp.where(cut_f, child, n)
    par = st.par.at[ctgt].set(child, mode="drop")
    pslot = st.pslot.at[ctgt].set(cap, mode="drop")
    crank = jnp.cumsum(cut_f.astype(jnp.int32)) - 1
    cut = st.cut.at[jnp.where(cut_f, st.n_cut + crank, list_cap)].set(
        child, mode="drop")
    n_cut = st.n_cut + jnp.sum(cut_f.astype(jnp.int32))
    rrank = jnp.cumsum(rem.astype(jnp.int32)) - 1
    free = st.free.at[jnp.where(rem, st.n_free + rrank, cap)].set(
        slot, mode="drop")
    n_free = st.n_free + jnp.sum(rem.astype(jnp.int32))

    # additions pop the free stack; the device-side clamp keeps them
    # in-bounds even if the host guard's mirror were wrong
    rank = jnp.cumsum(add.astype(jnp.int32)) - 1
    add = add & (rank < n_free)
    tgt = jnp.where(add, free[jnp.clip(n_free - 1 - rank, 0, cap - 1)], cap)
    eu = st.eu.at[tgt].set(jnp.where(add, u, st.eu[tgt]))
    ev = st.ev.at[tgt].set(jnp.where(add, v, st.ev[tgt]))
    valid = valid.at[tgt].set(jnp.where(add, True, valid[tgt]))
    valid = valid.at[cap].set(False)                  # scratch stays dead
    n_add = jnp.sum(add.astype(jnp.int32))
    pend = st.pend.at[jnp.where(add, st.n_pend + rank, list_cap)].set(
        tgt, mode="drop")
    state = st._replace(eu=eu, ev=ev, valid=valid, in_f=in_f, free=free,
                        n_free=n_free - n_add, par=par, pslot=pslot,
                        pend=pend, n_pend=st.n_pend + n_add, cut=cut,
                        n_cut=n_cut)
    return state, ok


def _nearer_root(par: jax.Array, x: jax.Array, y: jax.Array) -> jax.Array:
    """True iff ``x``'s path to its root is no longer than ``y``'s (both
    climb in step until one reaches a root)."""
    def climbing(c):
        return (par[c[0]] != c[0]) & (par[c[1]] != c[1])

    cx, _ = jax.lax.while_loop(climbing, lambda c: (par[c[0]], par[c[1]]),
                               (x, y))
    return par[cx] == cx


def _hang(par: jax.Array, pslot: jax.Array, a: jax.Array, b: jax.Array,
          s: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Re-root ``a``'s tree at ``a`` (reverse the path to its old root)
    and hang it under ``b`` by the edge in slot ``s``."""
    def step(c):
        par, pslot, cur, prev, prev_slot, _ = c
        nxt, nslot = par[cur], pslot[cur]
        par = par.at[cur].set(prev)
        pslot = pslot.at[cur].set(prev_slot)
        return par, pslot, nxt, cur, nslot, nxt == cur

    out = jax.lax.while_loop(lambda c: ~c[5], step,
                             (par, pslot, a, b, s, jnp.bool_(False)))
    return out[0], out[1]


def _link(st: GraphState, cand: jax.Array) -> GraphState:
    """Link the trees the live candidate edges (slots; ``capacity`` pads)
    join: while a candidate's endpoints carry different labels, take the
    first such, hang the endpoint with the shorter root path (its tree
    re-rooted there) under the other, put the edge in the forest and
    give the hung tree the other's root as label.  Each step joins two
    trees, so the loop runs once per tree merged away.  Clears the
    pending and cut lists."""
    alive = st.valid[cand]
    x, y = st.eu[cand], st.ev[cand]

    def crossing(c):
        labels, _par, _pslot, _in_f, la, lb = c
        return jnp.any(alive & (la != lb))

    def step(c):
        labels, par, pslot, in_f, la, lb = c
        i = jnp.argmax(alive & (la != lb))
        x_first = _nearer_root(par, x[i], y[i])
        a = jnp.where(x_first, x[i], y[i])
        b = jnp.where(x_first, y[i], x[i])
        par, pslot = _hang(par, pslot, a, b, cand[i])
        loser = jnp.where(x_first, la[i], lb[i])
        winner = jnp.where(x_first, lb[i], la[i])
        relabel = lambda t: jnp.where(t == loser, winner, t)  # noqa: E731
        return (relabel(labels), par, pslot, in_f.at[cand[i]].set(True),
                relabel(la), relabel(lb))

    labels, par, pslot, in_f, _la, _lb = jax.lax.while_loop(
        crossing, step, (st.labels, st.par, st.pslot, st.in_f,
                         st.labels[x], st.labels[y]))
    return st._replace(labels=labels, par=par, pslot=pslot, in_f=in_f,
                       n_pend=jnp.int32(0), n_cut=jnp.int32(0))


def _pending(st: GraphState) -> jax.Array:
    cap = st.eu.shape[0] - 1
    lane = jnp.arange(st.pend.shape[0], dtype=jnp.int32)
    return jnp.where(lane < st.n_pend, st.pend, cap)


def _moved(st: GraphState) -> Tuple[jax.Array, jax.Array, jax.Array,
                                    jax.Array]:
    """The vertices below the cuts, walking down the forest one level a
    step from the cut list: ``(vertex, its subtree's top, steps, fits)``
    rows of :data:`MOVED_CAP` (padded with ``n``), the steps counting
    the last, which finds nothing; ``fits`` is False past the cap."""
    n = st.par.shape[0]
    m = MOVED_CAP
    j = jnp.arange(m, dtype=jnp.int32)
    k = min(m, st.cut.shape[0])
    seed = jnp.full((m,), n, jnp.int32).at[:k].set(st.cut[:k])
    mv = jnp.where(j < st.n_cut, seed, n)
    iota = jnp.arange(n, dtype=jnp.int32)
    has_par = st.par != iota

    def more(c):
        _mv, _mt, lo, hi, _k, fits = c
        return fits & (lo < hi)

    def level(c):
        mv, mt, lo, hi, steps, _ = c
        front = jnp.where((j >= lo) & (j < hi), mv, -1)
        eq = (st.par[:, None] == front[None, :]) & has_par[:, None]
        below = jnp.any(eq, axis=1)
        top = mt[jnp.argmax(eq, axis=1)]
        idx, cnt = _first_set(below, m, n)
        pos = jnp.where(j < cnt, hi + j, m)
        mv = mv.at[pos].set(idx, mode="drop")
        mt = mt.at[pos].set(top[jnp.clip(idx, 0, n - 1)], mode="drop")
        return mv, mt, hi, hi + cnt, steps + 1, hi + cnt <= m

    mv, mt, _lo, _hi, steps, fits = jax.lax.while_loop(
        more, level, (mv, mv, jnp.int32(0), jnp.minimum(st.n_cut, m),
                      jnp.int32(0), st.n_cut <= m))
    return mv, mt, steps, fits


def _read_impl(state: GraphState, uv: jax.Array, *, n: int, e_bound: int,
               n_shards: int, use_pallas: bool, placement=None
               ) -> Tuple[GraphState, jax.Array, jax.Array]:
    """Fused refresh + gather/compare: ONE program per read batch.

    A ``lax.switch`` picks the refresh from the state (module docstring):
    scratch after a bulk load or an overflowed list, repair after cuts,
    merge with pending inserts only, keep otherwise; a repair past a cap
    runs from scratch instead.  The scratch fixpoint reads the buffer in
    place when the static pow2 bound ``e_bound`` (≥ the live count)
    reaches the capacity, else the live edges compacted to ``e_bound``
    positions (padding repeats the scratch slot, a sanitized no-op).

    ``placement`` (static): a ``MeshPlacement`` runs the scratch fixpoint
    edge-partitioned (DESIGN.md §18 — D devices scatter-min disjoint edge
    blocks, ``pmin`` merges the tables).  The merge, the repair and the
    update passes stay replicated: ``GraphState`` has no K axis to split,
    and they touch a few trees' paths, not the edge list.

    Returns ``(state, answers, stats)``; ``stats`` is a copy of the
    state's refresh counters, for the read's one fetch."""
    cap = state.eu.shape[0] - 1
    list_cap = state.pend.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)

    def scratch(st):
        valid = st.valid
        if e_bound >= cap:
            idx = None
            seu = jnp.where(valid[:cap], st.eu[:cap], 0)
            sev = jnp.where(valid[:cap], st.ev[:cap], 0)
        else:
            idx = jnp.nonzero(valid, size=e_bound, fill_value=cap)[0]
            seu = jnp.where(valid[idx], st.eu[idx], 0)
            sev = jnp.where(valid[idx], st.ev[idx], 0)
        labels, parent, steps = spanning_forest(
            seu, sev, n=n, n_shards=n_shards, use_pallas=use_pallas,
            placement=placement)
        has = parent < seu.shape[0]
        pos = jnp.where(has, parent, 0)
        pslot = jnp.where(has, pos if idx is None else idx[pos], cap)
        par = jnp.where(has, jnp.where(seu[pos] == iota, sev[pos], seu[pos]),
                        iota)
        in_f = jnp.zeros((cap + 1,), jnp.bool_).at[pslot].set(True)
        st = st._replace(labels=labels, par=par, pslot=pslot,
                         in_f=in_f.at[cap].set(False), n_pend=jnp.int32(0),
                         n_cut=jnp.int32(0), fresh=jnp.bool_(False))
        return st, jnp.int32(SCRATCH), jnp.int32(0), steps

    def keep(st):
        return st, jnp.int32(KEEP), jnp.int32(0), st.stats[ITERS]

    def merge(st):
        return (_link(st, _pending(st)), jnp.int32(MERGE), jnp.int32(0),
                st.stats[ITERS])

    def repair(st):
        mv, top, steps, fits = _moved(st)
        labels = st.labels.at[mv].set(top, mode="drop")
        inside = lambda e: jnp.any(e[:, None] == mv[None, :], axis=1)  # noqa
        leaving = st.valid & ~st.in_f & (inside(st.eu) | inside(st.ev))
        cand, n_cand = _first_set(leaving, CROSS_CAP, cap)
        fits = fits & (n_cand <= CROSS_CAP)

        def link(st):
            st = _link(st._replace(labels=labels),
                       jnp.concatenate([cand, _pending(st)]))
            return st, jnp.int32(REPAIR), jnp.int32(0), steps

        def overflow(st):
            st, branch, _, k = scratch(st)
            return st, branch, jnp.int32(1), k

        return jax.lax.cond(fits, link, overflow, st)

    branch = jnp.where(
        state.fresh | (state.n_pend > list_cap) | (state.n_cut > list_cap),
        SCRATCH, jnp.where(state.n_cut > 0, REPAIR,
                           jnp.where(state.n_pend > 0, MERGE, KEEP)))
    st, taken, over, steps = jax.lax.switch(
        branch, [keep, merge, repair, scratch], state)
    stats = (st.stats.at[taken].add(1).at[OVERFLOW].add(over)
             .at[ITERS].set(steps))
    st = st._replace(stats=stats)
    return st, st.labels[uv[0]] == st.labels[uv[1]], stats + 0


# ``state`` is DONATED on every pass — the edge buffer, forest, labels and
# refresh state update in place (DESIGN.md §10/§11); the ``*_undonated``
# twins are the copy-per-pass ablation (EXPERIMENTS §Ablations).
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
                       placement=None
                       ) -> Tuple[GraphState, jax.Array, jax.Array]:
    """R heterogeneous update/read rounds as ONE ``lax.scan`` program
    (DESIGN.md §17): per row, a ``lax.cond`` on the round tag picks the
    fused mixed-op update pass or the fused refresh+gather read pass.
    ``tags`` (R,), ``buv`` (R, 2, c) endpoints (update) or query pairs
    (read), ``flags`` (R, c) insert selectors (ignored on read rows),
    ``nb`` (R,) live lane counts (update rows only — reads answer every
    lane and the host masks by count).  ``e_bound`` is one conservative
    static compaction bound covering every read row in the pass.
    Returns ``(state, oks (R, c), stats)`` — update rows stack their ok
    masks, read rows their connectivity answers, into the per-round
    slots; ``stats`` is a copy of the refresh counters after the pass."""

    def body(st, rnd):
        tag, ruv, rfl, rnb = rnd

        def upd(s):
            return _update_impl(s, ruv, rfl, rnb)

        def rd(s):
            s, ans, _stats = _read_impl(s, ruv, n=n, e_bound=e_bound,
                                        n_shards=n_shards,
                                        use_pallas=use_pallas,
                                        placement=placement)
            return s, ans

        st, ok = jax.lax.cond(tag == MEGA_READ, rd, upd, st)
        return st, ok

    state, oks = jax.lax.scan(body, state, (tags, buv, flags, nb))
    return state, oks, state.stats + 0


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

    def __init__(self, owner: "DeviceGraph", oks: jax.Array,
                 stats: jax.Array):
        self._owner: Optional["DeviceGraph"] = owner
        self._oks = (oks, stats)
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
      use_pallas: run the scratch fixpoint's propagation steps through
        the ``grid=(K,)`` Pallas kernel (DESIGN.md §11) instead of the
        XLA twin.
      donate: zero-copy (donated) passes (default); False is the
        copy-per-pass ablation twin.
      placement: shard layout for the scratch fixpoint (DESIGN.md §18)
        — a ``MeshPlacement`` partitions the edge list across D devices
        and min-merges the label tables with ``pmin`` (bit-exact per
        step, min being associative/commutative).  Updates, merges and
        repairs stay replicated (``GraphState`` is flat — there is no K
        axis to place).  Not combinable with ``use_pallas``.
      edges: optional initial edge list, (m, 2) vertex pairs — loaded in
        bulk on the host (canonicalized, deduplicated, self-loops
        dropped); the first read builds the forest from scratch.

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
        if edge_capacity > MAX_EDGE_CAPACITY:
            raise ValueError(f"edge_capacity must be <= {MAX_EDGE_CAPACITY}")
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
        list_cap = 2 * self.c_max
        eu, ev, valid = self._initial_edges(edges)
        m = int(valid.sum())
        loaded = m > 0
        # +1: the scratch slot for predicated scatters (see GraphState);
        # the free stack pops the lowest free slot first
        self.state = GraphState(
            eu=jnp.asarray(eu),
            ev=jnp.asarray(ev),
            valid=jnp.asarray(valid),
            in_f=jnp.zeros((self.capacity + 1,), jnp.bool_),
            free=jnp.asarray(np.arange(self.capacity - 1, -1, -1,
                                       dtype=np.int32)),
            n_free=jnp.int32(self.capacity - m),
            labels=jnp.arange(self.n, dtype=jnp.int32),
            par=jnp.arange(self.n, dtype=jnp.int32),
            pslot=jnp.full((self.n,), self.capacity, jnp.int32),
            pend=jnp.zeros((list_cap,), jnp.int32),
            n_pend=jnp.int32(0),
            cut=jnp.zeros((list_cap,), jnp.int32),
            n_cut=jnp.int32(0),
            # a bulk load has no forest yet: the first read builds one
            fresh=jnp.bool_(loaded),
            stats=jnp.zeros((ITERS + 1,), jnp.int32),
        )
        # live-edge-count mirror: exact after every resolved fetch; the
        # bound adds inserts whose result masks are still on device
        self._n_edges = int(valid.sum())
        self._outstanding_ins = 0
        # elimination instrumentation (DESIGN.md §12): ops answered by the
        # host chain rule instead of a device lane
        self.eliminated_ops = 0
        # the device's refresh counters as the last fetch brought them
        # (TRACER counts the difference each fetch makes)
        self._stats_seen = np.zeros((ITERS + 1,), np.int64)
        self._unresolved: List[AsyncUpdateResult] = []
        # True iff an update pass was dispatched, or a bulk load left the
        # forest to build, since the last fused read — False means the
        # device labels are known-current and a read can take the lean
        # gather/compare dispatch
        self._maybe_stale = loaded
        # the scratch fixpoint's compaction bound: a pow2 ≥ the live
        # count, with hysteresis (grow on demand, shrink only on a 4x
        # drop) so a live count oscillating across a pow2 boundary
        # doesn't recompile the fused read pass every few batches
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

    def _net_classes(self, methods: Sequence[str], inputs: Sequence[Any]
                     ) -> Tuple[int, List[List[Tuple[int, bool]]],
                                List[Tuple[int, int]]]:
        """Elimination pre-pass (DESIGN.md §12): ``(op count, the ops'
        ``(index, is_insert)`` grouped by normalized edge class in
        first-touch order, each class's edge)``; self-loops join no
        class."""
        for m in methods:
            if m not in ("insert", "delete"):
                raise ValueError(f"unknown update method {m!r}")
        arr = self._edge_array(list(inputs))
        n_ops = arr.shape[1]
        by_edge: Dict[Tuple[int, int], List[Tuple[int, bool]]] = {}
        for i in range(n_ops):
            u, v = int(arr[0, i]), int(arr[1, i])
            if u != v:
                by_edge.setdefault((min(u, v), max(u, v)), []).append(
                    (i, methods[i] == "insert"))
        self.eliminated_ops += n_ops - len(by_edge)
        return n_ops, list(by_edge.values()), list(by_edge)

    def _lane_rows(self, classes, lane_uv
                   ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """One device lane per class (its edge and its LAST op's kind),
        packed into ``(endpoints (2, c_max), is_insert (c_max,))`` rows."""
        c = self.c_max
        rows = []
        for r in range(-(-len(classes) // c)):
            chunk = range(r * c, min(len(classes), (r + 1) * c))
            buv = np.zeros((2, c), np.int32)
            sel = np.zeros((c,), bool)
            buv[:, :len(chunk)] = np.asarray([lane_uv[k] for k in chunk]).T
            sel[:len(chunk)] = [classes[k][-1][1] for k in chunk]
            rows.append((buv, sel))
        return rows

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
        with TRACER.span("graph.prep"):
            n_ops, classes, lane_uv = self._net_classes(methods, inputs)
            d = len(classes)
            if d == 0:
                # nothing dispatched (empty, or self-loops only): the
                # labels stay known-current and the handle resolves on
                # the host
                handle = AsyncUpdateResult(self, [], n_ops, [], [],
                                           self.c_max)
                handle._resolve([])
                return handle
            lane_ins = sum(ops[-1][1] for ops in classes)
            # guard the WHOLE batch before dispatching any slice: a
            # mid-loop refusal would leave already-applied slices in the
            # buffer with the host mirrors (and _maybe_stale) never updated
            if self._live_bound() + lane_ins > self.capacity:
                raise ValueError(
                    f"edge capacity {self.capacity} exceeded: "
                    f"≤{self._live_bound()} live edges "
                    f"+ {lane_ins} distinct-edge inserts")
            # pow2-pad the round count (no-op rows, nb=0): the scan
            # program recompiles per distinct leading dim and batch sizes
            # are workload-driven — bucketing bounds the jit-cache variants
            rows = self._lane_rows(classes, lane_uv)
            n_rounds = _pow2(len(rows))
            buv = np.zeros((n_rounds, 2, self.c_max), np.int32)
            sel = np.zeros((n_rounds, self.c_max), bool)
            for r, (buv_r, sel_r) in enumerate(rows):
                buv[r], sel[r] = buv_r, sel_r
            lane_counts = [len(classes[r * self.c_max:(r + 1) * self.c_max])
                           for r in range(len(rows))]

        def commit():
            # mirror mutations live inside the guarded thunk so a
            # transactional restore rewinds them with the device state
            with TRACER.span("graph.dispatch"):
                if n_rounds == 1:
                    fn = update_pass if self.donate \
                        else update_pass_undonated
                    self.state, ok = fn(self.state, jnp.asarray(buv[0]),
                                        jnp.asarray(sel[0]), jnp.int32(d))
                else:
                    fn = update_rounds if self.donate \
                        else update_rounds_undonated
                    nb = np.zeros((n_rounds,), np.int32)
                    nb[:len(lane_counts)] = lane_counts
                    self.state, ok = fn(self.state, jnp.asarray(buv),
                                        jnp.asarray(sel), jnp.asarray(nb))
            self._outstanding_ins += lane_ins
            self._maybe_stale = True
            return [ok]

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
        with TRACER.span("graph.fetch"):
            fetched = _host_fetch(([h.masks for h in todo], extra))
        with TRACER.span("graph.convert"):
            for h, masks_h in zip(todo, fetched[0]):
                h._resolve(masks_h)
                self._unresolved.remove(h)
            if extra is None:
                return None
            got, stats = fetched[1]
            self._count_refreshes(stats)
            return got

    def _count_refreshes(self, stats) -> None:
        """Hand TRACER what the device's refresh counters gained since
        the last fetch, and the last walk's or fixpoint's step count.  A
        megapass handle resolved after a later pass brings an older
        snapshot: it adds nothing."""
        stats = np.asarray(stats, np.int64)
        gained = stats - self._stats_seen
        if (gained[:ITERS] < 0).any():
            return
        self._stats_seen = stats
        for i, name in enumerate(BRANCHES):
            TRACER.count(f"graph.refresh.{name}", int(gained[i]))
        TRACER.count("graph.repair.overflow", int(gained[OVERFLOW]))
        if gained[REPAIR] or gained[SCRATCH]:
            TRACER.gauge("graph.refresh.steps", int(stats[ITERS]))

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
        when the labels are known-current.  Queries pad to ``c_max``
        lanes (a pow2 multiple of it past that), so every combined read
        batch runs the one compiled program that carries the refresh."""
        with TRACER.span("graph.prep"):
            arr = self._edge_array(pairs)
            npairs = arr.shape[1]
            if not npairs:
                return []
            uv = np.zeros((2, self.c_max * _pow2(-(-npairs // self.c_max))),
                          np.int32)
            uv[:, :npairs] = arr
        if not (self._maybe_stale or self._unresolved):
            with TRACER.span("graph.dispatch"):
                ans = _connected_pairs(self.state.labels, jnp.asarray(uv))
            with TRACER.span("graph.fetch"):
                got = _host_fetch(ans)
            with TRACER.span("graph.convert"):
                TRACER.count("graph.refresh.lean")
                return np.asarray(got)[:npairs].tolist()

        def commit():
            # cleared BEFORE the dispatch: a reentrant update re-marks it
            # (the lazy-but-correct refresh ordering, cf. DynamicGraph);
            # the fused read DONATES state too, so it is guarded like an
            # update (a failed refresh must restore labels + forest)
            self._maybe_stale = False
            fn = read_pass if self.donate else read_pass_undonated
            with TRACER.span("graph.dispatch"):
                self.state, ans, stats = fn(
                    self.state, jnp.asarray(uv), n=self.n,
                    e_bound=self._rebuild_bound(), n_shards=self.n_shards,
                    use_pallas=self.use_pallas, placement=self._pstatic)
            return ans, stats

        if self._guard is None:
            out = commit()
        else:
            out = self._guard.run(commit, self._snapshot, self._restore,
                                  site="graph.read_pass")
        got = self._resolve_through(None, extra=out)
        with TRACER.span("graph.convert"):
            return np.asarray(got)[:npairs].tolist()

    def connected(self, u: int, v: int) -> bool:
        return self.connected_batch([(u, v)])[0]

    def read_batch(self, methods: Sequence[str],
                   inputs: Sequence[Any]) -> List[Any]:
        assert all(m == "connected" for m in methods)
        return self.connected_batch(inputs)

    # -- megapass (DESIGN.md §17) --------------------------------------------
    def _mega_plan(self, rounds):
        """The tagged rows of a megapass and each round's plan.  Raises
        before anything dispatches if the whole megapass could overflow
        the buffer (atomic refusal: conservative — deletes inside the
        pass could free slots, but the refusal contract trades that for
        exactness)."""
        c = self.c_max
        row_tags: List[int] = []
        row_buv: List[np.ndarray] = []
        row_flags: List[np.ndarray] = []
        row_nb: List[int] = []
        plans: List[Tuple] = []
        total_lane_ins = 0
        for kind, methods, inputs in rounds:
            methods, inputs = list(methods), list(inputs)
            if kind == "update":
                n_ops, classes, lane_uv = self._net_classes(methods, inputs)
                if not classes:               # empty / all self-loops
                    handle = AsyncUpdateResult(self, [], n_ops, [], [], c)
                    handle._resolve([])
                    plans.append(("done", handle))
                    continue
                total_lane_ins += sum(ops[-1][1] for ops in classes)
                row_lo = len(row_tags)
                lane_counts: List[int] = []
                for r, (buv_r, sel_r) in enumerate(
                        self._lane_rows(classes, lane_uv)):
                    nc = len(classes[r * c:(r + 1) * c])
                    row_tags.append(MEGA_UPDATE)
                    row_buv.append(buv_r)
                    row_flags.append(sel_r)
                    row_nb.append(nc)
                    lane_counts.append(nc)
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
        if self._live_bound() + total_lane_ins > self.capacity:
            raise ValueError(
                f"edge capacity {self.capacity} exceeded: "
                f"≤{self._live_bound()} live edges "
                f"+ {total_lane_ins} distinct-edge inserts (megapass)")
        return row_tags, row_buv, row_flags, row_nb, plans, total_lane_ins

    def mixed_rounds(self, rounds):
        """R heterogeneous update/read rounds as ONE donated scan program.

        Each update round gets the same elimination pre-pass as
        ``update_batch_async`` (one lane per distinct edge class, host
        chain rule for the rest); each round's lanes pack into ≤ c_max
        rows of the tagged (R, 2, c_max) row stack, pow2-padded with
        no-op UPDATE rows (nb=0 — a pad READ row would dispatch refresh
        machinery and count a refresh).  The capacity guard
        covers the WHOLE megapass conservatively (live bound + every
        round's distinct-edge inserts) before anything dispatches, and
        one static ``e_bound`` ≥ that bound serves every read row.  NO
        blocking transfer at dispatch: all handles share one fetch
        (:class:`_GraphMegaFetch`)."""
        with TRACER.span("graph.prep"):
            (row_tags, row_buv, row_flags, row_nb, plans,
             total_lane_ins) = self._mega_plan(rounds)
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
        c = self.c_max
        while len(row_tags) < _pow2(len(row_tags)):
            row_tags.append(MEGA_UPDATE)
            row_buv.append(np.zeros((2, c), np.int32))
            row_flags.append(np.zeros((c,), bool))
            row_nb.append(0)

        def commit():
            self._outstanding_ins += total_lane_ins
            self._maybe_stale = (upd_after if has_read
                                 else self._maybe_stale or upd_after)
            fn = (mixed_rounds_pass if self.donate
                  else mixed_rounds_pass_undonated)
            with TRACER.span("graph.dispatch"):
                # one conservative static compaction bound for every
                # read row
                self.state, oks, stats = fn(
                    self.state, jnp.asarray(row_tags, jnp.int32),
                    jnp.asarray(np.stack(row_buv)),
                    jnp.asarray(np.stack(row_flags)),
                    jnp.asarray(row_nb, jnp.int32),
                    n=self.n, e_bound=self._rebuild_bound(),
                    n_shards=self.n_shards, use_pallas=self.use_pallas,
                    placement=self._pstatic)
            return oks, stats

        if self._guard is None:
            oks, stats = commit()
        else:
            oks, stats = self._guard.run(commit, self._snapshot,
                                         self._restore,
                                         site="graph.mixed_rounds")
        shared = _GraphMegaFetch(self, oks, stats)
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
        """Device-side count of refreshes from scratch (the regression
        hook of the merge and repair paths: inserts and deletes that keep
        within the caps must not bump it)."""
        return self.refresh_counts()["scratch"]

    def refresh_counts(self) -> Dict[str, int]:
        """The device's refresh counters (test/debug; one fetch): fused
        reads by branch, repairs that overflowed to scratch, and the
        steps of the last repair walk or scratch fixpoint."""
        stats = np.asarray(_host_fetch(self.state.stats)).tolist()
        return dict(zip(BRANCHES, stats), overflow=stats[OVERFLOW],
                    steps=stats[ITERS])

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
