"""Device-resident batched dynamic graph (DESIGN.md §11).

Deterministic differential fuzz (tier-1: shared harness, no hypothesis),
the union-find fast-path regression, the zero-copy donation contract, the
one-blocking-fetch contract, and the lazy-refresh staleness fixes.
"""
import numpy as np
import pytest

from conformance import run_differential
from differential import BFSOracle

import repro.core.device_graph as dg
from repro.core import substrate
from repro.core.device_graph import DeviceGraph
from repro.core.read_opt import batched_read_optimized

substrate.load_builtins()
_SPEC = substrate.get("graph")


def _mk(n=30, **kw):
    kw.setdefault("edge_capacity", 512)
    kw.setdefault("c_max", 8)
    return DeviceGraph(n, **kw)


def _fuzz(g, rng, steps, *, n):
    """Kit-driven differential fuzz vs the independent BFS oracle."""
    run_differential(g, BFSOracle(n), _SPEC, rng, steps, ctx={"n": n})


# ---------------------------------------------------------------------------
# differential fuzz vs the BFS oracle (conformance kit)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_device_graph_vs_bfs_oracle(n_shards):
    rng = np.random.default_rng(100 + n_shards)
    g = _mk(n_shards=n_shards)
    _fuzz(g, rng, steps=40, n=30)
    # host mirrors stayed exact
    assert len(g) == len(g.edges())


def test_device_graph_fuzz_pallas_path():
    """Full rebuilds through the grid=(K,) kernel (interpret mode on CPU
    CI) must be observationally identical."""
    rng = np.random.default_rng(7)
    g = _mk(n=20, n_shards=4, use_pallas=True)
    _fuzz(g, rng, steps=25, n=20)


def test_device_graph_fuzz_nodonate_ablation():
    rng = np.random.default_rng(11)
    g = _mk(donate=False)
    _fuzz(g, rng, steps=30, n=30)


def test_device_and_host_graph_agree():
    """Same op stream through both tiers — identical results."""
    from repro.core.dynamic_graph import DynamicGraph

    rng = np.random.default_rng(3)
    n = 25
    g_dev = _mk(n=n, n_shards=2)
    g_host = DynamicGraph(n)
    for _ in range(120):
        u, v = int(rng.integers(0, n)), int(rng.integers(0, n))
        m = ("insert", "delete", "connected")[int(rng.integers(0, 3))]
        assert g_dev.apply(m, (u, v)) == g_host.apply(m, (u, v)), (m, u, v)


# ---------------------------------------------------------------------------
# mixed-batch chain semantics (the fused update pass)
# ---------------------------------------------------------------------------
def test_mixed_batch_chains_and_duplicates():
    """Duplicate edges inside ONE batch resolve in arrival order, incl.
    delete-reinsert cycles and transient insert+delete pairs."""
    g = _mk()
    e = (3, 4)
    # chain on an absent edge: ins(T), ins(F), del(T), ins(T), del(T), del(F)
    got = g.update_batch(["insert", "insert", "delete", "insert",
                          "delete", "delete"], [e] * 6)
    assert got == [True, False, True, True, True, False]
    assert g.edges() == set()            # transient: buffer never touched
    assert g.connected(3, 4) is False
    # chain on a present edge
    assert g.insert(3, 4) is True
    got = g.update_batch(["delete", "insert"], [e, e])
    assert got == [True, True]
    assert g.connected(3, 4) is True
    assert g.edges() == {(3, 4)}


def test_self_loop_updates_report_false():
    g = _mk()
    assert g.insert(5, 5) is False
    assert g.delete(5, 5) is False
    assert g.connected(5, 5) is True     # reflexive, without an edge
    assert len(g) == 0


def test_batches_larger_than_c_max_slice():
    g = _mk(n=40, c_max=4)
    edges = [(i, i + 1) for i in range(20)]
    assert g.insert_batch(edges) == [True] * 20
    assert g.insert_batch(edges) == [False] * 20     # dedups across slices
    assert g.connected(0, 20) is True
    assert g.delete_batch(edges[:10]) == [True] * 10
    assert g.connected(0, 20) is False
    assert len(g) == 10


def test_empty_batches():
    g = _mk()
    assert g.update_batch([], []) == []
    assert g.read_batch([], []) == []
    assert g.insert_batch([]) == []
    assert len(g) == 0


def test_vertex_range_validated():
    g = _mk(n=10)
    with pytest.raises(ValueError, match="range"):
        g.insert(0, 10)
    with pytest.raises(ValueError, match="range"):
        g.connected(-1, 3)


def test_edge_capacity_overflow_rejected():
    g = DeviceGraph(64, edge_capacity=8, c_max=8)
    with pytest.raises(ValueError, match="capacity"):
        for i in range(5):
            g.insert_batch([(4 * i, 4 * i + 1), (4 * i + 2, 4 * i + 3)])
    # the graph stays coherent after the refusal
    assert len(g) == len(g.edges())


def test_capacity_refusal_is_atomic():
    """A refused multi-slice batch must not dispatch ANY slice: the
    buffer, the mirrors and the staleness flag are untouched, and the
    graph keeps working afterwards."""
    g = DeviceGraph(64, edge_capacity=20, c_max=8)
    with pytest.raises(ValueError, match="capacity"):
        g.insert_batch([(i, i + 1) for i in range(25)])   # 4 slices
    assert len(g) == 0 and g.edges() == set()
    assert g._outstanding_ins == 0 and g._maybe_stale is False
    assert g.connected(0, 1) is False
    assert g.insert_batch([(i, i + 1) for i in range(10)]) == [True] * 10
    assert g.connected(0, 10) is True


# ---------------------------------------------------------------------------
# union-find fast path (satellite: counting regression)
# ---------------------------------------------------------------------------
def test_insert_only_batches_take_union_find_fast_path():
    """Insert-only traffic must NOT trigger label-prop rebuilds — the
    device-side counters (the fused-pass analogue of the
    ``_host_fetch`` counting hook) show merges only; a delete outside the
    forest changes nothing, and a netted-out forest edge is repaired
    exactly once, without a rebuild from scratch."""
    g = _mk(n=50)
    base = g.full_rebuilds()
    for i in range(6):
        g.insert_batch([(i, i + 1), (i + 10, i + 11)])
        assert g.connected(0, i + 1) is True
    assert g.full_rebuilds() == base     # merges only — the fast path
    assert g.refresh_counts()["merge"] == 6
    # a FAILED delete must not invalidate either
    assert g.delete(40, 41) is False
    assert g.connected(0, 1) is True
    assert g.full_rebuilds() == base
    # a cycle edge is no forest edge: deleting it repairs nothing
    assert g.insert(0, 2) is True
    assert g.connected(0, 2) is True
    assert g.delete(0, 2) is True
    assert g.connected(0, 2) is True
    assert g.refresh_counts()["repair"] == 0
    # one successful forest-edge delete → exactly one repair
    assert g.delete(2, 3) is True
    assert g.connected(0, 2) is True
    assert g.connected(0, 3) is False
    counts = g.refresh_counts()
    assert counts["repair"] == 1 and counts["overflow"] == 0
    assert g.full_rebuilds() == base


def test_fast_path_labels_equal_full_rebuild_labels():
    """The incremental merges give the same partition as a from-scratch
    rebuild, with canonical labels: each vertex's label is the root of
    its forest tree, and the forest edges span the components."""
    from repro.kernels.label_prop.ref import components_reference

    rng = np.random.default_rng(5)
    g = _mk(n=40)
    for _ in range(10):                  # interleave inserts and reads so
        edges = [(int(rng.integers(0, 40)), int(rng.integers(0, 40)))
                 for _ in range(4)]
        g.insert_batch(edges)
        g.connected(0, 1)                # merges happen incrementally
    want = components_reference(40, g.edges())
    labels = np.asarray(g.state.labels)
    par = np.asarray(g.state.par)
    for x in range(40):                  # same partition, root labels
        r = x
        while par[r] != r:
            r = par[r]
        assert labels[x] == r
        assert (labels == labels[x]).tolist() == (want == want[x]).tolist()
    eu, ev = np.asarray(g.state.eu), np.asarray(g.state.ev)
    in_f, valid = np.asarray(g.state.in_f), np.asarray(g.state.valid)
    assert np.all(valid[in_f])
    assert in_f.sum() == 40 - len(np.unique(want))


def test_pending_overflow_falls_back_to_full_rebuild():
    """More pending inserts than the device pending buffer holds → the
    update pass raises dirty_full instead of dropping merges."""
    g = DeviceGraph(64, edge_capacity=512, c_max=4)   # pend_cap = 8
    base = g.full_rebuilds()
    g.insert_batch([(i, i + 1) for i in range(20)])   # 20 > pend_cap
    assert g.connected(0, 20) is True
    assert g.full_rebuilds() == base + 1


# ---------------------------------------------------------------------------
# zero-copy donation contract (DESIGN.md §10/§11)
# ---------------------------------------------------------------------------
def test_update_pass_donates_and_invalidates_buffers():
    import jax.numpy as jnp

    g = _mk()
    buv = jnp.zeros((2, 8), jnp.int32)
    sel = jnp.zeros((8,), jnp.bool_)
    lowered = dg.update_pass.lower(g.state, buv, sel, jnp.int32(0))
    assert "tf.aliasing_output" in lowered.as_text()
    old = g.state
    g.insert(1, 2)
    assert old.eu.is_deleted() and old.valid.is_deleted()
    # the undonated ablation twin must NOT alias
    g2 = _mk(donate=False)
    lowered2 = dg.update_pass_undonated.lower(g2.state, buv, sel,
                                              jnp.int32(0))
    assert "tf.aliasing_output" not in lowered2.as_text()
    old2 = g2.state
    g2.insert(1, 2)
    assert not old2.eu.is_deleted()


def test_read_pass_donates_state():
    import jax.numpy as jnp

    g = _mk()
    g.insert(1, 2)
    old = g.state
    lowered = dg.read_pass.lower(old, jnp.zeros((2, 1), jnp.int32),
                                 n=g.n, e_bound=1, n_shards=1,
                                 use_pallas=False)
    assert "tf.aliasing_output" in lowered.as_text()
    assert g.connected(1, 2) is True     # fused refresh+read consumed it
    assert old.labels.is_deleted()


# ---------------------------------------------------------------------------
# sync-free contract: masks ride the read fetch (DESIGN.md §10 idiom)
# ---------------------------------------------------------------------------
def test_one_blocking_fetch_per_update_read_pass(monkeypatch):
    """An update batch published via ``update_batch_async`` performs NO
    blocking transfer; the next read's single fetch resolves it (same
    counting idiom as the PQ's ``_host_fetch`` test)."""
    fetches = []
    real_fetch = dg._host_fetch

    def counting_fetch(tree):
        fetches.append(1)
        return real_fetch(tree)

    monkeypatch.setattr(dg, "_host_fetch", counting_fetch)
    g = _mk(n=30)
    g.insert_batch([(i, i + 1) for i in range(6)])
    g.connected(0, 3)
    fetches.clear()
    h = g.update_batch_async(["insert", "delete", "insert"],
                             [(10, 11), (0, 1), (20, 21)])
    assert fetches == []                 # publication is sync-free
    got = g.read_batch(["connected"] * 2, [(10, 11), (0, 1)])
    assert fetches == [1]                # ONE fetch: answers + masks
    assert got == [True, False]
    assert h.result() == [True, True, True]
    assert fetches == [1]                # result() was already resolved
    # a clean read (labels current, nothing outstanding) is also one fetch
    fetches.clear()
    assert g.connected(10, 11) is True
    assert fetches == [1]


# ---------------------------------------------------------------------------
# lazy-but-correct refresh on the read path (satellite fix)
# ---------------------------------------------------------------------------
def test_direct_insert_then_connected_sees_edge_immediately():
    """insert/delete return before any refresh — the read path must still
    observe them (the return-before-refresh staleness fix)."""
    g = _mk(n=20)
    assert g.connected(3, 7) is False    # forces a (cached) label read
    assert g.insert(3, 7) is True
    assert g.connected(3, 7) is True     # no explicit refresh call
    assert g.delete(3, 7) is True
    assert g.connected(3, 7) is False


def test_reentrant_update_during_read_is_not_lost():
    """An update landing between the read dispatch and the flag
    bookkeeping must re-mark the labels stale (the clear-BEFORE-build
    ordering; cf. the DynamicGraph regression in test_core_apps.py)."""
    g = _mk(n=20)
    g.insert(1, 2)
    assert g.connected(1, 2) is True
    # simulate: an update slips in right after a read pass resolved
    g.insert(2, 3)
    assert g._maybe_stale is True
    assert g.connected(1, 3) is True


def test_combiner_integration_update_results_delivered():
    g = _mk(n=30)
    eng = batched_read_optimized(g)
    assert eng.execute("insert", (4, 5)) is True
    assert eng.execute("insert", (4, 5)) is False
    assert eng.execute("connected", (4, 5)) is True
    assert eng.execute("delete", (4, 5)) is True
    assert eng.execute("connected", (4, 5)) is False


def test_oracle_harness_self_check():
    """The shared oracle itself honors the engine result contract."""
    o = BFSOracle(5)
    assert o.insert(0, 1) and not o.insert(1, 0)
    assert o.connected(0, 1) and not o.connected(0, 2)
    assert o.delete(0, 1) and not o.delete(0, 1)
