"""The plain dynamic-connectivity reference (``core/seq_graph.py``) and the
device graph held to it on Graph500 Kronecker graphs.

* ``SeqGraph`` against scipy's connected components, recomputed after
  every batch, at scales 8-10;
* ``DeviceGraph`` through ``StructureExecutor`` against ``SeqGraph`` at
  scale 10 under the benchmark's read-dominated mix and a delete-heavy
  one, every answer and the final edge set compared; forest-edge
  deletes, repairs past their caps and the first reads after a bulk load;
* the stacked and the mesh layout give equal answers on a fake 4-device
  CPU mesh (in a subprocess, which can set the device count).
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
import scipy.sparse as sp
from scipy.sparse.csgraph import connected_components

from repro.core import substrate
from repro.core.device_graph import DeviceGraph
from repro.core.seq_graph import SeqGraph, canonical_edges, kronecker_edges
from repro.launch.serve import StructureExecutor

substrate.load_builtins()
ROOT = Path(__file__).resolve().parents[1]
MIXES = {"r90": {"connected": 0.9, "insert": 0.05, "delete": 0.05},
         "delete_heavy": {"connected": 0.4, "insert": 0.2, "delete": 0.4}}


def kron_graph(scale, seed):
    """A Kronecker edge list and a pool of fresh edges under one vertex
    permutation, as the benchmark's preload draws them."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(1 << scale)
    return (kronecker_edges(scale, 16 << scale, rng, perm),
            kronecker_edges(scale, 1 << scale, rng, perm))


def requests(rng, k, mix, distinct, fresh, verts):
    """``k`` operations of ``mix``: reads over the vertices of degree >= 1,
    inserts from the fresh pool, deletes of initial edges."""
    names = sorted(mix)
    kinds = rng.choice(len(names), k, p=[mix[m] for m in names])
    out = []
    for kind in kinds:
        m = names[kind]
        if m == "connected":
            uv = tuple(int(x) for x in rng.choice(verts, 2))
        else:
            pool = fresh if m == "insert" else distinct
            uv = tuple(int(x) for x in pool[rng.integers(len(pool))])
        out.append((m, uv))
    return out


def same_partition(a, b):
    """Two labelings name the same partition."""
    _, fa = np.unique(a, return_inverse=True)
    _, fb = np.unique(b, return_inverse=True)
    first_a = np.full(fa.max() + 1, -1)
    first_b = np.full(fb.max() + 1, -1)
    idx = np.arange(len(a))
    first_a[fa[::-1]] = idx[::-1]
    first_b[fb[::-1]] = idx[::-1]
    return np.array_equal(first_a[fa], first_b[fb])


def scipy_labels(n, edges):
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    adj = sp.coo_matrix((np.ones(len(e)), (e[:, 0], e[:, 1])),
                        shape=(n, n))
    return connected_components(adj, directed=False)[1]


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scale", [8, 9, 10])
def test_seq_graph_matches_scipy_after_every_batch(scale):
    n = 1 << scale
    edges, fresh = kron_graph(scale, 100 + scale)
    distinct = canonical_edges(edges, n)
    verts = np.flatnonzero(np.bincount(distinct.ravel(), minlength=n))
    g = SeqGraph(n, edges)
    live = set(map(tuple, distinct.tolist()))
    assert same_partition(g.comp, scipy_labels(n, distinct))
    rng = np.random.default_rng(scale)
    mix = {"connected": 0.3, "insert": 0.3, "delete": 0.4}
    for _ in range(25):
        ops = requests(rng, 32, mix, distinct, fresh, verts)
        got = g.step([m for m, _ in ops], [uv for _, uv in ops])
        for (m, (u, v)), a in zip(ops, got):    # updates in arrival order
            e = (min(u, v), max(u, v))
            if m == "insert":
                assert a == (u != v and e not in live)
                if u != v:
                    live.add(e)
            elif m == "delete":
                assert a == (e in live)
                live.discard(e)
        want = scipy_labels(n, sorted(live))
        assert same_partition(g.comp, want)
        for (m, (u, v)), a in zip(ops, got):    # then the reads
            if m == "connected":
                assert a == bool(want[u] == want[v])
        np.testing.assert_array_equal(g.edges(), np.asarray(
            sorted(live), np.int64).reshape(-1, 2))


# ---------------------------------------------------------------------------
# the device graph against the reference
# ---------------------------------------------------------------------------
def serve_and_compare(scale, mix, seed, batches=30, width=64):
    """Batches of ``mix`` through ``StructureExecutor`` (updates then
    reads, the benchmark's path) and through ``SeqGraph``; every answer
    and the final edge set must agree.  Returns the graph."""
    n = 1 << scale
    edges, fresh = kron_graph(scale, seed)
    distinct = canonical_edges(edges, n)
    verts = np.flatnonzero(np.bincount(distinct.ravel(), minlength=n))
    ex = StructureExecutor(substrate.get("graph"), n=n,
                           edge_capacity=1 << (scale + 4), c_max=64,
                           n_shards=4, edges=edges)
    ref = SeqGraph(n, edges)
    rng = np.random.default_rng(seed + 1)
    for _ in range(batches):
        ops = requests(rng, width, mix, distinct, fresh, verts)
        got = ex([{"method": m, "input": uv} for m, uv in ops])
        want = ref.step([m for m, _ in ops], [uv for _, uv in ops])
        assert got == want
    np.testing.assert_array_equal(
        canonical_edges(sorted(ex.ds.edges()), n), ref.edges())
    return ex.ds


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_device_graph_matches_seq_graph(mix):
    g = serve_and_compare(10, MIXES[mix], seed=7)
    counts = g.refresh_counts()
    assert counts["scratch"] == 1 + counts["overflow"]   # the bulk load
    if mix == "delete_heavy":
        assert counts["repair"] > 0


def test_forest_edge_deletes_are_repaired():
    """Deleting forest edges (read off the device's forest bits) repairs
    the pieces they split; every answer matches the reference."""
    scale, n = 10, 1 << 10
    edges, _fresh = kron_graph(scale, 21)
    g = DeviceGraph(n, edge_capacity=1 << 14, c_max=64, edges=edges)
    ref = SeqGraph(n, edges)
    rng = np.random.default_rng(22)
    pairs = [tuple(int(x) for x in rng.integers(0, n, 2))
             for _ in range(64)]
    assert g.connected_batch(pairs) == ref.step(["connected"] * 64, pairs)
    for _ in range(8):
        in_f = np.asarray(g.state.in_f)
        eu, ev = np.asarray(g.state.eu), np.asarray(g.state.ev)
        slots = rng.choice(np.flatnonzero(in_f), 3, replace=False)
        dels = [(int(eu[s]), int(ev[s])) for s in slots]
        assert g.delete_batch(dels) == [True] * 3
        ref.step(["delete"] * 3, dels)
        pairs = [tuple(int(x) for x in rng.integers(0, n, 2))
                 for _ in range(64)]
        assert g.connected_batch(pairs) == ref.step(["connected"] * 64,
                                                    pairs)
    counts = g.refresh_counts()
    assert counts["repair"] + counts["overflow"] == 8
    assert counts["scratch"] == 1 + counts["overflow"]


def _path(n):
    return [(i, i + 1) for i in range(n - 1)]


def _double_star(k):
    """Vertex 0 joined to leaves 2..k+1, hub 1 joined to every leaf: the
    forest hangs the hub under one leaf, so cutting that edge leaves one
    vertex with k - 1 edges out of it."""
    return [(0, i) for i in range(2, k + 2)] + [(1, i)
                                                for i in range(2, k + 2)]


@pytest.mark.parametrize("case", ["moved_cap", "cross_cap"])
def test_repair_past_a_cap_runs_from_scratch(case):
    """A cut whose subtree passes MOVED_CAP vertices, or whose subtree
    has more than CROSS_CAP edges leaving it, overflows to the scratch
    fixpoint, and the answers stay exact."""
    from repro.core import device_graph as dg

    if case == "moved_cap":
        n, edges = 200, _path(200)
        cut = (0, 1)                     # 198 vertices below the cut
    else:
        k = dg.CROSS_CAP + 8
        n, edges = k + 2, _double_star(k)
        cut = None
    g = DeviceGraph(n, edge_capacity=2 * len(edges), c_max=8, edges=edges)
    ref = SeqGraph(n, edges)
    assert g.connected(0, n - 1) is True
    if cut is None:                      # the hub's forest edge
        par, pslot = np.asarray(g.state.par), np.asarray(g.state.pslot)
        assert pslot[1] < g.capacity
        cut = (min(1, int(par[1])), max(1, int(par[1])))
    assert g.delete(*cut) is True
    ref.delete(*cut)
    pairs = [(0, n - 1), (0, 1), (1, n - 1), (2, n - 2)]
    assert g.connected_batch(pairs) == ref.step(["connected"] * 4, pairs)
    counts = g.refresh_counts()
    assert counts["overflow"] == 1 and counts["repair"] == 0
    assert counts["scratch"] == 2


def test_bulk_load_reads_see_the_loaded_components():
    """The first reads after ``DeviceGraph(edges=...)`` are read-only
    batches; they must answer from the loaded graph, not from the
    identity labels a fresh state starts with."""
    from repro.core.tracing import TRACER

    scale, n = 9, 1 << 9
    edges, _fresh = kron_graph(scale, 31)
    g = DeviceGraph(n, edge_capacity=1 << 13, c_max=64, edges=edges)
    assert g._maybe_stale is True
    ref = SeqGraph(n, edges)
    rng = np.random.default_rng(32)
    TRACER.start()
    try:
        for _ in range(3):
            pairs = [tuple(int(x) for x in rng.integers(0, n, 2))
                     for _ in range(40)]
            assert g.read_batch(["connected"] * 40, pairs) == ref.step(
                ["connected"] * 40, pairs)
    finally:
        counters = TRACER.stop()["counters"]
    assert counters["graph.refresh.scratch"] == 1
    assert counters["graph.refresh.lean"] == 2


# ---------------------------------------------------------------------------
# stacked and mesh layouts
# ---------------------------------------------------------------------------
MESH_SCRIPT = r"""
import numpy as np
from repro.core import placement, substrate
from repro.core.device_graph import DeviceGraph
from repro.core.seq_graph import SeqGraph, canonical_edges, kronecker_edges
from repro.launch.mesh import make_combining_mesh
import jax
assert len(jax.devices()) == 4, jax.devices()
n, scale = 1 << 9, 9
rng = np.random.default_rng(41)
perm = rng.permutation(n)
edges = kronecker_edges(scale, 16 * n, rng, perm)
fresh = kronecker_edges(scale, n, rng, perm)
distinct = canonical_edges(edges, n)
mesh = placement.MeshPlacement(make_combining_mesh(4))
graphs = [DeviceGraph(n, edge_capacity=1 << 13, c_max=64, edges=edges),
          DeviceGraph(n, edge_capacity=1 << 13, c_max=64, edges=edges,
                      placement=mesh)]
ref = SeqGraph(n, edges)
for step in range(20):
    k = 24
    kinds = rng.choice(3, k, p=[0.4, 0.2, 0.4])
    ops = []
    for kind in kinds:
        if kind == 0:
            uv = tuple(int(x) for x in rng.integers(0, n, 2))
            ops.append(("connected", uv))
        else:
            pool = fresh if kind == 1 else distinct
            m = "insert" if kind == 1 else "delete"
            uv = tuple(int(x) for x in pool[rng.integers(len(pool))])
            ops.append((m, uv))
    want = ref.step([m for m, _ in ops], [uv for _, uv in ops])
    upd = [(m, uv) for m, uv in ops if m != "connected"]
    rd = [uv for m, uv in ops if m == "connected"]
    for g in graphs:
        if step % 2:
            hs = g.mixed_rounds([("update", [m for m, _ in upd],
                                  [uv for _, uv in upd]),
                                 ("read", ["connected"] * len(rd), rd)])
            got_u, got_r = hs[0].result(), hs[1].result()
        else:
            got_u = g.update_batch([m for m, _ in upd], [uv for _, uv in upd])
            got_r = g.connected_batch(rd)
        it_u, it_r = iter(got_u), iter(got_r)
        got = [next(it_r) if m == "connected" else next(it_u) for m, _ in ops]
        assert got == want, (step, got, want)
for a, b in zip(jax.tree_util.tree_leaves(graphs[0].state),
                jax.tree_util.tree_leaves(graphs[1].state)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
c = graphs[1].refresh_counts()
assert c["repair"] > 0 and c["scratch"] >= 1, c
print("MESH_OK", c)
"""


def test_stacked_and_mesh_layouts_answer_alike():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "MESH_OK" in proc.stdout
