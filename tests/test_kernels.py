"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs ref.py."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.heap_insert import insert_chunk, insert_chunk_sharded
from repro.kernels.heap_insert.ref import (check_heap_property,
                                           insert_chunk_reference,
                                           insert_chunk_sequential)
from repro.kernels.heap_kmin import k_smallest, k_smallest_sharded
from repro.kernels.heap_kmin.ref import k_smallest_reference
from repro.kernels.heap_sift import sift_wavefront, sift_wavefront_sharded
from repro.kernels.heap_sift.ref import sift_wavefront_reference
from repro.kernels.label_prop import (connected_components, label_step,
                                      label_step_xla, spanning_forest)
from repro.kernels.label_prop.ref import (components_reference,
                                          label_step_reference)
from repro.kernels.linear_scan import rglru_scan, rwkv6_scan
from repro.kernels.linear_scan.ref import rglru_reference, rwkv6_reference
from repro.kernels.sorted_merge import (merge_compact,
                                        merge_compact_sharded,
                                        merge_edits_xla)
from repro.kernels.sorted_merge.ref import merge_compact_reference


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
ATTN_CASES = [
    # B, Sq, Skv, H, K, hd, causal, window, cap, dtype
    (2, 128, 128, 4, 2, 64, True, 0, 0.0, jnp.float32),
    (1, 64, 64, 4, 4, 32, True, 0, 50.0, jnp.float32),
    (2, 64, 256, 8, 2, 64, False, 0, 0.0, jnp.float32),
    (1, 256, 256, 4, 1, 64, True, 64, 0.0, jnp.float32),
    (1, 96, 96, 2, 2, 16, True, 32, 30.0, jnp.float32),   # ragged blocks
    (2, 128, 128, 4, 2, 64, True, 0, 0.0, jnp.bfloat16),
    (1, 33, 65, 2, 1, 8, True, 0, 0.0, jnp.float32),      # odd sizes → pad
]


@pytest.mark.parametrize(
    "B,Sq,Skv,H,K,hd,causal,window,cap,dtype", ATTN_CASES)
def test_flash_attention_matches_ref(B, Sq, Skv, H, K, hd, causal, window,
                                     cap, dtype, rng):
    q = jnp.asarray(rng.standard_normal((B, Sq, H, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((B, Skv, K, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((B, Skv, K, hd)), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window, cap=cap,
                          block_q=32, block_k=32)
    want = attention_reference(q, k, v, causal=causal, window=window,
                               cap=cap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_q_offset(rng):
    """Continuation semantics: q_offset shifts the causal diagonal."""
    B, S, H, hd = 1, 32, 2, 16
    q = jnp.asarray(rng.standard_normal((B, 8, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, q_offset=24,
                          block_q=8, block_k=8)
    want = attention_reference(q, k, v, causal=True, q_offset=24)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# linear scans
# ---------------------------------------------------------------------------
RWKV_CASES = [(2, 128, 2, 16, 32), (1, 100, 3, 32, 64), (2, 64, 1, 8, 64),
              (1, 256, 2, 16, 16)]


@pytest.mark.parametrize("B,S,H,hd,chunk", RWKV_CASES)
def test_rwkv6_scan_matches_ref(B, S, H, hd, chunk, rng):
    r, k, v = (jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
               for _ in range(3))
    logw = -jnp.exp(jnp.asarray(rng.uniform(-3.0, 0.5, (B, S, H, hd)),
                                jnp.float32))
    w = jnp.exp(logw)
    u = jnp.asarray(rng.standard_normal((H, hd)), jnp.float32)
    s0 = jnp.asarray(rng.standard_normal((B, H, hd, hd)), jnp.float32)
    y, sT = rwkv6_scan(r, k, v, w, u, s0, chunk=chunk)
    yr, sr = rwkv6_reference(r, k, v, w, u, s0)
    scale = float(jnp.max(jnp.abs(yr))) + 1e-9
    assert float(jnp.max(jnp.abs(y - yr))) / scale < 1e-4
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sr),
                               atol=1e-3, rtol=1e-4)


def test_rwkv6_strong_decay_domain(rng):
    """Decays at the stiff end of the validity domain (|log w| ≈ 1)."""
    B, S, H, hd = 1, 64, 2, 16
    r, k, v = (jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
               for _ in range(3))
    w = jnp.full((B, S, H, hd), math.exp(-1.0), jnp.float32)
    u = jnp.zeros((H, hd), jnp.float32)
    s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    y, sT = rwkv6_scan(r, k, v, w, u, s0, chunk=32)
    yr, sr = rwkv6_reference(r, k, v, w, u, s0)
    scale = float(jnp.max(jnp.abs(yr))) + 1e-9
    assert float(jnp.max(jnp.abs(y - yr))) / scale < 1e-4


@pytest.mark.parametrize("B,S,R,chunk", [(2, 128, 64, 32), (1, 100, 48, 256),
                                         (3, 64, 16, 16)])
def test_rglru_scan_exact(B, S, R, chunk, rng):
    a = jnp.asarray(rng.uniform(0.2, 1.0, (B, S, R)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((B, S, R)), jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((B, R)), jnp.float32)
    hs, hT = rglru_scan(a, b, h0, chunk=chunk)
    hr, hTr = rglru_reference(a, b, h0)
    # in-kernel fori matches the sequential scan bit-for-bit
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hTr), atol=1e-5)


# ---------------------------------------------------------------------------
# heap kernels (paper §4 phases)
# ---------------------------------------------------------------------------
def _random_heap(rng, n, cap):
    vals = np.sort(rng.uniform(0, 100, n).astype(np.float32))
    a = np.full(cap, np.inf, np.float32)
    a[1:n + 1] = vals
    return a


@pytest.mark.parametrize("trial", range(10))
def test_heap_sift_matches_se_order(trial):
    rng = np.random.default_rng(100 + trial)
    n = int(rng.integers(8, 200))
    cap = 256
    a = _random_heap(rng, n, cap)
    c = int(rng.integers(1, 9))
    starts_set = sorted(rng.choice(np.arange(1, n + 1),
                                   size=min(c, n), replace=False).tolist())
    starts = np.zeros(8, np.int32)
    active = np.zeros(8, np.int32)
    for i, s in enumerate(starts_set):
        a[s] = rng.uniform(0, 150)      # perturb upward → sift needed
        starts[i] = s
        active[i] = 1
    want = sift_wavefront_reference(a, n, starts, active)
    got = np.asarray(sift_wavefront(jnp.asarray(a), jnp.int32(n),
                                    jnp.asarray(starts), jnp.asarray(active)))
    np.testing.assert_array_equal(got, want)


def test_heap_sift_noop_when_inactive():
    a = _random_heap(np.random.default_rng(0), 20, 64)
    got = np.asarray(sift_wavefront(
        jnp.asarray(a), jnp.int32(20),
        jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int32)))
    np.testing.assert_array_equal(got, a)


@pytest.mark.parametrize("trial", range(10))
def test_heap_insert_matches_parallel_ref(trial):
    rng = np.random.default_rng(200 + trial)
    n = int(rng.integers(0, 120))
    cap = 512
    a = _random_heap(rng, n, cap)
    lo = n + 1
    level_end = (2 << int(math.floor(math.log2(lo)))) - 1
    m = int(rng.integers(1, min(8, level_end - lo + 1) + 1))
    ins = np.sort(rng.uniform(0, 100, m).astype(np.float32))
    C = 8
    cv = np.full(C, np.inf, np.float32)
    cv[:m] = ins
    got, new_sz = insert_chunk(jnp.asarray(a), jnp.int32(n),
                               jnp.asarray(cv), jnp.int32(m))
    got = np.asarray(got)
    want, _ = insert_chunk_reference(a, n, cv, m, c_max=C, max_depth=10)
    np.testing.assert_array_equal(got, np.asarray(want))
    # Thm-2 semantics vs the sequential oracle
    seq_a, seq_n = insert_chunk_sequential(a, n, ins)
    np.testing.assert_allclose(np.sort(got[1:n + m + 1]),
                               np.sort(seq_a[1:seq_n + 1]))
    assert check_heap_property(got, n + m)
    assert int(new_sz) == n + m


# ---------------------------------------------------------------------------
# shard-grid dispatch (DESIGN.md §10): grid=(K,) — one program per heap shard
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("trial", range(4))
def test_heap_sift_sharded_matches_per_shard_reference(trial):
    rng = np.random.default_rng(300 + trial)
    K, cap, c = 3, 256, 8
    A = np.stack([_random_heap(rng, int(rng.integers(16, 200)), cap)
                  for _ in range(K)])
    sizes = np.asarray([np.isfinite(A[k, 1:]).sum() for k in range(K)],
                       np.int32)
    starts = np.zeros((K, c), np.int32)
    active = np.zeros((K, c), np.int32)
    wants = []
    for k in range(K):
        ss = sorted(rng.choice(np.arange(1, sizes[k] + 1), size=3,
                               replace=False).tolist())
        for i, s in enumerate(ss):
            A[k, s] = rng.uniform(0, 150)
            starts[k, i] = s
            active[k, i] = 1
        wants.append(sift_wavefront_reference(A[k], sizes[k], starts[k],
                                              active[k]))
    got = np.asarray(sift_wavefront_sharded(
        jnp.asarray(A), jnp.asarray(sizes), jnp.asarray(starts),
        jnp.asarray(active)))
    np.testing.assert_array_equal(got, np.stack(wants))


def test_heap_insert_sharded_ragged_chunks():
    """Per-shard chunk sizes differ (one shard empty this level) — the
    shard-grid kernel handles the ragged case fully predicated."""
    rng = np.random.default_rng(7)
    K, cap, C = 3, 512, 8
    sizes = np.asarray([20, 27, 34], np.int32)
    A = np.stack([_random_heap(rng, int(s), cap) for s in sizes])
    ms = np.asarray([3, 0, 5], np.int32)
    CV = np.full((K, C), np.inf, np.float32)
    wants = []
    for k in range(K):
        if ms[k]:
            lo = int(sizes[k]) + 1
            level_end = (2 << int(math.floor(math.log2(lo)))) - 1
            ms[k] = min(int(ms[k]), level_end - lo + 1)
            CV[k, :ms[k]] = np.sort(
                rng.uniform(0, 100, ms[k]).astype(np.float32))
        w, _ = insert_chunk_reference(A[k], sizes[k], CV[k], ms[k],
                                      c_max=C, max_depth=10)
        wants.append(np.asarray(w))
    got, new_sz = insert_chunk_sharded(
        jnp.asarray(A), jnp.asarray(sizes), jnp.asarray(CV),
        jnp.asarray(ms))
    np.testing.assert_array_equal(np.asarray(got), np.stack(wants))
    np.testing.assert_array_equal(np.asarray(new_sz), sizes + ms)


@pytest.mark.parametrize("trial", range(6))
def test_heap_kmin_matches_xla_and_reference(trial):
    """The fused frontier-search kernel must agree ELEMENT-WISE with the
    XLA scan twin (prefix-stability is load-bearing for the sharded
    candidate merge) and the numpy oracle."""
    from repro.core.batched_pq import _k_smallest

    rng = np.random.default_rng(400 + trial)
    n = int(rng.integers(0, 150))
    cap, c_max = 256, 8
    a = _random_heap(rng, n, cap)
    ne = int(rng.integers(0, c_max + 1))
    ids_r, vals_r = k_smallest_reference(a, n, ne, c_max)
    ids_x, vals_x = _k_smallest(jnp.asarray(a), jnp.int32(n),
                                jnp.int32(ne), c_max)
    ids_k, vals_k = k_smallest(jnp.asarray(a), jnp.int32(n),
                               jnp.int32(ne), c_max=c_max)
    np.testing.assert_array_equal(np.asarray(ids_x), ids_r)
    np.testing.assert_array_equal(np.asarray(vals_x), vals_r)
    np.testing.assert_array_equal(np.asarray(ids_k), ids_r)
    np.testing.assert_array_equal(np.asarray(vals_k), vals_r)


def test_heap_kmin_sharded_per_shard_search():
    rng = np.random.default_rng(11)
    K, cap, c_max = 4, 256, 8
    sizes = np.asarray([30, 0, 40, 5], np.int32)   # one empty shard
    A = np.stack([_random_heap(rng, int(s), cap) for s in sizes])
    ids, vals = k_smallest_sharded(jnp.asarray(A), jnp.asarray(sizes),
                                   jnp.int32(5), c_max=c_max)
    for k in range(K):
        ir, vr = k_smallest_reference(A[k], sizes[k], 5, c_max)
        np.testing.assert_array_equal(np.asarray(ids)[k], ir)
        np.testing.assert_array_equal(np.asarray(vals)[k], vr)


# ---------------------------------------------------------------------------
# label propagation (dynamic graph, DESIGN.md §11): grid=(K,) vertex shards
# ---------------------------------------------------------------------------
def _random_edges(rng, n, e):
    return (rng.integers(0, n, e).astype(np.int32),
            rng.integers(0, n, e).astype(np.int32))


@pytest.mark.parametrize("n_shards", [1, 3, 4, 5, 8])
@pytest.mark.parametrize("trial", range(3))
def test_label_step_kernel_bit_exact_across_shard_counts(n_shards, trial):
    """One scatter-min + pointer-jump iteration: the grid=(K,) kernel,
    the XLA twin and the numpy oracle agree ELEMENT-WISE for every K —
    ragged vertex partitions (n not divisible by K) included."""
    rng = np.random.default_rng(500 + trial)
    n = int(rng.integers(5, 80))                   # rarely divisible by K
    e = int(rng.integers(1, 120))
    eu, ev = _random_edges(rng, n, e)
    # mid-convergence labels, not just arange: run the oracle a few steps
    labels = np.arange(n, dtype=np.int32)
    for _ in range(int(rng.integers(0, 3))):
        labels = label_step_reference(labels, eu, ev)
    want = label_step_reference(labels, eu, ev)
    got_x = np.asarray(label_step_xla(jnp.asarray(labels), jnp.asarray(eu),
                                      jnp.asarray(ev)))
    got_k = np.asarray(label_step(jnp.asarray(labels), jnp.asarray(eu),
                                  jnp.asarray(ev), n_shards=n_shards))
    np.testing.assert_array_equal(got_x, want)
    np.testing.assert_array_equal(got_k, want)


@pytest.mark.parametrize("n_shards", [3, 4, 5])
def test_label_step_empty_edge_set(n_shards):
    """The zero-width batch edge case: zero edges must be identity
    (padding edges are (0,0) self-loops — a no-op), including on
    non-pow2 shard grids."""
    labels = jnp.arange(17, dtype=jnp.int32)
    out = label_step(labels, jnp.zeros((0,), jnp.int32),
                     jnp.zeros((0,), jnp.int32), n_shards=n_shards)
    np.testing.assert_array_equal(np.asarray(out), np.arange(17))


@pytest.mark.parametrize("n_shards,use_pallas", [(1, False), (1, True),
                                                 (4, True), (8, True)])
def test_connected_components_matches_union_find(n_shards, use_pallas):
    rng = np.random.default_rng(31)
    n, e = 60, 70
    eu, ev = _random_edges(rng, n, e)
    got = np.asarray(connected_components(
        jnp.asarray(eu), jnp.asarray(ev), n=n, n_shards=n_shards,
        use_pallas=use_pallas))
    np.testing.assert_array_equal(got,
                                  components_reference(n, zip(eu, ev)))


def test_connected_components_pallas_and_xla_bit_exact():
    """Same fixpoint trajectory, not just the same partition."""
    rng = np.random.default_rng(13)
    n, e = 50, 40
    eu, ev = _random_edges(rng, n, e)
    a = connected_components(jnp.asarray(eu), jnp.asarray(ev), n=n)
    b = connected_components(jnp.asarray(eu), jnp.asarray(ev), n=n,
                             n_shards=4, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n_shards", [1, 4])
def test_label_step_without_jump_is_one_hop(n_shards):
    """``jump=False``: the kernel's scatter-min alone, one hop of
    min-label propagation, equal to the numpy scatter-min."""
    rng = np.random.default_rng(17)
    n, e = 70, 60
    eu, ev = _random_edges(rng, n, e)
    labels = rng.permutation(n).astype(np.int32)
    m = np.minimum(labels[eu], labels[ev])
    want = labels.copy()
    np.minimum.at(want, eu, m)
    np.minimum.at(want, ev, m)
    got = label_step(jnp.asarray(labels), jnp.asarray(eu), jnp.asarray(ev),
                     n_shards=n_shards, jump=False)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("seed,n,e,use_pallas", [
    (3, 60, 40, False), (4, 60, 90, False), (5, 200, 260, False),
    (6, 9, 0, False), (4, 60, 90, True)])
def test_spanning_forest_is_breadth_first(seed, n, e, use_pallas):
    """Labels equal the component mins; each other vertex's parent edge
    leads one hop nearer its component's min, so the parent edges are
    n minus the component count distinct edges spanning every component;
    the step count is the largest hop distance plus the step that finds
    nothing."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import shortest_path

    rng = np.random.default_rng(seed)
    eu, ev = (_random_edges(rng, n, e) if e
              else (np.zeros(1, np.int32), np.zeros(1, np.int32)))
    lab, parent, steps = spanning_forest(jnp.asarray(eu), jnp.asarray(ev),
                                         n=n, n_shards=4,
                                         use_pallas=use_pallas)
    lab, parent = np.asarray(lab), np.asarray(parent)
    want = components_reference(n, zip(eu, ev))
    np.testing.assert_array_equal(lab, want)
    roots = np.unique(want)
    adj = sp.coo_matrix((np.ones(len(eu)), (eu, ev)), shape=(n, n))
    hops = shortest_path(adj, unweighted=True, directed=False,
                         indices=roots)
    d = hops[np.searchsorted(roots, want), np.arange(n)]
    assert int(steps) == int(d.max()) + 1
    is_root = want == np.arange(n)
    assert np.all(parent[is_root] == len(eu))
    kids = np.flatnonzero(~is_root)
    pe = parent[kids]
    assert len(set(pe.tolist())) == len(kids)
    assert np.all((eu[pe] == kids) | (ev[pe] == kids))
    other = np.where(eu[pe] == kids, ev[pe], eu[pe])
    np.testing.assert_array_equal(d[other], d[kids] - 1)


# ---------------------------------------------------------------------------
# sorted merge-compact (batched map, DESIGN.md §13): grid=(K,) map shards
# ---------------------------------------------------------------------------
def _merge_case(rng, n, c):
    """Random (A-run + keep mask, B-run) pair honoring the kernel's
    preconditions: kept-A and valid-B strictly increasing, disjoint."""
    nk = int(rng.integers(0, n - c + 1))
    a_keys = np.full((n,), np.inf, np.float32)
    a_vals = np.full((n,), np.inf, np.float32)
    pool = rng.permutation(np.arange(0, 4096, dtype=np.float32))
    ks = np.sort(pool[:nk])
    a_keys[:nk] = ks
    a_vals[:nk] = rng.uniform(-9, 9, nk).astype(np.float32)
    keep = np.zeros((n,), bool)
    keep[:nk] = rng.random(nk) < 0.7
    bc = int(rng.integers(0, c + 1))
    bs = np.sort(pool[nk : nk + bc])                   # disjoint from A
    b_keys = np.full((c,), np.inf, np.float32)
    b_vals = np.full((c,), np.inf, np.float32)
    b_keys[:bc] = bs
    b_vals[:bc] = rng.uniform(-9, 9, bc).astype(np.float32)
    return a_keys, a_vals, keep, b_keys, b_vals, bc


@pytest.mark.parametrize("trial", range(4))
def test_merge_compact_kernel_bit_exact(trial):
    """Kernel ≡ numpy ref ELEMENT-WISE (keys AND values) for an
    arbitrary keep mask, ragged sizes included — the merge moves f32
    bits, no arithmetic."""
    rng = np.random.default_rng(900 + trial)
    n = int(rng.integers(8, 70))                       # not tile-aligned
    c = int(rng.integers(1, 8))
    a_keys, a_vals, keep, b_keys, b_vals, bc = _merge_case(rng, n, c)
    want = merge_compact_reference(a_keys, a_vals, keep, b_keys, b_vals,
                                   bc)
    got = merge_compact(jnp.asarray(a_keys), jnp.asarray(a_vals),
                        jnp.asarray(keep), jnp.asarray(b_keys),
                        jnp.asarray(b_vals), jnp.int32(bc))
    np.testing.assert_array_equal(np.asarray(got[0]), want[0])
    np.testing.assert_array_equal(np.asarray(got[1]), want[1])


@pytest.mark.parametrize("n_shards", [1, 3, 4, 5, 8])
def test_merge_compact_sharded_per_shard_reference(n_shards):
    """ONE grid=(K,) dispatch merges every shard independently —
    per-shard output equals the per-shard oracle, for every K."""
    rng = np.random.default_rng(77)
    n, c = 48, 6
    stacks = [_merge_case(rng, n, c) for _ in range(n_shards)]
    ak = jnp.asarray(np.stack([s[0] for s in stacks]))
    av = jnp.asarray(np.stack([s[1] for s in stacks]))
    kp = jnp.asarray(np.stack([s[2] for s in stacks]))
    bk = jnp.asarray(np.stack([s[3] for s in stacks]))
    bv = jnp.asarray(np.stack([s[4] for s in stacks]))
    bc = jnp.asarray(np.asarray([s[5] for s in stacks], np.int32))
    mk, mv = merge_compact_sharded(ak, av, kp, bk, bv, bc)
    for k in range(n_shards):
        want = merge_compact_reference(*stacks[k])
        np.testing.assert_array_equal(np.asarray(mk)[k], want[0])
        np.testing.assert_array_equal(np.asarray(mv)[k], want[1])


def test_merge_compact_empty_and_full_cases():
    """Edge cases: empty B (pure compaction), empty A, everything
    dropped, and a full-width merge (n_keep + b_count == N)."""
    n, c = 16, 4
    a_keys = np.full((n,), np.inf, np.float32)
    a_vals = np.full((n,), np.inf, np.float32)
    a_keys[:3] = [1.0, 5.0, 9.0]
    a_vals[:3] = [10.0, 50.0, 90.0]
    keep = np.zeros((n,), bool)
    keep[:3] = [True, False, True]
    b_keys = np.full((c,), np.inf, np.float32)
    b_vals = np.full((c,), np.inf, np.float32)
    b_keys[:2] = [2.0, 7.0]
    b_vals[:2] = [20.0, 70.0]
    for bc in (0, 2):
        want = merge_compact_reference(a_keys, a_vals, keep, b_keys,
                                       b_vals, bc)
        got = merge_compact(jnp.asarray(a_keys), jnp.asarray(a_vals),
                            jnp.asarray(keep), jnp.asarray(b_keys),
                            jnp.asarray(b_vals), jnp.int32(bc))
        np.testing.assert_array_equal(np.asarray(got[0]), want[0])
        np.testing.assert_array_equal(np.asarray(got[1]), want[1])
    # everything dropped + empty B → all-padding output
    got = merge_compact(jnp.asarray(a_keys), jnp.asarray(a_vals),
                        jnp.asarray(np.zeros((n,), bool)),
                        jnp.asarray(b_keys), jnp.asarray(b_vals),
                        jnp.int32(0))
    assert np.all(np.isinf(np.asarray(got[0])))
    assert np.all(np.isinf(np.asarray(got[1])))
    # full-width merge: N kept + 0 new fills every slot
    full_k = np.arange(n, dtype=np.float32)
    full_v = np.arange(n, dtype=np.float32) * 2
    got = merge_compact(jnp.asarray(full_k), jnp.asarray(full_v),
                        jnp.asarray(np.ones((n,), bool)),
                        jnp.asarray(np.full((c,), np.inf, np.float32)),
                        jnp.asarray(np.full((c,), np.inf, np.float32)),
                        jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(got[0]), full_k)
    np.testing.assert_array_equal(np.asarray(got[1]), full_v)


def _edit_case(rng, n, c, kind):
    """One shard's bounded edit for :func:`merge_edits_xla`: a live run of
    ``size`` keys, ≤ c deletion slots (lane order shuffled, unused lanes
    at n; no slots at all for ``ins_only``, the sketch's insert-only
    merge) and ≤ c sorted inserts absent from the kept run."""
    pool = rng.permutation(np.arange(1, 8 * (n + c) + 8)).astype(np.float32)
    if kind == "none":
        size, dels, bc = int(rng.integers(0, n + 1)), [], 0
    elif kind == "ins_only":                  # no deletion slots at all
        size, dels = int(rng.integers(0, n + 1)), []
        bc = int(rng.integers(0, min(c, n - size) + 1))
    elif kind in ("del_front", "del_back"):
        size = n
        nd = min(c, size)
        dels = list(range(nd)) if kind == "del_front" \
            else list(range(size - nd, size))
        bc = 0
    elif kind == "del_at_size":
        size = int(rng.integers(min(c, n), n + 1))
        dels, bc = list(range(size - min(c, size), size)), 0
    elif kind in ("ins_below", "ins_above"):
        size, dels, bc = n - min(c, n), [], min(c, n)
    elif kind == "full":
        size = int(rng.integers(max(0, n - c), n + 1))
        nd = int(rng.integers(0, min(size, c - n + size) + 1))
        dels = sorted(rng.choice(size, nd, replace=False).tolist())
        bc = n - size + nd
    else:                                              # "mixed"
        size = int(rng.integers(0, n + 1))
        nd = int(rng.integers(0, min(c, size) + 1))
        dels = sorted(rng.choice(size, nd, replace=False).tolist())
        bc = int(rng.integers(0, min(c, n - size + nd) + 1))
    a = np.sort(pool[:size])
    b = np.sort(pool[size : size + bc])
    if kind == "ins_below":
        a = np.sort(pool[:size] + 8 * (n + c) + 8)
    elif kind == "ins_above":
        b = np.sort(pool[size : size + bc] + 8 * (n + c) + 8)
    a_keys = np.full((n,), np.inf, np.float32)
    a_vals = np.full((n,), np.inf, np.float32)
    a_keys[:size] = a
    a_vals[:size] = rng.uniform(-9, 9, size).astype(np.float32)
    d_slots = np.full((0 if kind == "ins_only" else c,), n, np.int32)
    d_slots[:len(dels)] = dels
    d_slots = rng.permutation(d_slots)
    b_keys = np.full((c,), np.inf, np.float32)
    b_vals = np.full((c,), np.inf, np.float32)
    b_keys[:bc] = b
    b_vals[:bc] = rng.uniform(-9, 9, bc).astype(np.float32)
    keep = np.arange(n) < size
    keep[dels] = False
    return (a_keys, a_vals, size, d_slots, b_keys, b_vals, bc), keep


EDIT_CASES = [
    ("none", 37, 5), ("none", 1, 1), ("none", 200, 64),
    ("del_front", 200, 64), ("del_back", 200, 64), ("del_back", 1, 1),
    ("del_at_size", 513, 64), ("del_at_size", 37, 5),
    ("ins_below", 200, 64), ("ins_above", 200, 64), ("ins_below", 1, 1),
    ("ins_above", 37, 5),
    ("full", 64, 64), ("full", 37, 5), ("full", 1, 1),
    ("mixed", 200, 64), ("mixed", 513, 5), ("mixed", 37, 1),
    ("mixed", 64, 64), ("mixed", 300, 130), ("mixed", 16640, 64),
    ("ins_only", 37, 5), ("ins_only", 200, 64), ("ins_only", 1, 1),
]


@pytest.mark.parametrize("K", [1, 4])
@pytest.mark.parametrize("kind,n,c", EDIT_CASES)
def test_merge_edits_xla_matches_reference(kind, n, c, K):
    """The bounded-edit merge, vmapped over K shards, gives every shard
    the numpy oracle's BITS (keys and values), the keep mask being the
    live run less the deletion slots."""
    rng = np.random.default_rng(1400 + 2 * EDIT_CASES.index((kind, n, c))
                                + K)
    cases = [_edit_case(rng, n, c, kind) for _ in range(K)]
    stk = [jnp.asarray(np.stack([np.asarray(cs[0][i]) for cs in cases]))
           for i in range(7)]
    mk, mv = jax.jit(jax.vmap(merge_edits_xla))(*stk)
    for (args, keep), gk, gv in zip(cases, np.asarray(mk), np.asarray(mv)):
        a_keys, a_vals, _, _, b_keys, b_vals, bc = args
        wk, wv = merge_compact_reference(a_keys, a_vals, keep, b_keys,
                                         b_vals, bc)
        np.testing.assert_array_equal(gk.view(np.uint32),
                                      wk.view(np.uint32))
        np.testing.assert_array_equal(gv.view(np.uint32),
                                      wv.view(np.uint32))


# ---------------------------------------------------------------------------
# grid=(K,) parity sweep: non-pow2 shard counts (K=3, K=5) and
# zero-width batches for EVERY sharded kernel.  Non-pow2 grids catch
# tiling/padding assumptions baked into the pow2 happy path; zero-width
# dispatches are what an idle shard sees on every combining pass.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("K", [3, 5])
def test_heap_sift_sharded_nonpow2_grid(K):
    """K=3/K=5 shard grids, last shard carrying ZERO active wavefronts —
    per-shard output equals the per-shard oracle (identity for the idle
    shard)."""
    rng = np.random.default_rng(600 + K)
    cap, c = 256, 8
    A = np.stack([_random_heap(rng, int(rng.integers(16, 200)), cap)
                  for _ in range(K)])
    sizes = np.asarray([np.isfinite(A[k, 1:]).sum() for k in range(K)],
                       np.int32)
    starts = np.zeros((K, c), np.int32)
    active = np.zeros((K, c), np.int32)
    wants = []
    for k in range(K):
        if k == K - 1:                     # idle shard: zero-width batch
            wants.append(A[k].copy())
            continue
        ss = sorted(rng.choice(np.arange(1, sizes[k] + 1), size=2,
                               replace=False).tolist())
        for i, s in enumerate(ss):
            A[k, s] = rng.uniform(0, 150)
            starts[k, i] = s
            active[k, i] = 1
        wants.append(sift_wavefront_reference(A[k], sizes[k], starts[k],
                                              active[k]))
    got = np.asarray(sift_wavefront_sharded(
        jnp.asarray(A), jnp.asarray(sizes), jnp.asarray(starts),
        jnp.asarray(active)))
    np.testing.assert_array_equal(got, np.stack(wants))


@pytest.mark.parametrize("K", [3, 5])
def test_heap_sift_sharded_zero_width_batch(K):
    """All shards inactive: the dispatch must be a bit-exact no-op."""
    rng = np.random.default_rng(610 + K)
    A = np.stack([_random_heap(rng, 20 + 3 * k, 128) for k in range(K)])
    sizes = np.asarray([20 + 3 * k for k in range(K)], np.int32)
    got = np.asarray(sift_wavefront_sharded(
        jnp.asarray(A), jnp.asarray(sizes),
        jnp.zeros((K, 8), jnp.int32), jnp.zeros((K, 8), jnp.int32)))
    np.testing.assert_array_equal(got, A)


@pytest.mark.parametrize("K", [3, 5])
def test_heap_insert_sharded_nonpow2_grid(K):
    rng = np.random.default_rng(620 + K)
    cap, C = 512, 8
    sizes = np.asarray([12 + 7 * k for k in range(K)], np.int32)
    A = np.stack([_random_heap(rng, int(s), cap) for s in sizes])
    ms = np.asarray([(k * 2 + 1) % (C + 1) for k in range(K)], np.int32)
    ms[K // 2] = 0                         # one zero-width shard mid-grid
    CV = np.full((K, C), np.inf, np.float32)
    wants = []
    for k in range(K):
        if ms[k]:
            lo = int(sizes[k]) + 1
            level_end = (2 << int(math.floor(math.log2(lo)))) - 1
            ms[k] = min(int(ms[k]), level_end - lo + 1)
            CV[k, :ms[k]] = np.sort(
                rng.uniform(0, 100, ms[k]).astype(np.float32))
        w, _ = insert_chunk_reference(A[k], sizes[k], CV[k], ms[k],
                                      c_max=C, max_depth=10)
        wants.append(np.asarray(w))
    got, new_sz = insert_chunk_sharded(
        jnp.asarray(A), jnp.asarray(sizes), jnp.asarray(CV),
        jnp.asarray(ms))
    np.testing.assert_array_equal(np.asarray(got), np.stack(wants))
    np.testing.assert_array_equal(np.asarray(new_sz), sizes + ms)


@pytest.mark.parametrize("K", [3, 5])
def test_heap_insert_sharded_zero_width_batch(K):
    """All shards insert nothing: heaps and sizes are unchanged."""
    rng = np.random.default_rng(630 + K)
    sizes = np.asarray([10 + k for k in range(K)], np.int32)
    A = np.stack([_random_heap(rng, int(s), 256) for s in sizes])
    got, new_sz = insert_chunk_sharded(
        jnp.asarray(A), jnp.asarray(sizes),
        jnp.full((K, 8), np.inf, jnp.float32), jnp.zeros((K,), jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), A)
    np.testing.assert_array_equal(np.asarray(new_sz), sizes)


@pytest.mark.parametrize("K", [3, 5])
def test_heap_kmin_sharded_nonpow2_grid(K):
    rng = np.random.default_rng(640 + K)
    cap, c_max = 256, 8
    sizes = np.asarray([(17 * (k + 1)) % 60 for k in range(K)], np.int32)
    sizes[K - 1] = 0                       # one empty shard
    A = np.stack([_random_heap(rng, int(s), cap) for s in sizes])
    ids, vals = k_smallest_sharded(jnp.asarray(A), jnp.asarray(sizes),
                                   jnp.int32(4), c_max=c_max)
    for k in range(K):
        ir, vr = k_smallest_reference(A[k], sizes[k], 4, c_max)
        np.testing.assert_array_equal(np.asarray(ids)[k], ir)
        np.testing.assert_array_equal(np.asarray(vals)[k], vr)


@pytest.mark.parametrize("K", [3, 5])
def test_heap_kmin_sharded_zero_width_batch(K):
    """ne=0 across every shard: all-padding candidates, no reads past
    the frontier."""
    rng = np.random.default_rng(650 + K)
    sizes = np.asarray([8 + 2 * k for k in range(K)], np.int32)
    A = np.stack([_random_heap(rng, int(s), 128) for s in sizes])
    ids, vals = k_smallest_sharded(jnp.asarray(A), jnp.asarray(sizes),
                                   jnp.int32(0), c_max=8)
    for k in range(K):
        ir, vr = k_smallest_reference(A[k], sizes[k], 0, 8)
        np.testing.assert_array_equal(np.asarray(ids)[k], ir)
        np.testing.assert_array_equal(np.asarray(vals)[k], vr)


@pytest.mark.parametrize("K", [3, 5])
def test_merge_compact_sharded_zero_width_batch(K):
    """Every shard merges an EMPTY B-run with keep-all: the grid=(K,)
    dispatch must return the input runs bit-for-bit."""
    rng = np.random.default_rng(660 + K)
    n, c = 32, 4
    ak = np.stack([np.concatenate([
        np.sort(rng.permutation(np.arange(0, 512, dtype=np.float32))[:12]),
        np.full((n - 12,), np.inf, np.float32)]) for _ in range(K)])
    av = np.where(np.isinf(ak), np.inf,
                  rng.uniform(-9, 9, ak.shape)).astype(np.float32)
    keep = ~np.isinf(ak)
    bk = np.full((K, c), np.inf, np.float32)
    mk, mv = merge_compact_sharded(
        jnp.asarray(ak), jnp.asarray(av), jnp.asarray(keep),
        jnp.asarray(bk), jnp.asarray(bk), jnp.zeros((K,), jnp.int32))
    np.testing.assert_array_equal(np.asarray(mk), ak)
    np.testing.assert_array_equal(np.asarray(mv), av)


def _pallas_structures():
    """(name, constructor) just past each kernel's VMEM limit."""
    from repro.core.batched_map import ShardedMap
    from repro.core.batched_pq import BatchedPriorityQueue
    from repro.core.batched_sketch import ShardedSketch
    from repro.core.batched_union_find import BatchedUnionFind
    from repro.core.device_graph import DeviceGraph
    from repro.core.sharded_pq import ShardedBatchedPQ
    from repro.kernels import _rows
    from repro.kernels.label_prop import ops as lp
    from repro.kernels.sorted_merge import ops as sm

    heap = _rows.MAX_HEAP_CAPACITY + 1
    slots = sm.MAX_PALLAS_SLOTS + 1
    return {
        "sharded_pq": lambda: ShardedBatchedPQ(heap, c_max=4, n_shards=2,
                                               use_pallas=True),
        "batched_pq": lambda: BatchedPriorityQueue(heap, c_max=4,
                                                   use_pallas=True),
        "map": lambda: ShardedMap(slots, c_max=4, use_pallas=True),
        "sketch": lambda: ShardedSketch(slots, c_max=4, use_pallas=True),
        "graph_vertices": lambda: DeviceGraph(lp.MAX_PALLAS_VERTICES + 1,
                                              edge_capacity=64,
                                              use_pallas=True),
        "graph_edges": lambda: DeviceGraph(64,
                                           edge_capacity=lp.MAX_PALLAS_EDGES
                                           + 1, use_pallas=True),
        "union_find": lambda: BatchedUnionFind(lp.MAX_PALLAS_VERTICES + 1,
                                               use_pallas=True),
    }


@pytest.mark.parametrize("name", sorted(_pallas_structures()))
def test_pallas_refused_at_construction_above_vmem_limit(name):
    """A structure the whole-shard-in-VMEM kernels cannot hold is refused
    when it is built, naming the limit — never mid-run on the device."""
    with pytest.raises(ValueError, match="limit"):
        _pallas_structures()[name]()
