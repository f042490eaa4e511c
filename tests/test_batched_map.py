"""Batched ordered map (DESIGN.md §13): semantics, reads, rounds,
occupancy guard, one-sync contract — deterministic tier-1 suite plus
seeded differential fuzz at K ∈ {1, 4, 8}."""
import math

import numpy as np
import pytest

import repro.core.batched_map as bm
from conformance import run_differential
from repro.core import substrate
from repro.core.batched_map import BatchedMap, ShardedMap
from repro.core.pc_map import fc_map, pc_map
from repro.core.seq_map import SequentialSortedMap

substrate.load_builtins()

KR = (0.0, 100.0)


def _sharded(K, capacity=256, c_max=8, **kw):
    return ShardedMap(capacity, c_max=c_max, n_shards=K,
                      key_range=None if K == 1 else KR, **kw)


# ---------------------------------------------------------------------------
# update semantics
# ---------------------------------------------------------------------------
def test_single_op_semantics():
    m = BatchedMap(64, c_max=4)
    assert m.insert(5.0, 1.0) is True
    assert m.insert(5.0, 2.0) is False          # no-op, value kept
    assert m.lookup(5.0) == 1.0
    assert m.assign(5.0, 3.0) is True
    assert m.lookup(5.0) == 3.0
    assert m.assign(9.0, 1.0) is False          # assign-on-absent
    assert m.lookup(9.0) is None
    assert m.delete(9.0) is False
    assert m.delete(5.0) is True
    assert m.lookup(5.0) is None
    assert len(m) == 0


def test_mixed_batch_arrival_order_chain_rule():
    """Duplicate-key ops inside ONE batch resolve by the last-earlier-
    same-key chain rule; the buffer takes only the net effect."""
    m = _sharded(4, c_max=16)
    o = SequentialSortedMap()
    methods = ["insert", "insert", "delete", "insert", "assign",
               "delete", "assign", "insert", "delete", "insert"]
    inputs = [(1.0, 10.0), (1.0, 11.0), 1.0, (1.0, 12.0), (1.0, 13.0),
              2.0, (2.0, 9.0), (2.0, 8.0), (2.0), (3.0, 7.0)]
    got = m.update_batch(methods, inputs)
    want = [o.apply(mm, ii) for mm, ii in zip(methods, inputs)]
    assert got == want
    assert m.items() == o.items()
    # transient insert+delete pairs never reach the buffer
    m2 = _sharded(1, c_max=8)
    got = m2.update_batch(["insert", "delete"], [(4.0, 1.0), 4.0])
    assert got == [True, True]
    assert m2.items() == []


def test_update_results_ride_the_read_fetch():
    """update_batch_async pays NO sync; the next read's single fetch
    resolves the masks (the PQ/graph one-sync contract)."""
    calls = []
    orig = bm._host_fetch
    bm._host_fetch = lambda x: (calls.append(1), orig(x))[1]
    try:
        m = _sharded(2, capacity=64)
        calls.clear()
        h = m.update_batch_async(["insert"] * 3,
                                 [(1.0, 1.0), (2.0, 2.0), (3.0, 3.0)])
        assert calls == []                      # sync-free dispatch
        res = m.read_batch(["lookup", "range_count"], [2.0, (0.0, 10.0)])
        assert len(calls) == 1                  # ONE fetch for the pass
        assert h.result() == [True, True, True]
        assert len(calls) == 1                  # masks rode the fetch
        assert res == [2.0, 3]
    finally:
        bm._host_fetch = orig


def test_rounds_scan_path_equals_sequential_slices():
    """A batch wider than c_max lowers onto ONE lax.scan program whose
    result equals applying the c_max slices one by one."""
    rng = np.random.default_rng(3)
    ops = []
    for i in range(19):                          # c_max=4 → 8 pow2 rows
        mth = ("insert", "delete", "assign")[int(rng.integers(0, 3))]
        k = float(np.float32(rng.integers(0, 12)))
        ops.append((mth, k if mth == "delete"
                    else (k, float(np.float32(rng.uniform(0, 9))))))
    fused = _sharded(2, capacity=64, c_max=4)
    sliced = _sharded(2, capacity=64, c_max=4)
    got = fused.update_batch([m for m, _ in ops], [i for _, i in ops])
    want = []
    for j in range(0, len(ops), 4):
        chunk = ops[j : j + 4]
        want.extend(sliced.update_batch([m for m, _ in chunk],
                                        [i for _, i in chunk]))
    assert got == want
    assert fused.items() == sliced.items()


def test_key_and_value_validation():
    m = BatchedMap(16, c_max=4)
    for bad in (float("nan"), float("inf"), -float("inf")):
        with pytest.raises(ValueError):
            m.insert(bad, 1.0)
        with pytest.raises(ValueError):
            m.lookup(bad)
    with pytest.raises(ValueError):
        m.insert(1.0, float("nan"))
    with pytest.raises(ValueError):
        m.update_batch(["upsert"], [(1.0, 1.0)])
    with pytest.raises(ValueError):
        ShardedMap(16, c_max=4, n_shards=2)      # K>1 needs key_range


# ---------------------------------------------------------------------------
# reads
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("K", [1, 4])
def test_reads_match_oracle(K):
    items = [(float(k), float(k) * 2.0) for k in range(0, 40, 3)]
    m = _sharded(K, items=items)
    o = SequentialSortedMap(items)
    for k in [0.0, 3.0, 4.0, 39.0, 100.0, -5.0]:
        assert m.lookup(k) == o.lookup(k)
    for lo, hi in [(0.0, 40.0), (5.0, 5.0), (6.0, 6.0), (10.0, 3.0),
                   (-10.0, 200.0), (38.0, 39.0)]:
        assert m.range_count(lo, hi) == o.range_count(lo, hi)
        assert abs(m.range_sum(lo, hi) - o.range_sum(lo, hi)) < 1e-3
    for k in [0, 1, 5, len(items), len(items) + 1, -2]:
        assert m.kth_smallest(k) == o.kth_smallest(k)


def test_reads_on_empty_map():
    m = _sharded(4)
    assert m.lookup(1.0) is None
    assert m.range_count(0.0, 50.0) == 0
    assert m.range_sum(0.0, 50.0) == 0.0
    assert m.kth_smallest(1) is None
    assert m.read_batch([], []) == []


def test_kth_smallest_spans_shards_in_global_order():
    """Key-range routing keeps the shard concatenation globally sorted;
    kth_smallest must walk it via the cumulative-size search."""
    items = [(float(k), 0.0) for k in range(0, 100, 7)]
    m = _sharded(8, items=items)
    keys = sorted(k for k, _ in items)
    for j, k in enumerate(keys, start=1):
        assert m.kth_smallest(j) == k


# ---------------------------------------------------------------------------
# occupancy guard (the ISSUE-5 overflow audit, map side)
# ---------------------------------------------------------------------------
def test_overflow_refusal_is_atomic_and_recoverable():
    """A refused oversized batch leaves the device buffers AND the host
    occupancy mirror bit-for-bit unchanged — even when only a LATER
    slice of the batch would overflow — and the next legal apply
    succeeds."""
    m = ShardedMap(8, c_max=4, n_shards=2, key_range=KR)
    m.update_batch(["insert"] * 3, [(float(i), 0.0) for i in (1, 2, 3)])
    before = {
        "keys": np.asarray(m.state.keys).copy(),
        "vals": np.asarray(m.state.vals).copy(),
        "size": np.asarray(m.state.size).copy(),
        "ub": m._sizes_ub.copy(),
    }
    # 6 inserts all routed to shard 0 (< 50.0) across two slices of 4+2:
    # the FIRST slice alone fits (3+4 ≤ 8), the second overflows —
    # nothing may apply (before the atomic guard, slice 1 would have
    # reached the device before slice 2's refusal)
    with pytest.raises(ValueError):
        m.update_batch(["insert"] * 6,
                       [(10.0 + i, 0.0) for i in range(6)])
    assert np.array_equal(np.asarray(m.state.keys), before["keys"])
    assert np.array_equal(np.asarray(m.state.vals), before["vals"])
    assert np.array_equal(np.asarray(m.state.size), before["size"])
    assert np.array_equal(m._sizes_ub, before["ub"])
    # shard 1 has room: the next legal apply must succeed
    assert m.insert(60.0, 1.0) is True
    assert m.lookup(60.0) == 1.0
    # guard is an upper bound: deletes re-open room after a fetch
    m.update_batch(["delete"] * 2, [1.0, 2.0])
    m.read_batch(["lookup"], [3.0])             # fetch → exact sizes
    assert m.insert(20.0, 0.0) is True


# ---------------------------------------------------------------------------
# ablation twins
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("K", [1, 4])
def test_pallas_merge_matches_xla_twin(K):
    """use_pallas routes the merge-compact through the grid=(K,) kernel;
    the resulting state must be bit-identical to the XLA twin's."""
    rng = np.random.default_rng(5)
    a = _sharded(K, capacity=128, use_pallas=False)
    b = _sharded(K, capacity=128, use_pallas=True)
    for _ in range(6):
        n = int(rng.integers(1, 10))
        methods, inputs = [], []
        for _ in range(n):
            mth = ("insert", "delete", "assign")[int(rng.integers(0, 3))]
            k = float(np.float32(rng.integers(0, 30)))
            methods.append(mth)
            inputs.append(k if mth == "delete"
                          else (k, float(np.float32(rng.uniform(0, 9)))))
        assert a.update_batch(methods, inputs) == \
            b.update_batch(methods, inputs)
    np.testing.assert_array_equal(np.asarray(a.state.keys),
                                  np.asarray(b.state.keys))
    np.testing.assert_array_equal(np.asarray(a.state.vals),
                                  np.asarray(b.state.vals))
    np.testing.assert_array_equal(np.asarray(a.state.size),
                                  np.asarray(b.state.size))


def test_donated_and_undonated_agree():
    a = _sharded(2, donate=True)
    b = _sharded(2, donate=False)
    ops = (["insert"] * 4 + ["delete", "assign"],
           [(1.0, 1.0), (2.0, 2.0), (60.0, 3.0), (61.0, 4.0), 2.0,
            (60.0, 9.0)])
    assert a.update_batch(*ops) == b.update_batch(*ops)
    assert a.items() == b.items()


# ---------------------------------------------------------------------------
# mechanism guard: the update programs address ≤ K·c rows by index
# ---------------------------------------------------------------------------
def _lowered_update_program(name, K, cap, c, R=4):
    import jax
    import jax.numpy as jnp

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    state = bm.MapState(spec((K, cap + 1)), spec((K, cap + 1)),
                        spec((K,), jnp.int32))
    static = dict(key_range=(0.0, 1.0), use_pallas=False, placement=None)
    if name == "apply_pass":
        args = (spec((c,)), spec((c,)), spec((c,), jnp.int32),
                spec((), jnp.int32))
    elif name == "apply_rounds":
        args = (spec((R, c)), spec((R, c)), spec((R, c), jnp.int32),
                spec((R,), jnp.int32))
    else:
        args = (spec((R,), jnp.int32), spec((R, c)), spec((R, c)),
                spec((R, c), jnp.int32), spec((R,), jnp.int32))
    return getattr(bm, name).lower(state, *args, **static)


def _walk(op):
    for region in op.regions:
        for block in region.blocks:
            for inner in block.operations:
                yield inner
                yield from _walk(inner)


@pytest.mark.parametrize("program", ["apply_pass", "apply_rounds",
                                     "mixed_pass"])
def test_update_programs_address_at_most_k_c_rows(program):
    """At the benchmark's shard size no scatter or gather in an update
    program takes more than K·c index rows, and no value is a (K, cap, c)
    broadcast: the merge moves the shard by static shifts, not by an
    N-wide scatter or an N×c rank compare.  Lowering only, nothing runs."""
    import re

    from jax._src.interpreters import mlir

    K, cap, c = 4, 1 << 21, 64
    module = _lowered_update_program(program, K, cap, c).compiler_ir(
        "stablehlo")
    n_addressed = 0
    for op in _walk(module.operation):
        name = op.operation.name
        values = list(op.operands) + list(op.results)
        for v in values:
            if isinstance(v.type, mlir.ir.RankedTensorType):
                shape = tuple(v.type.shape)
                assert shape != (K, cap, c), (program, name, shape)
                assert math.prod(shape) <= 2 * K * (cap + 1), \
                    (program, name, shape)
        if name not in ("stablehlo.scatter", "stablehlo.gather"):
            continue
        dn = op.attributes["scatter_dimension_numbers"
                           if name.endswith("scatter")
                           else "dimension_numbers"]
        m = re.search(r"index_vector_dim = (\d+)", str(dn))
        ivd = int(m.group(1)) if m else 0
        idx = list(mlir.ir.RankedTensorType(op.operands[1].type).shape)
        rows = math.prod(d for i, d in enumerate(idx) if i != ivd)
        assert rows <= K * c, (program, name, idx)
        n_addressed += 1
    assert n_addressed > 0


# ---------------------------------------------------------------------------
# combining wrapper
# ---------------------------------------------------------------------------
def test_pc_map_engine_end_to_end():
    eng = pc_map(_sharded(4, capacity=64))
    host = fc_map()
    for m, i in [("insert", (5.0, 7.0)), ("insert", (8.0, 1.0)),
                 ("assign", (5.0, 2.0)), ("lookup", 5.0),
                 ("range_count", (0.0, 10.0)), ("range_sum", (0.0, 10.0)),
                 ("kth_smallest", 2), ("delete", 8.0), ("lookup", 8.0)]:
        got, want = eng.execute(m, i), host.execute(m, i)
        if m == "range_sum":
            assert abs(got - want) < 1e-3
        else:
            assert got == want, (m, i, got, want)


# ---------------------------------------------------------------------------
# seeded differential fuzz (the acceptance gate: K ∈ {1, 4, 8}),
# driven by the registry-level conformance kit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("K", [1, 4, 8])
def test_differential_fuzz_vs_sorted_map_oracle(K):
    spec = substrate.get("map")
    m = ShardedMap(192, c_max=8, n_shards=K,
                   key_range=None if K == 1 else KR,
                   items=[(float(j), float(j)) for j in range(0, 20, 2)])
    run_differential(m, spec.make_host(m), spec,
                     np.random.default_rng(100 + K), 30)
