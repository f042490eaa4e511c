"""Compile the served device programs for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a topology it is only
told about, so a Mosaic refusal (a block that breaks the (8, 128) tiling
rule, an unaligned dynamic slice, a kernel that runs out of VMEM) fails
here instead of on the chip.  Interpret mode, which every other kernel
test uses, cannot see any of these.  Each structure kernel compiles at
the largest size its structure admits with ``use_pallas=True``, and the
lowered program must carry the Mosaic kernel (``tpu_custom_call``).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import _rows
from repro.kernels.heap_insert import insert_chunk_sharded
from repro.kernels.heap_kmin import k_smallest_sharded
from repro.kernels.heap_sift import sift_wavefront_sharded
from repro.kernels.label_prop import label_step
from repro.kernels.label_prop import ops as label_ops
from repro.kernels.sorted_merge import merge_compact_sharded
from repro.kernels.sorted_merge import ops as merge_ops

F32, I32 = jnp.float32, jnp.int32
K = 4           # shards, as the served structures use
PQ_C = 16       # the PQ's served c_max
MAP_C = 64      # the map's served c_max


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with JAX's persistent cache off:
    a compile for a described chip is written to the cache but cannot be
    read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, args, sharding, **static):
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in args]
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *shapes, **static).compile()


HEAP = _rows.MAX_HEAP_CAPACITY
KERNELS = {
    "heap_kmin": (
        lambda a, s, n: k_smallest_sharded(a, s, n, c_max=PQ_C,
                                           interpret=False),
        [((K, HEAP), F32), ((K,), I32), ((), I32)]),
    "heap_sift": (
        lambda a, s, st, ac: sift_wavefront_sharded(a, s, st, ac,
                                                    interpret=False),
        [((K, HEAP), F32), ((K,), I32), ((K, PQ_C), I32),
         ((K, PQ_C), jnp.bool_)]),
    "heap_insert": (
        lambda a, s, v, m: insert_chunk_sharded(a, s, v, m,
                                                interpret=False),
        [((K, HEAP), F32), ((K,), I32), ((K, PQ_C), F32), ((K,), I32)]),
    "sorted_merge": (
        lambda ak, av, kp, bk, bv, bc: merge_compact_sharded(
            ak, av, kp, bk, bv, bc, interpret=False),
        [((K, merge_ops.MAX_PALLAS_SLOTS), F32),
         ((K, merge_ops.MAX_PALLAS_SLOTS), F32),
         ((K, merge_ops.MAX_PALLAS_SLOTS), jnp.bool_),
         ((K, MAP_C), F32), ((K, MAP_C), F32), ((K,), I32)]),
    "label_prop": (
        lambda l, u, v: label_step(l, u, v, n_shards=K, interpret=False),
        [((label_ops.MAX_PALLAS_VERTICES,), I32),
         ((label_ops.MAX_PALLAS_EDGES,), I32),
         ((label_ops.MAX_PALLAS_EDGES,), I32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_structure_kernel_compiles_for_v5e(one_chip, name):
    fn, args = KERNELS[name]
    compiled = _compile(fn, args, one_chip)
    assert "tpu_custom_call" in compiled.as_text(), name


def test_pq_rounds_program_compiles_for_v5e(one_chip):
    """The served PQ's fused multi-round XLA program (R=4 rounds)."""
    from repro.core.sharded_pq import ShardedHeapState, _sharded_rounds_impl

    cap, R = 1 << 18, 4
    state = ShardedHeapState(
        jax.ShapeDtypeStruct((K, cap), F32, sharding=one_chip),
        jax.ShapeDtypeStruct((K,), I32, sharding=one_chip))
    rows = [((R,), I32), ((R, PQ_C), F32), ((R,), I32)]
    compiled = jax.jit(
        _sharded_rounds_impl,
        static_argnames=("c_max", "n_shards", "key_range", "use_pallas",
                         "placement")).lower(
        state, *[jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                 for s, d in rows],
        c_max=PQ_C, n_shards=K, key_range=None, use_pallas=False,
        placement=None).compile()
    assert compiled.memory_analysis().argument_size_in_bytes >= K * cap * 4


@pytest.mark.parametrize("cap", [1 << 16, 1 << 21])
def test_map_apply_pass_compiles_for_v5e(one_chip, cap):
    """The served map's fused mixed-op update pass (XLA bounded-edit
    merge), at 2^16 slots a shard and at the benchmark's 2^21."""
    from repro.core.batched_map import MapState, _apply_impl

    state = MapState(*[jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                       for s, d in (((K, cap + 1), F32), ((K, cap + 1), F32),
                                    ((K,), I32))])
    ops = [((MAP_C,), F32), ((MAP_C,), F32), ((MAP_C,), I32), ((), I32)]
    compiled = jax.jit(
        _apply_impl, static_argnames=("key_range", "use_pallas",
                                      "placement")).lower(
        state, *[jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                 for s, d in ops],
        key_range=(0.0, 100.0), use_pallas=False, placement=None).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_sketch_apply_pass_compiles_for_v5e(one_chip):
    """The sketch's fused add pass (XLA insert-only bounded-edit merge)
    at 2^16 counters a shard."""
    from repro.core.batched_sketch import SketchState, _apply_impl

    cap = 1 << 16
    state = SketchState(*[jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                          for s, d in (((K, cap + 1), F32),
                                       ((K, cap + 1), F32), ((K,), I32))])
    ops = [((MAP_C,), F32), ((MAP_C,), F32), ((), I32)]
    compiled = jax.jit(_apply_impl, static_argnames=("use_pallas",)).lower(
        state, *[jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                 for s, d in ops], use_pallas=False).compile()
    assert "tpu_custom_call" not in compiled.as_text()
