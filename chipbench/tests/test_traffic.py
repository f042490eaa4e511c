"""The traffic generator: YCSB's scrambled Zipfian, seeds, key spaces."""
import numpy as np
import pytest

import traffic


def test_fnvhash64_matches_ycsb():
    # YCSB Utils.fnvhash64: FNV-1a over the 8 low-to-high bytes, abs()
    def ref(v):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h ^= v & 0xFF
            h = (h * 1099511628211) & (2 ** 64 - 1)
            v >>= 8
        h = h - 2 ** 64 if h >= 2 ** 63 else h
        return abs(h)

    vals = np.asarray([0, 1, 2, 255, 256, 123456789, 9999999999], np.int64)
    assert traffic.fnvhash64(vals).tolist() == [ref(int(v)) for v in vals]


def test_zipfian_skew():
    rng = np.random.default_rng(0)
    v = traffic.zipfian(rng, 200_000, 0.99)
    # item 0 has probability 1 / zeta(10^10, 0.99)
    assert np.mean(v == 0) == pytest.approx(1 / 26.46902820178302, rel=0.05)
    assert v.min() >= 0 and v.max() < traffic.YCSB_ITEM_COUNT


def test_keys_are_record_ids_and_hot_ids_spread():
    rng = np.random.default_rng(1)
    keys = traffic.draw_keys({"distribution": "scrambled_zipfian",
                              "theta": 0.99},
                             {"kind": "ids", "count": 1 << 21}, rng, 100_000)
    assert keys.dtype == np.float32
    assert keys.min() >= 1 and keys.max() <= 1 << 21
    assert np.all(keys == np.round(keys))
    ids, counts = np.unique(keys, return_counts=True)
    hot = ids[np.argsort(-counts)[:64]]
    # the 64 hottest ids fall in all four quarters of the key range
    assert len(np.unique((hot - 1) // (1 << 19))) == 4


def test_same_seed_same_stream_other_client_other_stream():
    mix = {"ops": {"lookup": 0.5, "assign": 0.5},
           "keys": {"distribution": "scrambled_zipfian", "theta": 0.99}}
    cfg = {"keys": {"kind": "ids", "count": 1000},
           "values": {"kind": "handle", "bits": 24}}

    def take(client, seed=2 ** 33 + 7):
        g = traffic.op_stream(mix, cfg, seed, 2, client)
        return [next(g) for _ in range(50)]

    assert take(3) == take(3)
    assert take(3) != take(4)
    methods = {m for m, _k, _v in take(3)}
    assert methods == {"lookup", "assign"}
    assert all(v == int(v) and 0 <= v < 2 ** 24 for _m, _k, v in take(3))


def test_float_keys_uniform():
    rng = np.random.default_rng(2)
    k = traffic.draw_keys({"distribution": "uniform"},
                          {"kind": "float", "lo": -1000.0, "hi": 1000.0},
                          rng, 10_000)
    assert k.dtype == np.float32 and k.min() >= -1000 and k.max() < 1000
