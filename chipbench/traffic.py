"""The one traffic generator: every mix is a data file of parameters.

A mix (``traffic/<name>.json``) names its closed-loop client count, its
operation mix and the distribution its keys are drawn from.  The key and
value spaces come from the configuration (``keys``, ``values``), so one mix
can run against any deployment whose structure knows its operations.

Each client draws its own stream from ``(seed, stream, client)``: the same
seed gives every client the same operations, whatever the timing.
"""
from __future__ import annotations

from typing import Dict, Iterator, Tuple

import numpy as np

# YCSB's ScrambledZipfianGenerator: a Zipfian over 10^10 items with the
# zeta constant precomputed for theta 0.99, hashed (FNV-1a 64) and folded
# onto the record count, so popular ids are spread over the key space
# (site.ycsb.generator.ScrambledZipfianGenerator, ZipfianGenerator).
YCSB_ITEM_COUNT = 10_000_000_000
YCSB_ZETAN = {0.99: 26.46902820178302}
FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)

CHUNK = 4096


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64`` of non-negative int64 values."""
    v = v.astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * FNV_PRIME_64
            v = v >> np.uint64(8)
    return np.abs(h.view(np.int64))


def zipfian(rng: np.random.Generator, n: int, theta: float) -> np.ndarray:
    """``ZipfianGenerator.nextLong(10^10)`` (Gray et al.), ``n`` draws."""
    if theta not in YCSB_ZETAN:
        raise ValueError(f"no precomputed zeta for theta {theta}")
    zetan = YCSB_ZETAN[theta]
    items = YCSB_ITEM_COUNT
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(n)
    uz = u * zetan
    v = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    v = np.where(uz < 1.0 + 0.5 ** theta, 1, v)
    return np.where(uz < 1.0, 0, v)


def draw_keys(spec: Dict, space: Dict, rng: np.random.Generator,
              n: int) -> np.ndarray:
    """``n`` f32 keys from the mix's ``keys`` spec over the config's space.

    ``space`` is ``{"kind": "ids", "count": N}`` (record ids 1..N, exact in
    f32 below 2^24) or ``{"kind": "float", "lo": a, "hi": b}``."""
    dist = spec["distribution"]
    if space["kind"] == "ids":
        count = int(space["count"])
        if count >= 1 << 24:
            raise ValueError("record ids past 2^24 are not exact in f32")
        if dist == "scrambled_zipfian":
            idx = fnvhash64(zipfian(rng, n, spec["theta"])) % count
        elif dist == "uniform":
            idx = rng.integers(0, count, n)
        else:
            raise ValueError(f"unknown key distribution {dist!r}")
        return (idx + 1).astype(np.float32)
    if space["kind"] == "float":
        if dist != "uniform":
            raise ValueError(f"float keys take a uniform distribution, "
                             f"not {dist!r}")
        return rng.uniform(space["lo"], space["hi"], n).astype(np.float32)
    raise ValueError(f"unknown key space {space['kind']!r}")


def draw_values(space: Dict, rng: np.random.Generator,
                n: int) -> np.ndarray:
    """``n`` f32 values: ``{"kind": "handle", "bits": b}`` draws integer
    record handles in [0, 2^b), exact in f32 for b <= 24."""
    if space["kind"] != "handle" or not 0 < int(space["bits"]) <= 24:
        raise ValueError(f"unknown value space {space!r}")
    return rng.integers(0, 1 << int(space["bits"]), n).astype(np.float32)


def op_stream(traffic: Dict, cfg: Dict, seed: int, stream: int,
              client: int) -> Iterator[Tuple[str, float, float]]:
    """Endless ``(method, key, value)`` draws for one client."""
    methods = sorted(traffic["ops"])
    p = np.asarray([traffic["ops"][m] for m in methods], np.float64)
    if not np.isclose(p.sum(), 1.0):
        raise ValueError(f"op shares sum to {p.sum()}, not 1")
    rng = np.random.default_rng([seed, stream, client])
    while True:
        kind = rng.choice(len(methods), CHUNK, p=p / p.sum())
        keys = draw_keys(traffic["keys"], cfg["keys"], rng, CHUNK)
        vals = (draw_values(cfg["values"], rng, CHUNK)
                if "values" in cfg else np.zeros(CHUNK, np.float32))
        yield from zip([methods[k] for k in kind], keys.tolist(),
                       vals.tolist())
