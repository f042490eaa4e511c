"""A configuration and mix at a size the CPU serves in seconds: the same
structure, key space kind, scheduler and operation mix, fewer records,
slots and clients."""
import json

import harness

SIZES = {"map": dict(records=2000, capacity=1024, n_shards=4),
         "pq": dict(records=2000, capacity=1024, n_shards=4)}
CLIENTS = 8
# (configuration, traffic) by cell; pq-5050 is not in BENCHMARK.json yet
CELLS = {"map-ycsb-a": ("map-kv32-4m", "ycsb-a"),
         "pq-5050": ("pq-2m", "pq-5050"),
         "map-ycsb-c": ("map-kv32-4m", "ycsb-c")}


def cell(name):
    config, traffic = CELLS[name]
    cfg = json.loads((harness.HERE / "configs" / f"{config}.json")
                     .read_text())
    mix = json.loads((harness.HERE / "traffic" / f"{traffic}.json")
                     .read_text())
    sz = SIZES[cfg["structure"]]
    cfg = dict(cfg, **sz)
    if cfg["keys"]["kind"] == "ids":
        cfg["keys"] = dict(cfg["keys"], count=sz["records"])
        cfg["key_range"] = [0.0, sz["records"] + 1.0]
    return cfg, dict(mix, clients=CLIENTS)
