"""Roofline analysis: measured kernel bandwidth + analytic model cells.

Two modes, both emitted to ``experiments/roofline.{md,json}``:

* **Kernel-bandwidth mode** (always runs; EXPERIMENTS.md §Roofline):
  times the combining kernels' XLA twins — ``heap_kmin`` (frontier
  search), ``sorted_merge`` (merge-compact), ``label_prop`` (one label
  iteration) — and reports achieved vs *measured* peak bandwidth.  The
  peak is the host stream-copy bandwidth measured on THIS container,
  not a device datasheet constant: on the XLA:CPU backend the v5e
  numbers below would make every fraction meaningless.  The XLA twins
  are what the CPU backend actually executes on the combining hot path
  (the Pallas kernels only run compiled on TPU; ``interpret=True``
  times the emulator, not the kernel), so these fractions steer kernel
  work with real data instead of CPU-container noise.

* **Dry-run cell mode** (opportunistic — needs ``repro.launch.dryrun``
  artifacts): three analytic terms per (arch × shape × mesh) cell —
  compute = FLOPs / (chips × 197e12), memory = HBM bytes/dev / 819e9,
  collective = link traffic/dev / 50e9 (TPU v5e: 197 TFLOP/s bf16,
  819 GB/s HBM, ~50 GB/s/link ICI), collectives parsed from the
  compiled HLO.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import time
from typing import Callable, Dict, List, Tuple

from benchmarks.flops import cell_cost

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
LINK_BW = 50e9               # bytes/s / link (ICI)

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..",
                          "experiments", "dryrun")


def analyse_cell(rec: Dict) -> Dict:
    arch, shape, mesh = rec["arch"], rec["shape"], rec["mesh"]
    chips = rec["n_devices"]
    cost = cell_cost(arch, shape, n_chips=chips)

    t_compute = cost.flops_total / (chips * PEAK_FLOPS)
    t_memory = cost.hbm_bytes_per_dev / HBM_BW
    t_coll = rec["collectives"]["traffic_bytes_per_device"] / LINK_BW

    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    # roofline fraction: useful-FLOPs time over the bound term
    t_model = cost.model_flops / (chips * PEAK_FLOPS)
    frac = t_model / bound if bound > 0 else 0.0

    return {
        "cell": rec["cell"], "arch": arch, "shape": shape, "mesh": mesh,
        "chips": chips,
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops": cost.model_flops,
        "hlo_flops_raw_per_dev": rec.get("flops", 0.0),
        "analytic_flops_total": cost.flops_total,
        "useful_ratio": cost.model_flops / max(cost.flops_total, 1.0),
        "roofline_fraction": frac,
        "mem_args_gib": rec["memory"]["argument_size_in_bytes"] / 2**30,
        "mem_temp_gib": rec["memory"]["temp_size_in_bytes"] / 2**30,
        "coll_count": rec["collectives"]["count"],
        "coll_by_kind": rec["collectives"]["by_kind"],
    }


def _fmt_s(x: float) -> str:
    if x >= 1e-3:
        return f"{x*1e3:.1f}ms"
    return f"{x*1e6:.0f}us"


# ---------------------------------------------------------------------------
# Kernel-bandwidth mode (PR 9): achieved vs MEASURED peak bandwidth of the
# combining kernels' XLA twins (see module docstring for why twins + why a
# measured peak)
# ---------------------------------------------------------------------------
def _median_time(fn: Callable[[], object], *, repeats: int = 15,
                 warmup: int = 3) -> float:
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def host_copy_bandwidth(mib: int = 64) -> float:
    """Measured host stream-copy bandwidth (bytes/s, read+write): the
    honest 'peak' for the backend this container runs on."""
    import numpy as np

    a = np.zeros(mib * 2**20 // 8, np.float64)
    t = _median_time(lambda: a.copy(), repeats=9, warmup=2)
    return 2 * a.nbytes / t


def kernel_cases() -> List[Tuple[str, str, int, Callable[[], object]]]:
    """(kernel, config, bytes_moved, jitted thunk) per combining kernel.

    ``bytes_moved`` is the minimal array footprint — every input array
    read once plus every output written once.  Gather/scatter traffic and
    scan temporaries are NOT counted, so ``achieved/peak`` is a lower
    bound on how hard the kernel drives the memory system."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.batched_pq import _k_smallest
    from repro.kernels.label_prop.ops import label_step_xla
    from repro.kernels.sorted_merge.ops import merge_edits_xla

    rng = np.random.default_rng(0)
    cases: List[Tuple[str, str, int, Callable[[], object]]] = []

    # heap_kmin: K-shard frontier search (PQ combining phase 1).  A
    # sorted ascending run is a valid 1-indexed min-heap (parent index <
    # child index ⇒ parent value ≤ child value); slot 0 is scratch.
    K, cap, c_max = 4, 1 << 15, 64
    heaps = jnp.asarray(
        np.sort(rng.random((K, cap)).astype(np.float32), axis=1))
    sizes = jnp.full((K,), cap - 1, jnp.int32)
    kmin = jax.jit(jax.vmap(
        lambda a, s: _k_smallest(a, s, jnp.int32(c_max), c_max)))
    jax.block_until_ready(kmin(heaps, sizes))
    cases.append((
        "heap_kmin", f"K={K} cap={cap} c_max={c_max}",
        K * cap * 4 + K * c_max * 8,
        lambda: jax.block_until_ready(kmin(heaps, sizes))))

    # sorted_merge: one bounded-edit merge (the map's update pass).
    # Evens in the sorted run, odds in the insert run — disjoint, both
    # strictly increasing; the last C slots of A deleted so the merge
    # fits N.
    N, C = 1 << 15, 64
    a_keys = jnp.asarray((np.arange(N) * 2.0).astype(np.float32))
    a_vals = a_keys + 0.5
    a_size = jnp.int32(N)
    d_slots = jnp.arange(N - C, N, dtype=jnp.int32)
    b_keys = jnp.asarray((np.arange(C) * 2.0 + 1.0).astype(np.float32))
    b_vals = b_keys + 0.5
    b_count = jnp.int32(C)
    merge = jax.jit(merge_edits_xla)
    jax.block_until_ready(merge(a_keys, a_vals, a_size, d_slots, b_keys,
                                b_vals, b_count))
    cases.append((
        "sorted_merge", f"N={N} C={C}",
        2 * N * 4 + C * 4 + 2 * C * 4 + 2 * N * 4,
        lambda: jax.block_until_ready(
            merge(a_keys, a_vals, a_size, d_slots, b_keys, b_vals,
                  b_count))))

    # label_prop: one scatter-min + pointer-jump iteration (graph full
    # rebuild inner step) over a random edge multiset.
    n, E = 1 << 14, 1 << 15
    labels = jnp.arange(n, dtype=jnp.int32)
    eu = jnp.asarray(rng.integers(n, size=E).astype(np.int32))
    ev = jnp.asarray(rng.integers(n, size=E).astype(np.int32))
    lstep = jax.jit(label_step_xla)
    jax.block_until_ready(lstep(labels, eu, ev))
    cases.append((
        "label_prop", f"n={n} E={E}",
        n * 4 + 2 * E * 4 + n * 4,
        lambda: jax.block_until_ready(lstep(labels, eu, ev))))
    return cases


def kernel_roofline(repeats: int = 15) -> Dict:
    """Time every kernel case; returns the JSON-ready payload."""
    peak = host_copy_bandwidth()
    rows = []
    for name, cfg, nbytes, thunk in kernel_cases():
        t = _median_time(thunk, repeats=repeats)
        bw = nbytes / t
        rows.append({
            "kernel": name, "config": cfg, "bytes": nbytes,
            "median_s": t, "achieved_gbs": round(bw / 1e9, 3),
            "peak_gbs": round(peak / 1e9, 3),
            "fraction": round(bw / peak, 4),
        })
    return {"peak_gbs": round(peak / 1e9, 3), "kernels": rows}


def build_kernel_table(payload: Dict) -> str:
    rows = ["| kernel | config | bytes/call | median | achieved GB/s | "
            "peak GB/s | fraction |",
            "|---|---|---|---|---|---|---|"]
    for r in payload["kernels"]:
        rows.append(
            f"| {r['kernel']} | {r['config']} | {r['bytes']} "
            f"| {_fmt_s(r['median_s'])} | {r['achieved_gbs']:.2f} "
            f"| {r['peak_gbs']:.2f} | {r['fraction']:.3f} |")
    return "\n".join(rows)


def build_table(records: List[Dict]) -> str:
    rows = ["| cell | compute | memory | collective | dominant | useful | "
            "roofline-frac | args GiB | temp GiB |",
            "|---|---|---|---|---|---|---|---|---|"]
    for r in records:
        rows.append(
            f"| {r['arch']}·{r['shape']}·{r['mesh']} "
            f"| {_fmt_s(r['t_compute_s'])} | {_fmt_s(r['t_memory_s'])} "
            f"| {_fmt_s(r['t_collective_s'])} | **{r['dominant']}** "
            f"| {r['useful_ratio']:.2f} | {r['roofline_fraction']:.2f} "
            f"| {r['mem_args_gib']:.2f} | {r['mem_temp_gib']:.2f} |")
    return "\n".join(rows)


def main(dryrun_dir: str = DRYRUN_DIR, mesh_filter: str = "16x16",
         out: str = None, repeats: int = 15):
    # kernel-bandwidth mode: always runs (it needs only this container)
    payload = kernel_roofline(repeats=repeats)
    ktable = build_kernel_table(payload)
    print(f"measured host copy bandwidth: {payload['peak_gbs']:.2f} GB/s")
    print(ktable)
    sections = [
        "# Roofline", "",
        "## Combining kernels — achieved vs measured peak bandwidth", "",
        f"Peak = host stream-copy bandwidth measured on this container "
        f"({payload['peak_gbs']:.2f} GB/s); bytes = minimal array "
        f"footprint (inputs read once + outputs written once).", "",
        ktable,
    ]
    # dry-run cell mode: opportunistic (needs launch.dryrun artifacts)
    recs = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        rec = json.load(open(path))
        if rec.get("status") != "ok":
            continue
        if mesh_filter and rec["mesh"] != mesh_filter:
            continue
        recs.append(analyse_cell(rec))
    payload["cells"] = recs
    if recs:
        table = build_table(recs)
        print(table)
        sections += ["", "## Dry-run cells (analytic, TPU v5e)", "", table]
        from collections import Counter
        doms = Counter(r["dominant"] for r in recs)
        print(f"\n{len(recs)} cells; dominant terms: {dict(doms)}")
        worst = sorted(recs, key=lambda r: r["roofline_fraction"])[:5]
        print("worst roofline fractions:",
              [(r["cell"], round(r["roofline_fraction"], 3))
               for r in worst])
    else:
        print("[roofline] no dry-run artifacts — kernel mode only "
              "(run `python -m repro.launch.dryrun --all --mesh both` "
              "for the cell table)")
    out = out or os.path.join(dryrun_dir, "..", "roofline.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    json.dump(payload, open(out, "w"), indent=1)
    with open(os.path.join(os.path.dirname(out), "roofline.md"), "w") as f:
        f.write("\n".join(sections) + "\n")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--dir", default=DRYRUN_DIR)
    ap.add_argument("--repeats", type=int, default=15)
    args = ap.parse_args()
    main(args.dir, args.mesh, repeats=args.repeats)
