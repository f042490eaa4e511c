"""Technique-in-framework: PC serving scheduler vs serial dispatch.

The production claim (DESIGN.md §3): under concurrent sessions, the
parallel-combining scheduler turns N per-request device dispatches into
~N/batch combined dispatches, with the batched-PQ deadline ordering.
Measures requests/s and device-step counts for the serial baseline, the
async PC scheduler with blocking submits ("pc"), the fully non-blocking
``submit_async`` client path ("pc-async"), and the zero-copy ablation
("pc-nodonate": the deadline PQ copies its heap buffers every combining
pass — EXPERIMENTS §Ablations) over the reduced qwen2 model.  The
"pc-pallas" mode (PQ through the shard-grid kernels, DESIGN.md §10) is
opt-in via ``schedulers=``, not in the default run — Pallas interpret
mode on a CPU backend is too slow for a benchmark row.

``--workload <structure>`` serves ANY registered batched structure
(``repro.core.substrate``, DESIGN.md §16 — graph, map, pq, sketch,
unionfind, ...) through the same schedulers via the generic
``StructureExecutor`` with ``--read-pct`` read share; rows land in
bench_serving_<structure>.json.
"""
from __future__ import annotations

import argparse

from repro.core import substrate
from repro.launch.serve import run_serving

from ._timing import median_iqr
from .common import save


def bench_serving(arch="qwen2_0_5b", session_counts=(1, 2, 4, 8),
                  requests=3, tokens=6, max_batch=8,
                  schedulers=("serial", "pc", "pc-async", "pc-nodonate"),
                  workload="decode", read_pct=90, repeats=5):
    """Each cell runs ``repeats`` times after one warmup run; the row is
    the median-``req_per_s`` sample with the IQR attached (the
    ``benchmarks._timing`` discipline — ``run_serving`` owns its own wall
    clock, so the median is taken over whole serving runs)."""
    results = []
    for sched in schedulers:
        for s in session_counts:
            def cell():
                return run_serving(arch, sessions=s,
                                   requests_per_session=requests,
                                   n_tokens=tokens, max_batch=max_batch,
                                   scheduler=sched, seed=42,
                                   workload=workload, read_pct=read_pct,
                                   reduced=True)

            cell()                                    # warmup
            samples = sorted((cell() for _ in range(repeats)),
                             key=lambda st: st["req_per_s"])
            # lower-middle sample: with an even count the upper-middle
            # would systematically report the better run as "median"
            stats = samples[(len(samples) - 1) // 2]
            spread = median_iqr(st["req_per_s"] for st in samples)
            stats["iqr"] = round(spread["iqr"], 2)
            stats["sessions"] = s
            results.append(stats)
            print(f"[serving] {workload} {sched:8s} sessions={s}: "
                  f"{stats['req_per_s']:6.2f} req/s "
                  f"(iqr {stats['iqr']}), "
                  f"{stats['device_steps']:4d} device steps, "
                  f"mean batch {stats['mean_batch']}")
    name = "bench_serving" if workload == "decode" \
        else f"bench_serving_{workload}"
    save(name, results)
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sessions", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--tokens", type=int, default=6)
    ap.add_argument("--workload",
                    choices=["decode"] + substrate.names(),
                    default="decode")
    ap.add_argument("--read-pct", type=int, default=90)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--repeats", type=int, default=5,
                    help="timed repeats per cell (median + IQR reported)")
    a = ap.parse_args(argv)
    bench_serving(session_counts=tuple(a.sessions), tokens=a.tokens,
                  workload=a.workload, read_pct=a.read_pct,
                  requests=a.requests, repeats=a.repeats)


if __name__ == "__main__":
    main()
