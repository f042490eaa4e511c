"""On-chip benchmark: one run of one cell.

    python3 chipbench/run.py --workload map-ycsb-a --seed 7 --seconds 10 \
        --trace 0

Cells, configurations and metrics are named in ``BENCHMARK.json`` at the
root of the checkout.  The run builds the cell's state from the seed,
warms every batch shape its traffic uses, serves the traffic closed-loop
through ``PCScheduler`` for ``--seconds``, checks every answer against the
plain reference, and prints one JSON line last on standard output:
end-to-end metrics with ``--trace 0``, per-layer metrics (from a profiler
trace of the window) with ``--trace 1``.  The numbers the check compared
are printed beside their limits as the last lines on standard error and
under ``checks``, the line's last key.

It exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the device is not in ``peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import harness  # noqa: E402
import readers  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = harness.load_benchmark()
    cell, cfg, mix = harness.load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU (JAX found {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    peaks = json.loads((harness.HERE / "peaks.json").read_text())
    if kind not in peaks:
        print(f"chipbench: device kind {kind!r} is not in peaks.json",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    # every program goes to the persistent cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = harness.CompileClock()

    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
        served = harness.serve(cfg, mix, seed=args.seed,
                               seconds=args.seconds,
                               trace_dir=tdir if args.trace else None,
                               t_start=T_START, clock=clock)
        summary = None
        if args.trace:
            import tracereduce as _trace

            t_read = time.perf_counter()
            summary = _trace.summarize(tdir)
            served["phases"]["trace_read_s"] = time.perf_counter() - t_read
            served["window"].trace = summary
    t_check = time.perf_counter()
    checks = harness.check(cfg, served)
    t_check = time.perf_counter() - t_check
    correct = harness.passed(checks)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": cell["chips"],
              "memory_peak_bytes": served["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": harness.attempted(served),
              "failed": checks["ops_failed"][0]}
    if args.trace:
        metrics = {}
        for m in bench["per_layer"]:
            if args.workload not in m.get("workloads", [args.workload]):
                continue
            v = readers.load(m["name"]).read(served["window"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result.update(metrics=metrics, device=device,
                      breakdown=harness.breakdown(summary))
    else:
        e2e = harness.end_to_end(served)
        result.update(metrics={
            m["name"]: {"value": e2e[m["name"]][0], "unit": m["unit"]}
            for m in bench["end_to_end"]
            if args.workload in m.get("workloads", [args.workload])
            and m["name"] in e2e}, device=device)
    result["window"] = {
        "seconds": served["window"].seconds, "ops": served["window"].ops,
        "compile_s": served["compile_s"],
        "window_compile_events": served["window_compile_events"],
        "deadline_pq_dispatches": served["pq_dispatches"]}
    result["phases"] = dict(served["phases"], check_s=t_check)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k} {v} limit {lim}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
