"""Where an operation's time goes, from the program's own spans: one
traced run of one cell with ``repro.core.tracing.TRACER`` on.

    python3 chipbench/stages.py --workload map-ycsb-c --seed 7 --seconds 51

It serves the cell as ``run.py --trace 1`` does (the same harness, the
same profiler trace of the window) with the program's tracer on, and
clock anchors written into the trace every ``(ramp + seconds) / 13``
seconds in bursts of 4.  It prints one JSON line: the stage metrics
(``programspans``), the stage sums beside what they are compared with,
device 0's idle time by program span, the anchors' offset and bracket,
and the run's phases.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from typing import Any, Dict  # noqa: E402

import numpy as np  # noqa: E402

import harness  # noqa: E402
import programspans as ps  # noqa: E402
import tracereduce  # noqa: E402

BURST, BURSTS = 4, 13


def _anchor_bursts(tracer, period: float, stop: threading.Event) -> None:
    """Anchors in bursts: the bracket of one burst's anchors is narrow
    while the thread holds the interpreter lock.  Those written outside
    the profiler session are not in the trace, and their indices are
    not matched."""
    while not stop.wait(period):
        for _ in range(BURST):
            tracer.anchor()


def traced_serve(cfg: Dict, mix: Dict, *, seed: int, seconds: float,
                 tdir: str, ramp_s: float = harness.RAMP_S,
                 t_start=None, clock=None) -> Dict[str, Any]:
    """``harness.serve`` with a profiler trace and the program's tracer
    on; the trace's summary and the program's records beside it."""
    from repro.core.tracing import TRACER

    stop = threading.Event()
    anchors = threading.Thread(
        target=_anchor_bursts, daemon=True, name="anchors",
        args=(TRACER, (ramp_s + seconds) / BURSTS, stop))
    TRACER.start()
    anchors.start()
    try:
        served = harness.serve(cfg, mix, seed=seed, seconds=seconds,
                               trace_dir=tdir, t_start=t_start,
                               clock=clock, ramp_s=ramp_s)
    finally:
        stop.set()
        anchors.join()
        program = TRACER.stop()
    t_read = time.perf_counter()
    xplane = tracereduce.find_xplane(tdir)
    summary = tracereduce.reduce(tracereduce.load(xplane))
    starts = ps.load_anchors(xplane)
    served["phases"]["trace_read_s"] = time.perf_counter() - t_read
    return dict(served=served, summary=summary, program=program,
                anchor_starts=starts)


def report(run: Dict[str, Any]) -> Dict[str, Any]:
    """The stage metrics, the stage checks and the idle attribution of
    one ``traced_serve``."""
    served, summary, program = run["served"], run["summary"], run["program"]
    starts = run["anchor_starts"]
    off = ps.clock_offset(program["anchors"], starts)
    if off is None:
        return {"error": "no clock anchor in the trace"}
    offset, bracket = off
    # the first half's anchors against the last half's: the drift
    idx = sorted(starts)
    early = ps.clock_offset(program["anchors"],
                            {n: starts[n] for n in idx[:len(idx) // 2]})
    late = ps.clock_offset(program["anchors"],
                           {n: starts[n] for n in idx[len(idx) // 2:]})
    w0, w1 = summary.window
    passes = ps.window_passes(program, w0 - offset, w1 - offset)
    gaps = [(g0, g1) for g0, g1, _lab in summary.gaps]
    n_ops = sum(p.n for p in passes)
    metrics = {
        "combiner.pending_ms": ps.pending_ms(passes),
        "combiner.handoff_ms": ps.handoff_ms(passes),
        "executor.prep_ms": ps.stage_ms(passes, "prep"),
        "executor.dispatch_ms": ps.stage_ms(passes, "dispatch"),
        "executor.fetch_ms": ps.stage_ms(passes, "fetch"),
        "executor.resolve_ms": ps.stage_ms(passes, "resolve"),
        "executor.offcpu_share": ps.offcpu_share(passes),
        "device.idle_waiting_share": ps.idle_waiting_share(
            program, gaps, summary.window, offset)}
    # wall and thread CPU per pass, by span: CPU-bound or waiting
    by_span = {name: [1e-6 * sum(p.wall.get(name, 0) for p in passes)
                      / len(passes),
                      1e-6 * sum(p.cpu.get(name, 0) for p in passes)
                      / len(passes)]
               for name in ps.MAP_SPANS + (ps.RESOLVE,)}
    step_ms = 1e-6 * sum(p.t_end - p.t_start for p in passes) / len(passes)
    map_ms = 1e-6 * sum(p.wall.get(s, 0) for p in passes
                        for s in ps.MAP_SPANS) / len(passes)
    pass_ms = 1e3 * float(np.mean(served["window"].pass_seconds))
    # per operation: each op waits for its whole pass and its resolve
    op_step = 1e-6 * sum(p.n * (p.t_end - p.t_start) for p in passes) / n_ops
    op_res = 1e-6 * sum(p.n * p.wall.get(ps.RESOLVE, 0)
                        for p in passes) / n_ops
    staged = (metrics["combiner.pending_ms"]
              + metrics["combiner.handoff_ms"] + op_step + op_res)
    lat_ms = 1e3 * float(np.mean(served["latencies"]))
    return {
        "passes": len(passes), "ops": n_ops,
        "metrics": metrics,
        "wall_cpu_ms_per_pass": by_span,
        "stage_check": {
            "map_spans_ms_per_pass": map_ms,
            "step_ms_per_pass": step_ms,
            "executor.pass_ms": pass_ms,
            "pass_ms_outside_map_spans": pass_ms - map_ms,
            "share_outside_map_spans": (pass_ms - map_ms) / pass_ms,
            "op_pending_handoff_step_resolve_ms": staged,
            "op_step_ms": op_step, "op_resolve_ms": op_res,
            "op_mean_latency_ms": lat_ms,
            "op_latency_less_stages_ms": lat_ms - staged},
        "idle_by_span_s": ps.idle_by_span(program, gaps, offset),
        "idle_s": summary.window_s - summary.busy_s,
        "window_s": summary.window_s, "busy_s": summary.busy_s,
        "anchors": {"in_trace": len(starts),
                    "written": len(program["anchors"]),
                    "offset_ns": offset, "bracket_ns": bracket,
                    "drift_ns": (late[0] - early[0]
                                 if early and late else None)},
        "spans_recorded": len(program["spans"]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    _cell, cfg, mix = harness.load_cell(args.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("stages: no TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = harness.CompileClock()
    with tempfile.TemporaryDirectory(prefix="chipbench-stages-") as tdir:
        run = traced_serve(cfg, mix, seed=args.seed, seconds=args.seconds,
                           tdir=tdir, t_start=T_START, clock=clock)
    checks = harness.check(cfg, run["served"])
    out = report(run)
    out.update(workload=args.workload, seed=args.seed,
               correct=harness.passed(checks),
               checks={k: v for k, (v, _lim) in checks.items()},
               ops_per_s=run["served"]["window"].ops
               / run["served"]["window"].seconds,
               phases=run["served"]["phases"],
               total_s=time.perf_counter() - T_START,
               device=jax.devices()[0].device_kind)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
