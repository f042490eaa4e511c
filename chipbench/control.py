"""The check's two readings for a cell, on the chip: the program's, and
the control's (the reference one precision lower, in the program's
place), each over several seeds in one process.

    python3 chipbench/control.py --workload map-ycsb-a --seconds 3 \
        --seeds 11 12 13

Prints one JSON line per seed with both sets of numbers.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    _cell, cfg, mix = harness.load_cell(args.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    clock = harness.CompileClock()
    for seed in args.seeds:
        served = harness.serve(cfg, mix, seed=seed, seconds=args.seconds,
                               clock=clock)
        program = harness.check(cfg, served)
        ctrl = harness.control(cfg, served)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "ops": served["window"].ops,
            "program": {k: v for k, (v, _l) in program.items()},
            "program_correct": harness.passed(program),
            "control": {k: v for k, (v, _l) in ctrl.items()},
            "control_correct": harness.passed(ctrl)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
