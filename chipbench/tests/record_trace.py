"""Record the trace reduction's test fixture.  Run on the chip:

    python3 chipbench/tests/record_trace.py chipbench/tests/data/v5e.json.gz

It serves ``map-ycsb-a`` at the tests' small size for a fraction of a
second with the profiler on, keeps what ``tracereduce.load`` reads of the
``.xplane.pb`` (host spans, device op intervals, program executions) as
gzipped JSON, and pins the reduction's numbers beside it.
"""
import dataclasses
import gzip
import json
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent), str(HERE.parents[1] / "src")]

import harness  # noqa: E402
import tiny  # noqa: E402
import tracereduce  # noqa: E402


def fixture(xplane: str, out: str) -> dict:
    t = tracereduce.load(xplane)
    s = tracereduce.reduce(t)
    pinned = {"window_s": s.window_s, "busy_s": s.busy_s,
              "program_s": s.program_s, "program_n": s.program_n,
              "idle_by_label": s.idle_by_label(), "gaps": len(s.gaps)}
    with gzip.open(out, "wt") as f:
        json.dump({"trace": dataclasses.asdict(t), "pinned": pinned}, f)
    return pinned


def main(out: str) -> int:
    cfg, mix = tiny.cell("map-ycsb-a")
    with tempfile.TemporaryDirectory() as tdir:
        harness.serve(cfg, mix, seed=11, seconds=0.05, trace_dir=tdir,
                      ramp_s=0.05)
        print(json.dumps(fixture(tracereduce.find_xplane(tdir), out)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
