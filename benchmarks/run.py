"""Benchmark entrypoint: ``PYTHONPATH=src python -m benchmarks.run``.

Structure benchmarks AUTO-ENROLL from the workload registry
(``repro.core.substrate``, DESIGN.md §16): every registered
:class:`StructureSpec` with a ``bench`` module contributes one step,
driven by its ``bench_smoke`` quick-sweep argv — registering a new
structure adds its bench row here with zero edits to this file.  The
fixed steps (batch scaling, serving, roofline) follow.  ``--repeats``
plumbs the shared timing discipline (``benchmarks/_timing.py``: warmup +
median-of-N + IQR) through every row.
"""
from __future__ import annotations

import argparse
import importlib
import time

from repro.core import substrate
from repro.launch.compile_cache import use_compile_cache


def main(argv=None) -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=2,
                    help="timed repeats per bench row (median + IQR via "
                         "benchmarks._timing.measure)")
    args = ap.parse_args(argv)
    repeats = args.repeats

    enrolled = [s for s in substrate.specs() if s.bench]
    n_steps = len(enrolled) + 3
    t0 = time.time()

    step = 0
    for spec in enrolled:
        step += 1
        print("=" * 70)
        print(f"[{step}/{n_steps}] {spec.title or spec.name} "
              f"({spec.bench}, registry-enrolled)")
        print("=" * 70)
        mod = importlib.import_module(spec.bench)
        mod.main(list(spec.bench_smoke) + ["--repeats", str(repeats)])

    step += 1
    print("=" * 70)
    print(f"[{step}/{n_steps}] Thm.4 — batched heap cost scaling "
          f"O(c log c + log n)")
    print("=" * 70)
    from .bench_batch_scaling import bench_scaling
    bench_scaling(n_fixed=1 << 13, c_list=(2, 8, 32),
                  n_list=(1 << 10, 1 << 13, 1 << 16))

    step += 1
    print("=" * 70)
    print(f"[{step}/{n_steps}] Serving — PC scheduler vs serial dispatch")
    print("=" * 70)
    from .bench_serving import bench_serving
    bench_serving(session_counts=(1, 4), requests=2, tokens=4,
                  repeats=repeats)

    step += 1
    print("=" * 70)
    print(f"[{step}/{n_steps}] Roofline — measured kernel bandwidth "
          f"(+ dry-run cells when artifacts exist)")
    print("=" * 70)
    from .roofline import main as roofline_main
    roofline_main(repeats=max(repeats, 5))

    print(f"\n[benchmarks] all done in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
