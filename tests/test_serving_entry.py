"""The serving entry point's contract: failures reach the caller, every
request is answered, the structure's counts add up, and the compile cache
is placed from outside."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.serve import run_serving

ROOT = Path(__file__).resolve().parent.parent


def test_failing_session_raises_from_run_serving():
    """A pass the structure refuses (a 2-slot heap overflows) fails the
    session; run_serving re-raises it instead of printing stats."""
    with pytest.raises(ValueError, match="capacity"):
        run_serving(workload="pq", scheduler="pc", sessions=2,
                    requests_per_session=6, read_pct=0, seed=1,
                    structure_kw=dict(capacity=2, n_shards=1, c_max=4))


def test_failing_session_fails_the_cli(tmp_path):
    """``python -m repro.launch.serve`` exits non-zero, printing no stats,
    when a session fails (the PQ's served sizing shrunk to 2 slots)."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    cmd = [sys.executable, "-c",
           "import sys; from repro.launch import serve; "
           "serve.substrate.get('pq').extras['serve_kw'].update("
           "capacity=2, n_shards=1, c_max=4); sys.argv[1:] = ["
           "'--workload', 'pq', '--sessions', '2', '--requests', '6', "
           "'--read-pct', '0']; serve.main()"]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "[serve]" not in proc.stdout
    assert "capacity" in proc.stderr


@pytest.mark.parametrize("workload", ["pq", "map", "graph"])
def test_served_counts_add_up(workload):
    """Every request answered; final size = preloaded + successful
    inserts - successful removals (PQ inserts always land)."""
    kw = {"pq": dict(values=[float(v) for v in range(40)]),
          "map": dict(items=[(float(k), 1.0) for k in range(1, 40)]),
          "graph": dict(edges=[(i, i + 1) for i in range(20)])}[workload]
    live = {"pq": 40, "map": 39, "graph": 20}[workload]
    st = run_serving(workload=workload, scheduler="pc", sessions=4,
                     requests_per_session=6, read_pct=30, seed=3,
                     n_vertices=64, structure_kw=kw)
    assert st["answered"] == st["requests"] == 24
    sent = {m: t[0] for m, t in st["ops"].items()}
    done = {m: t[1] for m, t in st["ops"].items()}
    assert sum(sent.values()) == 24
    if workload == "pq":
        want = live + sent.get("insert", 0) - done.get("extract_min", 0)
    else:
        want = live + done.get("insert", 0) - done.get("delete", 0)
    assert st["final_size"] == want


def test_compile_cache_defers_to_environment(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing; without
    it the cache goes to the fixed .jax_cache/ of the checkout."""
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
