"""Chip smoke test: the served path at deployment size on a TPU, every
answer checked against a plain reference.

    python chip_smoke.py             # one chip: pq, pc-pallas pq, map,
                                     # graph, decode (qwen2_0_5b)
    python chip_smoke.py --chips 4   # four chips: pq, map and graph placed
                                     # on a D=4 mesh vs the stacked layout

Everything runs in this one process, through ``repro.launch.serve.
run_serving`` with the ``PCScheduler``; traffic comes from the structure
registry's seeded generators and the state is preloaded at the sizes in
``SIZES``.  Each phase prints one JSON line (sizes, wall and compile
seconds, device kind, peak device bytes).  The last line of standard
output is ``{"ok": true, "device": {...}}``; with no TPU, a failed phase
or a wrong answer, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# deployment sizes per phase (the structures' own shapes, sized for one
# 16 GB chip; K=4 shards so the same state places 1:1 on a 4-chip mesh)
SIZES = {
    "pq": dict(n_shards=4, capacity=1 << 20, c_max=16, live=1 << 21),
    "map": dict(n_shards=4, capacity=1 << 20, c_max=64, live=1 << 21),
    "graph": dict(n=1 << 20, live=1 << 22, edge_capacity=1 << 23,
                  c_max=64, n_shards=4),
    "decode": dict(arch="qwen2_0_5b", sessions=4, requests=2, tokens=4,
                   prompt_len=16, max_batch=8),
}
# served traffic per structure phase: sessions x requests, read share
TRAFFIC = dict(sessions=8, requests=8)
READ_PCT = {"pq": 10, "map": 50, "graph": 90}
# reference pass: rounds of (one c_max update batch, a few reads)
REF_ROUNDS, REF_READS = 4, 2
# a prompt's prefill logits in a batch of max_batch vs alone (batch 1),
# as the relative L2 error ||batched - alone|| / ||alone||.  The weights
# and activations are bf16 through 24 layers and the TPU compiler tiles
# the dots of a batch of 8 and of 1 differently, so single logits move by
# about 1% of their scale (on a v5e ~0.6% of entries exceed the
# element-wise 2e-2 that tests/test_models.py holds the reduced config
# to); a row mixed up with another row's prompt or cache is off by ~100%.
LOGIT_TOL = 5e-2


def expect(ok, *what):
    """A check that stays under ``python -O`` (unlike ``assert``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, plus persistent
    cache hits, from JAX's own monitoring events."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name in self.EVENTS:
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def report(phase, sizes, t0, clock, c0, h0, **extra):
    import jax

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    print(json.dumps(dict(
        phase=phase, sizes=sizes,
        wall_s=round(time.perf_counter() - t0, 3),
        compile_s=round(clock.seconds - c0, 3),
        cache_hits=clock.cache_hits - h0,
        device_kind=dev.device_kind,
        peak_bytes_in_use=stats.get("peak_bytes_in_use"), **extra)),
        flush=True)


# ---------------------------------------------------------------------------
# Structure phases
# ---------------------------------------------------------------------------
def preload(name, sz, rng):
    """Seeded initial contents: ``(constructor kwargs, live count)``."""
    live = sz["live"]
    if name == "pq":
        return dict(values=rng.uniform(-1000.0, 1000.0, live)
                    .astype(np.float32)), live
    if name == "map":
        keys = np.unique(rng.uniform(0.0, 100.0, live + live // 8)
                         .astype(np.float32))
        keys = rng.permutation(keys[keys > 0])[:live]
        vals = rng.uniform(-50.0, 50.0, live).astype(np.float32)
        expect(keys.size == live)
        return dict(items=list(zip(keys.tolist(), vals.tolist()))), live
    n = sz["n"]
    uv = rng.integers(0, n, (live + live // 8, 2), dtype=np.int64)
    u, v = uv.min(axis=1), uv.max(axis=1)
    code = np.unique(u[u != v] * n + v[u != v])
    code = rng.permutation(code)[:live]
    expect(code.size == live)
    return dict(edges=np.stack([code // n, code % n], axis=1)), live


def structure_kw(name, sz, loaded, **kw):
    """Constructor kwargs of the phase's structure."""
    if name == "graph":
        return dict(n=sz["n"], edge_capacity=sz["edge_capacity"],
                    c_max=sz["c_max"], n_shards=sz["n_shards"], **loaded,
                    **kw)
    return dict(capacity=sz["capacity"], c_max=sz["c_max"],
                n_shards=sz["n_shards"], **loaded, **kw)


def reference_pass(spec, ds, sz, seed):
    """Apply seeded update/read batches to ``ds`` and to the spec's host
    reference, compare every answer with ``spec.result_ok`` and the final
    states with ``spec.dump_compare``.  Returns the structure's answers."""
    host = spec.make_host(ds)
    rng = np.random.default_rng(seed)
    ctx = spec.new_ctx()
    if "n" in sz:
        ctx["n"] = sz["n"]
    answers = []
    for _ in range(REF_ROUNDS):
        for gen, k in ((spec.gen_update, sz["c_max"]),
                       (spec.gen_read, REF_READS)):
            methods, inputs = gen(rng, k, ctx)
            if gen is spec.gen_update:
                got = ds.update_batch(methods, inputs)
                want = (host.update_batch(methods, inputs)
                        if hasattr(host, "update_batch")
                        else [host.apply(m, i)
                              for m, i in zip(methods, inputs)])
            else:
                got = ds.read_batch(methods, inputs)
                want = [host.apply(m, i) for m, i in zip(methods, inputs)]
            expect(len(got) == len(want) == len(methods))
            for m, g, w in zip(methods, got, want):
                expect(spec.result_ok(m, g, w), (spec.name, m, g, w))
            answers.append(got)
    spec.dump_compare(ds, host)
    return answers


def serve_and_count(name, sz, loaded, live, seed, **serve_kw):
    """One concurrent ``run_serving`` pass; every request must be
    answered and the final size must equal the live count plus the
    successful inserts minus the successful removals."""
    from repro.launch.serve import run_serving

    kw = structure_kw(name, sz, loaded)
    n = kw.pop("n", 512)
    st = run_serving(workload=name, scheduler=serve_kw.pop("scheduler",
                                                           "pc"),
                     sessions=TRAFFIC["sessions"],
                     requests_per_session=TRAFFIC["requests"],
                     read_pct=READ_PCT[name], seed=seed, n_vertices=n,
                     structure_kw=kw, **serve_kw)
    expect(st["answered"] == st["requests"], st)
    ops = st["ops"]
    sent = {m: t[0] for m, t in ops.items()}
    done = {m: t[1] for m, t in ops.items()}
    if name == "pq":
        want = live + sent.get("insert", 0) - done.get("extract_min", 0)
    else:
        want = live + done.get("insert", 0) - done.get("delete", 0)
    expect(st["final_size"] == want, (st["final_size"], want, ops))
    return {k: st[k] for k in ("requests", "answered", "final_size",
                                "ops", "mean_batch", "device_steps")
            if k in st} | ({"mesh_devices": st["mesh_devices"]}
                           if "mesh_devices" in st else {})


def structure_phase(name, clock, seed, *, pallas_twin=False):
    """Reference pass + served pass of one structure on one chip.  With
    ``pallas_twin`` (pq only) the same batches then run through the
    Pallas kernels and must give the XLA pass's answers bit for bit."""
    from repro.core import substrate

    spec = substrate.get(name)
    sz = SIZES[name]
    rng = np.random.default_rng(seed)
    t0, c0, h0 = time.perf_counter(), clock.seconds, clock.cache_hits
    loaded, live = preload(name, sz, rng)
    ds = spec.make(**structure_kw(name, sz, loaded))
    answers = reference_pass(spec, ds, sz, seed + 1)
    del ds
    served = serve_and_count(name, sz, loaded, live, seed + 2)
    report(name, sz, t0, clock, c0, h0, served=served)
    if not pallas_twin:
        return
    import jax.numpy as jnp

    from repro.core.sharded_pq import sharded_apply_batch

    t0, c0, h0 = time.perf_counter(), clock.seconds, clock.cache_hits
    ds = spec.make(**structure_kw(name, sz, loaded, use_pallas=True))
    lowered = sharded_apply_batch.lower(
        ds.state, jnp.int32(0), jnp.zeros((sz["c_max"],), jnp.float32),
        jnp.int32(0), c_max=sz["c_max"], n_shards=sz["n_shards"],
        key_range=None, use_pallas=True, placement=None)
    expect("tpu_custom_call" in lowered.as_text(), "no Pallas kernel")
    pallas_answers = reference_pass(spec, ds, sz, seed + 1)
    expect(pallas_answers == answers, "pallas and XLA answers differ")
    del ds
    served = serve_and_count(name, sz, loaded, live, seed + 2,
                             scheduler="pc-pallas")
    report("pq-pallas", sz, t0, clock, c0, h0, served=served,
           tpu_custom_call=True, same_answers_as_xla=True)


def mesh_phase(name, clock, seed, n_devices):
    """Stacked vs ``MeshPlacement`` on ``n_devices`` chips: the same
    seeded batches must give identical answers (each also checked
    against the host reference), and the served pass must report the
    mesh it ran on."""
    from repro.core import placement as _placement
    from repro.core import substrate
    from repro.launch.mesh import make_combining_mesh

    spec = substrate.get(name)
    sz = SIZES[name]
    rng = np.random.default_rng(seed)
    t0, c0, h0 = time.perf_counter(), clock.seconds, clock.cache_hits
    loaded, live = preload(name, sz, rng)
    stacked = reference_pass(spec, spec.make(**structure_kw(name, sz,
                                                            loaded)),
                             sz, seed + 1)
    mesh = _placement.MeshPlacement(make_combining_mesh(sz["n_shards"]))
    expect(mesh.n_devices == n_devices, mesh.describe())
    placed = reference_pass(
        spec, spec.make(**structure_kw(name, sz, loaded, placement=mesh)),
        sz, seed + 1)
    diff = [(r, i, a, b) for r, (ra, rb) in enumerate(zip(stacked, placed))
            for i, (a, b) in enumerate(zip(ra, rb)) if a != b]
    expect(not diff, "mesh and stacked answers differ (round, index, "
           "stacked, mesh)", diff[:4])
    served = serve_and_count(name, sz, loaded, live, seed + 2,
                             mesh_shards=sz["n_shards"])
    expect(served["mesh_devices"] == n_devices, served)
    report(f"{name}-mesh", sz, t0, clock, c0, h0, served=served,
           mesh_devices=n_devices, same_answers_as_stacked=True)


# ---------------------------------------------------------------------------
# Decode phase
# ---------------------------------------------------------------------------
def decode_phase(clock, seed):
    """qwen2_0_5b at its published widths, random weights from the seed:
    one batched prefill checked row by row against an unbatched forward,
    then a few requests served through the PC scheduler."""
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.launch.serve import DecodeExecutor, run_serving
    from repro.models import transformer

    sz = SIZES["decode"]
    t0, c0, h0 = time.perf_counter(), clock.seconds, clock.cache_hits
    cfg = configs.get(sz["arch"])
    widths = dict(n_layers=cfg.n_layers, d_model=cfg.d_model,
                  n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                  d_ff=cfg.d_ff, vocab=cfg.vocab)
    B, S = sz["max_batch"], sz["prompt_len"]
    ex = DecodeExecutor(cfg, max_batch=B, max_len=S + sz["tokens"] + 1,
                        seed=seed)
    toks = np.random.default_rng(seed).integers(
        2, cfg.vocab, (B, S)).astype(np.int32)
    cache = transformer.init_cache(ex.cfg, B, ex.max_len)
    batched, _ = ex._prefill(ex.params, {"tokens": jnp.asarray(toks)}, cache)
    batched = np.asarray(batched, np.float32)
    expect(batched.shape == (B, cfg.vocab) and np.isfinite(batched).all())
    one_cache = transformer.init_cache(ex.cfg, 1, ex.max_len)
    forward = jax.jit(lambda p, t: transformer.model_apply(
        p, ex.cfg, {"tokens": t}, mode="train")[0][:, -1])
    rel_err = max_err = train_err = 0.0
    for i in range(2):
        row = {"tokens": jnp.asarray(toks[i:i + 1])}
        single = np.asarray(ex._prefill(ex.params, row, one_cache)[0],
                            np.float32)[0]
        err = float(np.linalg.norm(batched[i] - single)
                    / np.linalg.norm(single))
        expect(err <= LOGIT_TOL, "batched prefill logits", i, err)
        rel_err = max(rel_err, err)
        max_err = max(max_err, float(np.abs(batched[i] - single).max()))
        # the teacher-forced forward takes another attention path; its
        # gap is reported, not held to LOGIT_TOL
        train = np.asarray(forward(ex.params, row["tokens"]), np.float32)[0]
        train_err = max(train_err, float(np.abs(batched[i] - train).max()))
    del ex, cache, one_cache
    st = run_serving(sz["arch"], workload="decode", scheduler="pc",
                     sessions=sz["sessions"],
                     requests_per_session=sz["requests"],
                     n_tokens=sz["tokens"], prompt_len=S, max_batch=B,
                     seed=seed)
    expect(st["answered"] == st["requests"]
           == sz["sessions"] * sz["requests"], st)
    report("decode", dict(sz, **widths), t0, clock, c0, h0,
           logits_rel_l2_err=rel_err, logits_rel_l2_tol=LOGIT_TOL,
           logits_max_abs_err=max_err,
           train_forward_max_abs_err=train_err,
           served={k: st[k] for k in ("requests", "answered", "mean_batch",
                                      "device_steps")})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the pq, map and graph phases, placed on "
                         "a D=4 mesh and compared with the stacked layout")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    print(json.dumps(dict(platform=devices[0].platform,
                          device_kind=devices[0].device_kind,
                          count=len(devices), compile_cache=cache_dir)),
          flush=True)
    clock = CompileClock()
    if args.chips == 4:
        mesh_phase("pq", clock, args.seed, 4)
        mesh_phase("map", clock, args.seed, 4)
        mesh_phase("graph", clock, args.seed, 4)
    else:
        structure_phase("pq", clock, args.seed, pallas_twin=True)
        structure_phase("map", clock, args.seed)
        structure_phase("graph", clock, args.seed)
        decode_phase(clock, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
