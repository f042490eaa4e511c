"""Serving driver: parallel-combining scheduler over the decode step.

Wires the paper's technique end-to-end: concurrent client sessions submit
prompts; the PC scheduler (serving/scheduler.py — Listing 1 + the §4
batched-PQ ordering) combines them into dense decode batches and drives ONE
jitted decode program per combining pass over fixed batch slots.

This is continuous batching with explicit synchronization: slots of
finished requests are refilled from the publication list each pass, which
is exactly the paper's claim — a single combiner with batch-parallel
execution beats fine-grained per-request dispatch once concurrency is high.

Usage (the published widths by default; ``--reduced`` for the smoke-size
config the CPU test tier uses):
  python -m repro.launch.serve --arch qwen2_0_5b --sessions 8 --requests 4
"""
from __future__ import annotations

import argparse
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import substrate
from repro.core.faults import FaultPlan
from repro.launch.compile_cache import use_compile_cache
from repro.models import lm, transformer
from repro.serving import PCScheduler, SerialScheduler


class DecodeExecutor:
    """Slot-based batched decode executor (the device side of the scheduler).

    Holds a fixed (max_batch, ...) KV-cache; each call takes ≤ max_batch
    (prompt, n_tokens) requests, prefills them into free slots and greedily
    decodes n_tokens — all as device programs with static shapes.
    """

    def __init__(self, cfg, *, max_batch: int = 8, max_len: int = 128,
                 seed: int = 0):
        self.cfg = cfg.with_(decode_cache_len=max_len)
        self.max_batch = max_batch
        self.max_len = max_len
        key = jax.random.PRNGKey(seed)
        self.params, _ = transformer.model_init(key, self.cfg)
        self._prefill = jax.jit(lm.make_prefill(self.cfg))
        self._decode = jax.jit(lm.make_decode_step(self.cfg))
        self.device_steps = 0

    def __call__(self, reqs: List[Dict[str, Any]]) -> List[np.ndarray]:
        """reqs: [{'prompt': (S,) int32, 'n_tokens': int}] — one combined
        batch; returns per-request generated token arrays."""
        n = len(reqs)
        S = max(len(r["prompt"]) for r in reqs)
        n_gen = max(int(r["n_tokens"]) for r in reqs)
        toks = np.zeros((self.max_batch, S), np.int32)
        for i, r in enumerate(reqs):
            toks[i, S - len(r["prompt"]):] = r["prompt"]   # left-pad
        cache = transformer.init_cache(self.cfg, self.max_batch, self.max_len)
        logits, cache = self._prefill(self.params,
                                      {"tokens": jnp.asarray(toks)}, cache)
        self.device_steps += 1
        out = np.zeros((self.max_batch, n_gen), np.int32)
        last = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        pos = jnp.int32(S)
        for t in range(n_gen):
            out[:, t] = np.asarray(last[:, 0])
            nxt, _, cache = self._decode(self.params, cache, pos, last)
            self.device_steps += 1
            last = nxt[:, None]
            pos = pos + 1
        return [out[i, : int(r["n_tokens"])] for i, r in enumerate(reqs)]


class StructureExecutor:
    """Registry-driven structure executor (DESIGN.md §16) — ONE executor
    class serves EVERY registered :class:`~repro.core.substrate.
    StructureSpec` workload (graph, map, pq, sketch, union-find, and any
    future registration) through the protocol surface alone.

    Each combined batch is a list of ``{'method': ..., 'input': ...}``
    requests.  Updates are applied first in arrival order (ONE fused
    mixed-op device pass per ≤ c_max slice via ``update_batch_async``,
    result masks left on device), then ALL reads are answered with one
    vectorized read program whose single fetch also resolves the update
    handles — the §3.3 read-optimized transform with the scheduler's
    combiner loop playing the combiner.  ``megapass=True``
    (DESIGN.md §17) fuses the two into ONE ``mixed_rounds`` dispatch —
    an update round followed by a read round in the same donated scan
    program, all handles sharing one fetch.
    """

    def __init__(self, spec: substrate.StructureSpec, *,
                 megapass: bool = False, **make_kw):
        self.spec = spec
        self.ds = spec.make(**make_kw)
        self.megapass = bool(megapass) and hasattr(self.ds, "mixed_rounds")
        self.device_steps = 0
        self.megapass_dispatches = 0
        self.megapass_rounds = 0

    def __call__(self, reqs: List[Dict[str, Any]]) -> List[Any]:
        methods = [r["method"] for r in reqs]
        inputs = [r["input"] for r in reqs]
        ro = self.ds.read_only
        upd = [i for i, m in enumerate(methods) if m not in ro]
        reads = [i for i, m in enumerate(methods) if m in ro]
        out: List[Any] = [None] * len(reqs)
        if self.megapass and upd:
            rounds = [("update", [methods[i] for i in upd],
                       [inputs[i] for i in upd])]
            if reads:
                rounds.append(("read", [methods[i] for i in reads],
                               [inputs[i] for i in reads]))
            handles = self.ds.mixed_rounds(rounds)
            self.device_steps += 1
            self.megapass_dispatches += 1
            self.megapass_rounds += len(rounds)
            if reads:
                for i, r in zip(reads, handles[1].result()):
                    out[i] = r
            for i, r in zip(upd, handles[0].result()):
                out[i] = r
            return out
        handle = None
        if upd:
            handle = self.ds.update_batch_async(
                [methods[i] for i in upd], [inputs[i] for i in upd])
            self.device_steps += 1
        if reads:
            res = self.ds.read_batch([methods[i] for i in reads],
                                     [inputs[i] for i in reads])
            for i, r in zip(reads, res):
                out[i] = r
            self.device_steps += 1
        if handle is not None:
            for i, r in zip(upd, handle.result()):
                out[i] = r
        return out


def _structure_requests(spec: substrate.StructureSpec, rng, sessions: int,
                        requests_per_session: int, read_pct: int,
                        serve_kw: Dict[str, Any]) -> List[List[dict]]:
    """Synthetic per-session request tables from the spec's registered
    op generators: ``read_pct``% reads, the rest updates, drawn from ONE
    shared ctx so sessions revisit each other's keys (the duplicate /
    delete-reinsert schedules the combiner nets out)."""
    ctx = spec.new_ctx()
    if isinstance(ctx, dict) and "n" in serve_kw:
        ctx["n"] = serve_kw["n"]          # sizing knob the generators read
    tab = []
    for _ in range(sessions):
        row = []
        for _ in range(requests_per_session):
            gen = (spec.gen_read
                   if spec.gen_read is not None
                   and rng.random() * 100 < read_pct else spec.gen_update)
            ms, ins = gen(rng, 1, ctx)
            row.append({"method": ms[0], "input": ins[0]})
        tab.append(row)
    return tab


def run_serving(arch_id: str = "qwen2_0_5b", *, sessions: int = 8,
                requests_per_session: int = 4, n_tokens: int = 8,
                prompt_len: int = 16, max_batch: int = 8,
                scheduler: str = "pc", seed: int = 0,
                workload: str = "decode", read_pct: int = 90,
                n_vertices: int = 512,
                graph_use_pallas: bool = False,
                rounds_cap: int = 4,
                tier: str = "eliminate",
                megapass: bool = False,
                mesh_shards: Optional[int] = None,
                fault_plan: Optional[FaultPlan] = None,
                reduced: bool = False,
                structure_kw: Optional[Dict[str, Any]] = None
                ) -> Dict[str, Any]:
    """Drive ``sessions`` concurrent client sessions through a scheduler.

    ``scheduler``: "serial" (one dispatch per request), "pc" (async
    combiner, blocking per-session submits), "pc-async" (each session
    publishes ALL its requests via ``submit_async`` up front and gathers
    the futures — the non-blocking client API), "pc-nodonate" (ablation:
    the deadline PQ copies its heap buffers every pass instead of the
    zero-copy donated dispatch, EXPERIMENTS §Ablations) or "pc-pallas"
    (the PQ's combining passes run as shard-grid Pallas kernels,
    DESIGN.md §10).

    ``workload``: "decode" (LM decode batches over ``DecodeExecutor``)
    or the name of ANY registered batched structure (``repro.core.
    substrate`` — "graph", "map", "pq", "sketch", "unionfind", ...),
    served through the generic :class:`StructureExecutor` with request
    streams drawn from the spec's registered op generators;
    ``read_pct`` sets each session's share of read queries.  Structure
    sizing comes from the spec's ``extras["serve_kw"]`` (falling back to
    the registered defaults); for the graph workload ``n_vertices``
    still overrides the vertex count.  Under the structure workloads the
    ablation scheduler modes apply to the engine too: "pc-nodonate"
    un-donates its passes and "pc-pallas" routes rebuilds through the
    shard-grid kernels (DESIGN.md §11, §13).

    ``tier``: ordering-tier override for the PC schedulers
    (DESIGN.md §14) — ``eliminate`` (default, the static pre-§14
    behavior), ``host``, ``device``, or ``auto`` (the online cost model
    routes each ordering pass; decisions land in the returned
    ``tier_decisions``).

    ``megapass``: fuse each structure pass's update and read rounds into
    ONE ``mixed_rounds`` dispatch (DESIGN.md §17) instead of the
    alternating update/read dispatch pair (structure workloads only;
    the decode workload ignores it).

    ``mesh_shards``: place the workload's K shards across a real device
    mesh (DESIGN.md §18).  Sets K to this value, builds the 1-D
    ``("shard",)`` combining mesh from the current world size
    (``make_combining_mesh`` — D = largest divisor of K that fits, so a
    1-device world degenerates gracefully), and threads the resulting
    ``MeshPlacement`` into BOTH the workload structure (structure
    workloads advertising placement support — refused loudly otherwise)
    and the PC scheduler's deadline PQ.  Incompatible with the
    "pc-pallas" scheduler (the kernels assume the stacked layout).

    ``reduced``: decode with the family's smoke-size config
    (``configs.get_reduced``) instead of the published widths — the CPU
    test tier's setting.

    ``structure_kw``: constructor arguments merged over the spec's
    ``serve_kw`` (deployment sizes, preloaded contents such as the PQ's
    ``values``, the map's ``items`` or the graph's ``edges``).

    Every session's exception is re-raised here after the sessions are
    joined and the scheduler closed; the returned ``answered`` counts the
    requests that got an answer, and structure workloads add ``ops``
    (per method: requests sent, answers that are neither None nor False)
    and the structure's ``final_size``.

    ``fault_plan``: optional deterministic :class:`FaultPlan`
    (DESIGN.md §15) shared between the workload structure (transactional
    guarded dispatch in the graph/map executors) and the PC scheduler
    (combiner kill + supervisor takeover, guarded deadline-PQ dispatch,
    circuit-breaker tier degradation).  Fault counters and the breaker
    state land in the returned ``faults`` stats entry.
    """
    rng = np.random.default_rng(seed)
    mesh_pl = None
    if mesh_shards is not None:
        from repro.core import placement as _placement
        from repro.launch.mesh import make_combining_mesh

        if mesh_shards < 1:
            raise ValueError("--mesh-shards must be >= 1")
        mesh_pl = _placement.MeshPlacement(make_combining_mesh(mesh_shards))
    if workload != "decode" and substrate.try_get(workload) is not None:
        spec = substrate.get(workload)
        if not spec.serve:
            raise ValueError(f"structure {workload!r} is not enrolled "
                             f"for serving (spec.serve=False)")
        serve_kw = dict(spec.extras.get("serve_kw", {}))
        if workload == "graph":
            serve_kw["n"] = n_vertices
            serve_kw.setdefault("edge_capacity", 16 * n_vertices)
        serve_kw.update(structure_kw or {})
        use_pallas = scheduler == "pc-pallas" or (
            workload == "graph" and graph_use_pallas)
        if mesh_pl is not None:
            if not spec.extras.get("placement"):
                raise ValueError(
                    f"workload {workload!r} does not support --mesh-shards "
                    "(no placement= constructor knob)")
            serve_kw["n_shards"] = mesh_shards
            serve_kw["placement"] = mesh_pl
        ex: Any = StructureExecutor(
            spec, megapass=megapass, use_pallas=use_pallas,
            donate=scheduler != "pc-nodonate", fault_plan=fault_plan,
            **serve_kw)
        reqs_tab = _structure_requests(spec, rng, sessions,
                                       requests_per_session, read_pct,
                                       serve_kw)
    elif workload == "decode":
        cfg = (configs.get_reduced if reduced else configs.get)(arch_id)
        ex = DecodeExecutor(cfg, max_batch=max_batch,
                            max_len=prompt_len + n_tokens + 1, seed=seed)
        prompts = rng.integers(2, cfg.vocab,
                               (sessions, requests_per_session,
                                prompt_len)).astype(np.int32)
        reqs_tab = [[{"prompt": prompts[s, j], "n_tokens": n_tokens}
                     for j in range(requests_per_session)]
                    for s in range(sessions)]
    else:
        raise ValueError(f"unknown workload {workload!r}")

    if scheduler in ("pc", "pc-async", "pc-nodonate", "pc-pallas"):
        sch_kw: Dict[str, Any] = {}
        if mesh_pl is not None:
            # the deadline PQ rides the same mesh: its K must match the
            # mesh the placement was built from (K % D == 0 by
            # construction of make_combining_mesh)
            sch_kw = dict(n_shards=mesh_shards, pq_placement=mesh_pl)
        sch = PCScheduler(ex, max_batch=max_batch, use_pq=True,
                          pq_donate=scheduler != "pc-nodonate",
                          pq_use_pallas=scheduler == "pc-pallas",
                          rounds_cap=rounds_cap, tier=tier,
                          fault_plan=fault_plan, **sch_kw)
    elif scheduler == "serial":
        sch = SerialScheduler(ex)
    else:
        raise ValueError(f"unknown scheduler {scheduler!r}")

    results: Dict[int, list] = {}
    errors: List[BaseException] = []
    t0 = time.time()

    def session(sid: int):
        reqs = [(reqs_tab[sid][j],
                 float(sid * requests_per_session + j))
                for j in range(requests_per_session)]
        try:
            if scheduler == "pc-async":
                futs = [sch.submit_async(inp, deadline=d)
                        for inp, d in reqs]
                results[sid] = [f.result() for f in futs]
            else:
                results[sid] = [sch.submit(inp, deadline=d)
                                for inp, d in reqs]
        except Exception as e:            # re-raised after the join
            errors.append(e)

    threads = [threading.Thread(target=session, args=(s,))
               for s in range(sessions)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        if isinstance(sch, PCScheduler):
            sch.close()
    wall = time.time() - t0
    if errors:
        raise errors[0]

    total_reqs = sessions * requests_per_session
    total_toks = total_reqs * (n_tokens if workload == "decode" else 1)
    stats = {
        "workload": workload,
        "scheduler": scheduler,
        "requests": total_reqs,
        "wall_s": round(wall, 3),
        "req_per_s": round(total_reqs / wall, 2),
        "tok_per_s": round(total_toks / wall, 1),
        "device_steps": ex.device_steps,
        "mean_batch": round(getattr(sch, "mean_batch", 1.0), 2)
        if scheduler != "serial" else 1.0,
        "tier_decisions": dict(getattr(sch, "tier_decisions", {})),
        "answered": sum(len(r) for r in results.values()),
    }
    if workload == "decode":
        stats["answered"] = sum(len(a) == n_tokens for r in results.values()
                                for a in r)
    else:
        ops: Dict[str, List[int]] = {}
        for sid, answers in results.items():
            for req, ans in zip(reqs_tab[sid], answers):
                tally = ops.setdefault(req["method"], [0, 0])
                tally[0] += 1
                tally[1] += ans is not None and ans is not False
        stats["ops"] = ops
        if hasattr(ex.ds, "__len__"):
            stats["final_size"] = len(ex.ds)
    if mesh_pl is not None:
        stats["placement"] = mesh_pl.describe()
        stats["mesh_devices"] = mesh_pl.n_devices
    if getattr(ex, "megapass_dispatches", 0):
        stats["megapass_dispatches"] = ex.megapass_dispatches
        stats["rounds_per_dispatch"] = round(
            ex.megapass_rounds / ex.megapass_dispatches, 2)
    if fault_plan is not None:
        # robustness counters (DESIGN.md §15): the plan is shared between
        # the structure's dispatch guard and the scheduler, so one
        # snapshot covers faults injected at every layer
        faults: Dict[str, Any] = fault_plan.counters.snapshot()
        if isinstance(sch, PCScheduler):
            faults.update(sch.fault_counters())
        stats["faults"] = faults
    return stats


def build_fault_plan(args) -> Optional[FaultPlan]:
    """CLI → :class:`FaultPlan` (DESIGN.md §15); None when no fault flag
    is set, so the default serving path carries zero fault machinery."""
    if args.faults == "standard":
        return FaultPlan.standard(args.fault_seed)
    spikes = tuple(args.fault_latency_spike or ())
    if (args.fault_kill_pass is None and args.fault_dispatch_rate == 0.0
            and not spikes):
        return None
    return FaultPlan(args.fault_seed,
                     kill_combiner_at_pass=args.fault_kill_pass,
                     dispatch_fail_rate=args.fault_dispatch_rate,
                     max_dispatch_failures=64,
                     latency_spike_passes=spikes,
                     latency_spike_s=args.fault_latency_spike_s)


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_0_5b")
    ap.add_argument("--reduced", action="store_true",
                    help="decode with the smoke-size config of the family "
                         "instead of its published widths")
    ap.add_argument("--sessions", type=int, default=8)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--scheduler",
                    choices=["pc", "pc-async", "pc-nodonate", "pc-pallas",
                             "serial"],
                    default="pc")
    ap.add_argument("--workload",
                    choices=["decode"] + substrate.names(),
                    default="decode")
    ap.add_argument("--read-pct", type=int, default=90)
    ap.add_argument("--rounds-cap", type=int, default=4,
                    help="cap R on the scheduler's adaptive multi-round "
                         "fused PQ dispatch (DESIGN.md §12)")
    ap.add_argument("--megapass", action="store_true",
                    help="fuse each structure pass's update+read rounds "
                         "into one mixed_rounds dispatch (DESIGN.md §17)")
    ap.add_argument("--mesh-shards", type=int, default=None, metavar="K",
                    help="place K shards across a real device mesh "
                         "(DESIGN.md §18): builds the 1-D combining mesh "
                         "from the current world size and threads the "
                         "MeshPlacement into the workload structure and "
                         "the scheduler's deadline PQ; run under "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N to fake an N-device host")
    ap.add_argument("--tier",
                    choices=["auto", "host", "device", "eliminate"],
                    default="eliminate",
                    help="ordering-tier override for the PC scheduler "
                         "(DESIGN.md §14); 'auto' routes per pass via "
                         "the online cost model")
    ap.add_argument("--faults", choices=["none", "standard"],
                    default="none",
                    help="'standard' enables the standard fault plan "
                         "(DESIGN.md §15: kill combiner at pass 3, 10%% "
                         "dispatch failure, one latency spike)")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--fault-kill-pass", type=int, default=None,
                    help="kill the combiner loop once at this pass")
    ap.add_argument("--fault-dispatch-rate", type=float, default=0.0,
                    help="probability a guarded device dispatch fails")
    ap.add_argument("--fault-latency-spike", type=int, action="append",
                    default=None, metavar="PASS",
                    help="inject a latency spike at this combiner pass "
                         "(repeatable)")
    ap.add_argument("--fault-latency-spike-s", type=float, default=0.05)
    args = ap.parse_args()
    stats = run_serving(args.arch, sessions=args.sessions,
                        requests_per_session=args.requests,
                        n_tokens=args.tokens, max_batch=args.max_batch,
                        scheduler=args.scheduler, workload=args.workload,
                        read_pct=args.read_pct,
                        rounds_cap=args.rounds_cap, tier=args.tier,
                        megapass=args.megapass,
                        mesh_shards=args.mesh_shards,
                        fault_plan=build_fault_plan(args),
                        reduced=args.reduced)
    print("[serve]", stats)


if __name__ == "__main__":
    main()
