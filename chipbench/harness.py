"""Serve one cell closed-loop through PCScheduler, then check every answer.

A cell is a configuration (``configs/<config>.json``: the structure, its
sizes and key space) under a traffic mix (``traffic/<mix>.json``).  The
structure's own file under ``structures/`` gives its preload, its
request inputs, its plain reference and its final state.

The served path is the one ``repro.launch.serve.run_serving`` wires:
``PCScheduler`` (``tier="eliminate"``, pipelined) over a
``StructureExecutor``.  The harness puts a recorder between the two: it
is the scheduler's ``step_fn``, calls the executor, and keeps every batch
with its answers in the order the executor applied them.  The check
replays those batches through the reference and compares every answer a
client received, and the final state.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import traffic as _traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

WARM_STREAM, CLIENT_STREAM = 1, 2
RAMP_S = 1.0              # closed-loop serving before the window, not timed
JOIN_S = 120.0            # longest wait for a client's last answer


# ---------------------------------------------------------------------------
# Finding a cell
# ---------------------------------------------------------------------------
def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(name: str, root: Path = ROOT) -> Tuple[Dict, Dict, Dict]:
    """``(workload entry, configuration, traffic mix)`` of a cell."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r} (have {sorted(cells)})")
    cell = cells[name]
    entry, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    cfg = json.loads((root / entry["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    return cell, cfg, mix


def structure(name: str):
    """The module ``structures/<name>.py``."""
    path = HERE / "structures" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_structure_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Compile events (JAX's own monitoring)
# ---------------------------------------------------------------------------
class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and how many
    such events there were, from JAX's monitoring events."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, name, secs, **_):
        if name in self.EVENTS:
            self.seconds += secs
            self.events += 1


# ---------------------------------------------------------------------------
# The recorder between scheduler and executor
# ---------------------------------------------------------------------------
@dataclass
class Batch:
    ids: List[Any]
    methods: List[str]
    inputs: List[Any]
    answers: Optional[List[Any]]      # None when the executor raised
    t0: float
    t1: float


class Recorder:
    """The executor as ``step_fn``: every batch, in the order applied."""

    def __init__(self, executor, span: Callable[[str], Any]):
        self.executor = executor
        self.span = span
        self.batches: List[Batch] = []

    def __call__(self, reqs: List[Dict[str, Any]]) -> List[Any]:
        t0 = time.perf_counter()
        out = None
        try:
            with self.span("executor.pass"):
                out = list(self.executor(reqs))
            return out
        finally:
            self.batches.append(Batch(
                [r["id"] for r in reqs], [r["method"] for r in reqs],
                [r["input"] for r in reqs], out, t0, time.perf_counter()))


def _annotate(span: Callable[[str], Any], name: str, fn):
    def wrapped(*a, **kw):
        with span(name):
            return fn(*a, **kw)
    return wrapped


@contextlib.contextmanager
def host_spans(sch, ds, span):
    """Spans around the layers' calls, for the traced run: the combiner's
    ordering pass, the executor's update dispatch and read, and every
    blocking fetch (the structures' ``_host_fetch`` hook)."""
    from repro.core import batched_map, batched_pq

    mods = (batched_map, batched_pq)
    saved = [m._host_fetch for m in mods]
    sch._order = _annotate(span, "combiner.order", sch._order)
    ds.update_batch_async = _annotate(span, "executor.update",
                                      ds.update_batch_async)
    ds.read_batch = _annotate(span, "executor.read", ds.read_batch)
    for m in mods:
        m._host_fetch = _annotate(span, "fetch", m._host_fetch)
    try:
        yield
    finally:
        for m, f in zip(mods, saved):
            m._host_fetch = f


# ---------------------------------------------------------------------------
# A run
# ---------------------------------------------------------------------------
@dataclass
class Window:
    """What the per-layer readers see of the measured window."""

    seconds: float
    ops: int
    batches: List[int]
    device_steps: int
    passes: List[Batch]
    reads: frozenset                  # the structure's read methods
    trace: Any = None

    @property
    def pass_seconds(self) -> List[float]:
        return [p.t1 - p.t0 for p in self.passes]

    def count(self, kind: str) -> int:
        """Passes in the window that carried an ``update`` or a ``read``."""
        return sum(any((m in self.reads) == (kind == "read")
                       for m in p.methods) for p in self.passes)


def _requests(mod, mix, cfg, seed, stream, client, tag):
    seq = 0
    for method, key, value in _traffic.op_stream(mix, cfg, seed, stream,
                                                 client):
        yield {"method": method, "input": mod.request_input(method, key,
                                                            value),
               "id": (tag, seq)}
        seq += 1


def warm_up(rec: Recorder, mod, mix, cfg, seed: int, max_batch: int):
    """Run every batch shape the mix can produce through the executor,
    outside the scheduler: for each pow2 width up to ``max_batch``, a
    batch of only each operation of the mix and, where it has more than
    one, one of the mix itself.  The batches are recorded and checked
    like the served ones."""
    mixes = [{m: 1.0} for m in sorted(mix["ops"])]
    if len(mix["ops"]) > 1:
        mixes.append(mix["ops"])
    for i, ops in enumerate(mixes):
        gen = _requests(mod, dict(mix, ops=ops), cfg, seed, WARM_STREAM, i,
                        f"warm{i}")
        w = 1
        while w <= max_batch:
            rec([next(gen) for _ in range(w)])
            w *= 2


def serve(cfg: Dict, mix: Dict, *, seed: int, seconds: float,
          trace_dir: Optional[str] = None, t_start: Optional[float] = None,
          clock: Optional[CompileClock] = None,
          ramp_s: float = RAMP_S) -> Dict[str, Any]:
    """Build the cell's state, warm it up, serve ``mix`` closed-loop for
    ``seconds`` and return what the check and the metrics need."""
    import jax

    from repro.core import substrate
    from repro.launch.serve import StructureExecutor
    from repro.serving import PCScheduler

    t_start = time.perf_counter() if t_start is None else t_start
    clock = clock or CompileClock()
    tracing = trace_dir is not None
    if tracing:
        span = lambda n: jax.profiler.TraceAnnotation("cb:" + n)  # noqa
    else:
        span = lambda n: contextlib.nullcontext()  # noqa
    mod = structure(cfg["structure"])
    sc = cfg["scheduler"]
    phases = {"start_s": time.perf_counter() - t_start}
    data = mod.preload(cfg, seed)
    ex = StructureExecutor(substrate.get(cfg["structure"]),
                           **mod.make_kwargs(cfg, data))
    phases["state_s"] = time.perf_counter() - t_start - sum(phases.values())
    rec = Recorder(ex, span)
    warm_up(rec, mod, mix, cfg, seed, sc["max_batch"])
    phases["warm_up_s"] = (time.perf_counter() - t_start
                           - sum(phases.values()))
    sch = PCScheduler(rec, max_batch=sc["max_batch"], use_pq=True,
                      rounds_cap=sc["rounds_cap"], tier=sc["tier"],
                      pipeline=sc["pipeline"])
    n_clients = int(mix["clients"])
    stop = threading.Event()
    records: List[List[tuple]] = [[] for _ in range(n_clients)]

    def client(c: int):
        gen = _requests(mod, mix, cfg, seed, CLIENT_STREAM, c, c)
        out = records[c]
        while not stop.is_set():
            req = next(gen)
            t0 = time.perf_counter()
            try:
                with span("client.publish"):
                    fut = sch.submit_async(req, deadline=t0 - t_start)
                ans, ok = fut.result(), True
            except Exception as e:        # counted in `failed`
                ans, ok = repr(e), False
            out.append((req["id"], t0, time.perf_counter(), ok, ans))

    threads = [threading.Thread(target=client, args=(c,), daemon=True,
                                name=f"client-{c}")
               for c in range(n_clients)]
    spans_on = host_spans(sch, ex.ds, span) if tracing \
        else contextlib.nullcontext()
    try:
        with spans_on:
            for t in threads:
                t.start()
            if tracing:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1     # the harness's spans only
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            time.sleep(ramp_s)
            t0 = time.perf_counter()
            c0, nb0, ds0 = clock.events, len(sch.batches), ex.device_steps
            with span("window"):
                time.sleep(seconds)
            t1 = time.perf_counter()
            c1, nb1, ds1 = clock.events, len(sch.batches), ex.device_steps
            stop.set()
            for t in threads:
                t.join(JOIN_S)
            if tracing:
                t_stop = time.perf_counter()
                jax.profiler.stop_trace()
                phases["trace_stop_s"] = time.perf_counter() - t_stop
    finally:
        stop.set()
        sch.close()
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a client got no answer within "
                           f"{JOIN_S} s of the window's end")
    stats = jax.devices()[0].memory_stats() or {}
    final = mod.fetch_state(ex.ds)
    window = Window(
        seconds=t1 - t0,
        ops=sum(t0 <= r[2] <= t1 for rs in records for r in rs),
        batches=list(sch.batches[nb0:nb1]), device_steps=ds1 - ds0,
        passes=[b for b in rec.batches if t0 <= b.t0 < t1],
        reads=frozenset(ex.ds.read_only))
    lat = np.asarray([r[2] - r[1] for rs in records for r in rs
                      if t0 <= r[2] <= t1])
    return dict(
        data=data, batches=rec.batches, records=records, final=final,
        window=window, latencies=lat, setup_s=t0 - t_start, phases=phases,
        compile_s=clock.seconds, window_compile_events=c1 - c0,
        memory_peak_bytes=stats.get("peak_bytes_in_use"),
        pq_dispatches=sch.pq_dispatches, module=mod)


# ---------------------------------------------------------------------------
# The check
# ---------------------------------------------------------------------------
def replay(mod, cfg, data, batches: List[Batch], precision: str):
    """The reference over the recorded batches: expected answer per op id
    and the final state."""
    ref = mod.Reference(data, precision, cfg)
    want: Dict[Any, Any] = {}
    for b in batches:
        if b.answers is None:
            continue
        for i, a in zip(b.ids, ref.step(b.methods, b.inputs)):
            want[i] = a
    return want, ref.state()


def compare(mod, served: Dict[str, Any], want: Dict[Any, Any],
            want_state, got=None, got_state=None) -> Dict[str, Tuple]:
    """Numbers compared, each ``(value, limit)``.  ``got``/``got_state``
    stand in for the program's answers and state (the control)."""
    answers: Dict[Any, Any] = {}
    applied: Dict[Any, int] = {}
    for b in served["batches"]:
        for i in b.ids:
            applied[i] = applied.get(i, 0) + 1
        if b.answers is not None and str(b.ids[0][0]).startswith("warm"):
            answers.update(zip(b.ids, b.answers))
    failed = 0
    for rs in served["records"]:
        for i, _t0, _t1, ok, ans in rs:
            if ok:
                answers[i] = ans
            else:
                failed += 1
    if got is not None:
        answers = {i: got[i] for i in answers}
    wrong = sum(i not in want or want[i] != a for i, a in answers.items())
    lost = (sum(n != 1 for n in applied.values())
            + sum(i not in applied for i in answers))
    state = mod.state_mismatch(served["final"] if got_state is None
                               else got_state, want_state)
    return {"answers_wrong": (wrong, 0), "ops_lost_or_repeated": (lost, 0),
            "state_entries_wrong": (state, 0), "ops_failed": (failed, 0)}


def check(cfg: Dict, served: Dict[str, Any]) -> Dict[str, Tuple]:
    mod = served["module"]
    want, want_state = replay(mod, cfg, served["data"], served["batches"],
                              cfg["precision"])
    return compare(mod, served, want, want_state)


def control(cfg: Dict, served: Dict[str, Any]) -> Dict[str, Tuple]:
    """The reference computed one precision lower, in the program's place:
    its answers and state are compared with the stated precision's."""
    from precision import LOWER

    mod = served["module"]
    want, want_state = replay(mod, cfg, served["data"], served["batches"],
                              cfg["precision"])
    low, low_state = replay(mod, cfg, served["data"], served["batches"],
                            LOWER[cfg["precision"]])
    return compare(mod, served, want, want_state, got=low,
                   got_state=low_state)


def passed(checks: Dict[str, Tuple]) -> bool:
    return all(v <= lim for v, lim in checks.values())


def attempted(served: Dict[str, Any]) -> int:
    return sum(len(rs) for rs in served["records"]) + sum(
        len(b.ids) for b in served["batches"]
        if str(b.ids[0][0]).startswith("warm"))


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------
def end_to_end(served: Dict[str, Any]) -> Dict[str, Tuple[float, str]]:
    w, lat = served["window"], served["latencies"]
    out = {"setup_s": (served["setup_s"], "s")}
    if w.ops:
        out["ops_per_s"] = (w.ops / w.seconds, "ops/s")
        out["op_p95_ms"] = (1e3 * float(np.percentile(lat, 95)), "ms")
    return out


def breakdown(summary) -> Dict[str, List]:
    ops = sorted(summary.program_s.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(summary.idle_by_label().items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps[:10]]}
