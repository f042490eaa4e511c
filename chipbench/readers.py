"""Per-layer metric readers, found by name under ``metrics/``.

A metric is ``metrics/<name>.py`` (constants ``LAYER``, ``SOURCE``,
``MOVES``, ``UNIT`` and ``read(window) -> float | None``) or
``metrics/<name>.json`` (the same four keys in lower case, plus a
``reducer`` named in ``REDUCERS`` and its parameters).  A reader that
finds nothing to read returns None and the metric is left out.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, Optional

METRICS = Path(__file__).resolve().parent / "metrics"


def device_ms_per_pass(window, programs, per) -> Optional[float]:
    """Device time of the named programs in the traced window, in ms,
    per executor pass of the kind ``per`` names."""
    tr = window.trace
    if tr is None:
        return None
    seen = [p for p in programs if p in tr.program_s]
    n = window.count(per)
    if not seen or not n:
        return None
    return 1e3 * sum(tr.program_s[p] for p in seen) / n


REDUCERS: Dict[str, Callable[..., Optional[float]]] = {
    "device_ms_per_pass": device_ms_per_pass,
}


class Reader:
    def __init__(self, name: str, layer: str, source: str, moves: str,
                 unit: str, fn: Callable[[Any], Optional[float]]):
        self.name, self.layer, self.source = name, layer, source
        self.moves, self.unit, self._fn = moves, unit, fn

    def read(self, window) -> Optional[float]:
        v = self._fn(window)
        return None if v is None else float(v)


def load(name: str) -> Reader:
    py, js = METRICS / f"{name}.py", METRICS / f"{name}.json"
    if py.exists():
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{name}", py)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return Reader(name, mod.LAYER, mod.SOURCE, mod.MOVES, mod.UNIT,
                      mod.read)
    if js.exists():
        d = json.loads(js.read_text())
        fn = REDUCERS[d["reducer"]]
        args = d.get("args", {})
        return Reader(name, d["layer"], d["source"], d["moves"], d["unit"],
                      lambda w: fn(w, **args))
    raise FileNotFoundError(f"no reader for metric {name!r} under {METRICS}")
