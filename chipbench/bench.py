"""Discovery by name and the shape of a run's result.

``BENCHMARK.json`` names the cells; everything a cell is made of is a file
of its own, found by name:

* ``chipbench/configs/<config>.json``   the deployment;
* ``chipbench/traffic/<traffic>.json``  the mix (see ``traffic.py``), whose
  ``loop`` names the runner ``chipbench/runners/<loop>.py`` and, for
  arrivals in time, whose ``arrivals`` names the gap shape
  ``chipbench/arrivals/<arrivals>.py``;
* ``chipbench/kinds/<op>.py``           a request kind: operands, plain
  reference, control, exact form, submission to the service;
* ``chipbench/plans/<op>.py``           a kind's single-crossbar engine
  plan and its batched decode;
* ``chipbench/metrics/<metric>.py``     ``read(ctx) -> float | None``.

Adding a cell, a mix, a kind, a runner or a metric adds files and
``BENCHMARK.json`` entries and edits nothing here.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
NAMED = ("runners", "arrivals", "kinds", "plans", "metrics")


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(known: {[w['name'] for w in bench['workloads']]})")


def load_config(name: str, here: Path = HERE) -> dict:
    cfg = json.loads((here / "configs" / f"{name}.json").read_text())
    if cfg.get("name") != name:
        raise ValueError(
            f"configs/{name}.json names itself {cfg.get('name')!r}")
    return cfg


def load_traffic(name: str, here: Path = HERE) -> dict:
    mix = json.loads((here / "traffic" / f"{name}.json").read_text())
    load_module("runners", mix["loop"], here)       # a loop that exists
    return mix


_MODULES: Dict[Path, object] = {}


def load_module(kind: str, name: str, here: Path = HERE):
    """The module ``chipbench/<kind>/<name>.py`` (``kind`` in ``NAMED``)."""
    if kind not in NAMED:
        raise ValueError(f"{kind!r} is not one of {NAMED}")
    path = here / kind / f"{name}.py"
    if path not in _MODULES:
        if not path.is_file():
            raise FileNotFoundError(f"no {kind} module {name!r} ({path})")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def load_reader(name: str, here: Path = HERE) -> Callable[[dict], object]:
    return load_module("metrics", name, here).read


def end_to_end(bench: dict, workload: str) -> List[dict]:
    return [m for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])]


def per_layer(bench: dict, workload: str) -> List[dict]:
    """Every per-layer entry names the cells it is read in."""
    return [m for m in bench["per_layer"] if workload in m["workloads"]]


def metric_values(entries: List[dict], values: Dict[str, float]) -> dict:
    """``{name: {"value", "unit"}}`` for the entries that have a value."""
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in entries if values.get(m["name"]) is not None}


def read_per_layer(entries: List[dict], ctx: dict,
                   here: Path = HERE) -> Dict[str, float]:
    out = {}
    for m in entries:
        v = load_reader(m["name"], here)(ctx)
        if v is not None:
            out[m["name"]] = float(v)
    return out


def use_checkout_cache(root: Path = ROOT) -> str:
    """Keep JAX's persistent compilation cache in the checkout's fixed
    ``.jax_cache``, whatever the environment names, with no size bound
    (a bound turns on eviction, whose bookkeeping files another writer may
    lack). Call before JAX compiles anything."""
    import os
    path = root / ".jax_cache"
    path.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    import jax

    from repro.compile_cache import configure_compile_cache
    jax.config.update("jax_compilation_cache_max_size", -1)
    return configure_compile_cache()


def device_block(devs, peak_bytes: int, trace: dict = None) -> dict:
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": int(peak_bytes)}
    if trace is not None:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = trace["window_s"]
    return out


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return max(peaks) if peaks else 0


class CompileCounter:
    """Counts XLA backend compiles (and their seconds) from JAX's event
    stream; one listener for the life of the process."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration
