"""Run one cell and shape its result line.

``run_cell`` resolves the cell's files by name, hands them to the runner
that the traffic's ``loop`` names (``chipbench/runners/<loop>.py``), reads
the per-layer metrics of a traced run, and returns the result object and
the comparison lines. It looks for no chip itself: ``run.py`` does that
before calling it, and the tests call it on the CPU.
"""
from __future__ import annotations

import time
from pathlib import Path
from typing import List, Optional, Tuple

from . import bench

TRACE_DIR = bench.ROOT / "chipbench_out" / "trace"


def run_cell(spec: dict, workload: str, *, seed: int, seconds: float,
             trace: bool, t0: float, devs, config: Optional[dict] = None,
             traffic: Optional[dict] = None,
             trace_dir: Optional[Path] = None,
             compiles=None, control: bool = False
             ) -> Tuple[dict, List[str], dict]:
    """``spec`` is the parsed ``BENCHMARK.json``; ``config``/``traffic``
    override the files the cell names (the tests run small copies);
    ``control`` puts the cell's control in the program's place."""
    c = bench.cell(spec, workload)
    cfg = config or bench.load_config(c["config"])
    mix = traffic or bench.load_traffic(c["traffic"])
    tdir = (trace_dir or TRACE_DIR / workload) if trace else None
    out = bench.load_module("runners", mix["loop"]).run(
        cfg, mix, seed=seed, seconds=seconds, trace_dir=tdir, t0=t0,
        clock=time.perf_counter, devs=devs, compiles=compiles,
        control=control)
    if trace:
        entries = bench.per_layer(spec, workload)
        values = bench.read_per_layer(entries, {**out.ctx,
                                                "trace": out.trace})
    else:
        entries = bench.end_to_end(spec, workload)
        values = out.e2e
    checks = {name: {"value": v, "limit": lim}
              for name, (v, lim) in out.checks.items()}
    result = {
        "correct": out.correct,
        "attempted": int(out.attempted),
        "failed": int(out.failed),
        "metrics": bench.metric_values(entries, values),
        "device": bench.device_block(devs, out.peak_bytes, out.trace),
    }
    if out.trace is not None:
        result["breakdown"] = {"device_ops": out.trace["device_ops"],
                               "idle_gaps": out.trace["idle_gaps"]}
    result["checks"] = checks
    lines = [f"check {name}: {v} (limit {lim})"
             for name, (v, lim) in out.checks.items()]
    return result, lines, out.info
