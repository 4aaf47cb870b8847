"""The harness end to end on the CPU at a small size: ``run.py`` refuses to
run without a TPU or without the program, a sound run is ``correct``, and
each fault planted under the timed path turns ``correct`` false."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import bench, harness, ops  # noqa: E402

GEOM = {"rows": 64, "cols": 256, "parts": 8}
ENGINE = "bmv-1024x384.mc1024"
# the served cell waits for its rate from a sweep on the chip (PERF.md,
# open questions); its runner is driven here under a cell entry of its own
SERVED = "service-bmv-1024x384.steady"
SERVED_TRAFFIC = {"loop": "open", "arrivals": "poisson", "rate_per_s": 40.0,
                  "mix": {"bmv": 1}, "max_units": 64}
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
SPEC["workloads"].append({"name": SERVED, "config": "service-bmv-1024x384",
                          "traffic": "steady", "chips": 1})
SPEC["end_to_end"].append({"name": "req_p90_ms", "unit": "ms",
                           "workloads": [SERVED]})


def run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", ENGINE,
         "--seed", str(2**40 + 1), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_exits_nonzero_without_a_tpu():
    p = run_py(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in SPEC["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run_py(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def engine_config(op):
    spec = ({"op": "binary_matvec", "shape": [16, 24]} if op == "bmv"
            else {"op": "matvec", "shape": [16, 4], "N": 8})
    cfg = dict(bench.load_config("bmv-1024x384"), geometry=GEOM, plan=spec,
               backend="numpy")
    cp = ops.make_plan(spec, GEOM).compile()
    cfg["cycles"], cfg["stats"] = cp.n_cycles, dict(cp.stats)
    return cfg


def service_config():
    cfg = dict(bench.load_config("service-bmv-1024x384"), geometry=GEOM,
               backend="numpy")
    cfg["requests"] = [
        {"name": "bmv", "op": "binary_matvec", "shape": [16, 24]}]
    return cfg


def run_small(workload, config, traffic, seconds=0.3, control=False):
    import jax
    result, lines, info = harness.run_cell(
        SPEC, workload, seed=2**41 + 7, seconds=seconds, trace=False,
        t0=time.perf_counter(), devs=jax.devices(), config=config,
        traffic=traffic, control=control)
    return result, lines


# -- faults planted under the timed path (``CrossbarPlan.execute_batch``) --


def unchanged(real):
    """A step that returns its state unchanged."""
    def execute(cp, mem, **kw):
        res = real(cp, mem[:1], **kw)
        res.mem = np.array(mem, copy=True)
        return res
    return execute


def half_batch(real):
    """Half of the batch left out: only the first half is replayed."""
    def execute(cp, mem, **kw):
        res = real(cp, mem, **kw)
        res.mem[len(mem) // 2:] = mem[len(mem) // 2:]
        return res
    return execute


def altered(real):
    """An answer altered where it is produced: crossbar 0 flipped."""
    def execute(cp, mem, **kw):
        res = real(cp, mem, **kw)
        res.mem[0] ^= 1
        return res
    return execute


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "altered": altered}


def plant(monkeypatch, fault):
    from repro.core import plan as plan_mod
    if fault is not None:
        monkeypatch.setattr(plan_mod, "execute",
                            FAULTS[fault](plan_mod.execute))


@pytest.mark.parametrize("fault", [None, *FAULTS])
@pytest.mark.parametrize("op", ["bmv", "mv"])
def test_engine_cell_correct_only_when_sound(monkeypatch, op, fault):
    plant(monkeypatch, fault)
    result, lines = run_small(ENGINE, engine_config(op),
                              {"loop": "batch", "crossbars": 70})
    assert result["correct"] is (fault is None)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert set(result["metrics"]) == {"xbar_cycles_per_s", "setup_s"}
    assert result["checks"]["wrong_crossbars"]["limit"] == 0
    assert (result["failed"] > 0) is (fault is not None)
    assert len(lines) == len(result["checks"])


@pytest.mark.parametrize("fault", [None, *FAULTS])
def test_service_cell_correct_only_when_sound(monkeypatch, fault):
    plant(monkeypatch, fault)
    result, _ = run_small(SERVED, service_config(), SERVED_TRAFFIC,
                          seconds=0.5)
    assert result["correct"] is (fault is None)
    assert set(result["metrics"]) == {"req_p90_ms", "setup_s"}
    assert result["attempted"] == 20
    assert result["failed"] <= result["attempted"]
    assert (result["checks"]["wrong_results"]["value"] > 0) is \
        (fault is not None)


# -- the control in the program's place -------------------------------------


@pytest.mark.parametrize("op", ["bmv", "mv"])
def test_engine_control_is_not_correct(op):
    cfg = engine_config(op)
    if op == "mv":      # the control breaks the 2N-bit sum only where N > 16
        cfg["plan"] = {"op": "matvec", "shape": [8, 2], "N": 32}
        cfg["geometry"] = {"rows": 32, "cols": 1024, "parts": 32}
    result, _ = run_small(ENGINE, cfg, {"loop": "batch", "crossbars": 40},
                          control=True)
    assert result["correct"] is False
    assert result["checks"]["wrong_crossbars"]["value"] > 0
    assert result["checks"]["wrong_cycles_calls"]["value"] == 0


def test_service_control_is_not_correct():
    cfg = service_config()
    cfg["requests"][0]["shape"] = [64, 8]       # ties are common
    result, _ = run_small(SERVED, cfg, SERVED_TRAFFIC, seconds=0.5,
                          control=True)
    assert result["correct"] is False
    assert result["checks"]["wrong_results"]["value"] > 0
    assert result["checks"]["lost_requests"]["value"] == 0
