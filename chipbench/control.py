#!/usr/bin/env python3
"""A cell's control: the plain reference with one stated guarantee broken,
computed on the accelerator with ``jax.numpy`` (64-bit mode off) in the
program's place, and judged by the cell's own comparison.

    python3 chipbench/control.py --workload mv-1024x8-n32.mc1024 \\
        --seeds 5,6,7 --seconds 51

For each seed it runs the cell as ``run.py`` does, with the control
standing in for the system under test (an engine cell's ``execute_batch``
calls, a served cell's ``PlanService``), and prints the result line, whose
``correct`` has to come out false: its ``checks`` are the upper readings
the cell's limits sit below. The benchmark's own runs never run this.
Exits non-zero without a TPU.
"""
import argparse
import json
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _device(spec, a):
    """Operands as the device takes them: N-bit values fit uint32."""
    import jax.numpy as jnp
    import numpy as np
    return jnp.asarray(a if spec["op"] == "binary_matvec"
                       else np.asarray(a, np.uint32))


def engine_control(cfg, A, x):
    """``(execute, decode)`` for an engine cell: each call computes the
    control of every crossbar on the device and reports the configuration's
    cycles and stats, so only the results can fail."""
    import jax
    import numpy as np

    from chipbench import ops
    spec = cfg["plan"]
    a, b = _device(spec, A), _device(spec, x)
    fn = jax.jit(lambda a, b: ops.control(spec, a, b, jax.numpy))

    def execute():
        return np.asarray(fn(a, b)), cfg["cycles"], dict(cfg["stats"]), \
            "control"
    return execute, np.asarray


class ControlService:
    """``PlanService``'s place in a served cell: each submitted request's
    result is its control, computed on the device at the next step."""

    def __init__(self):
        self.queue = []
        self.stats = types.SimpleNamespace(units=0, batches=0)

    def submit(self, op, a, b, *_args):
        t = types.SimpleNamespace(spec={"op": op}, operands=(a, b),
                                  result=None, done=False, wall_s=None,
                                  submitted_s=time.perf_counter())
        self.queue.append(t)
        return t

    @property
    def pending_units(self):
        return len(self.queue)

    def step(self, max_units=None):
        import jax.numpy as jnp
        import numpy as np

        from chipbench import ops
        n = len(self.queue) if max_units is None else max_units
        todo, self.queue = self.queue[:n], self.queue[n:]
        for t in todo:
            a, b = (_device(t.spec, v) for v in t.operands)
            t.result = np.asarray(ops.control(t.spec, a, b, jnp))
            t.wall_s = time.perf_counter() - t.submitted_s
            t.done = True
        self.stats.units += len(todo)
        self.stats.batches += bool(todo)
        return todo

    def flush(self):
        return self.step()

    def close(self):
        pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from chipbench import bench, harness

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    bench.use_checkout_cache(ROOT)
    spec = bench.load_benchmark(ROOT)
    cell = bench.cell(spec, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        result, lines, _ = harness.run_cell(
            spec, args.workload, seed=seed, seconds=args.seconds,
            trace=False, t0=time.perf_counter(), devs=devs[:cell["chips"]],
            control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": result}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
