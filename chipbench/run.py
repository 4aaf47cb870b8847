#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

    python3 chipbench/run.py --workload bmv-1024x384.mc1024 --seed 7 \\
        --seconds 10 --trace 0

Set-up (imports, plan compile, operands from ``--seed``, warm-up) is timed
from the first line of this file to the first timed call. The window then
measures for ``--seconds``. With ``--trace 1`` the window runs under the
JAX profiler and the result carries the cell's per-layer metrics instead of
its end-to-end ones. The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, optionally
``breakdown``, and last ``checks``: each compared number with its limit);
the comparison lines also end standard error.

Without a TPU, with fewer chips than the cell asks for, or without the
repository's ``src/`` beside ``chipbench/``, it exits non-zero and prints
no result. JAX's compilation cache is kept in the checkout's ``.jax_cache``.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no repro package under {ROOT / 'src'}")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench import bench, harness

    spec = bench.load_benchmark(ROOT)
    try:
        cell = bench.cell(spec, args.workload)
    except KeyError as e:
        return fail(str(e))
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return fail(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < cell["chips"]:
        return fail(f"{args.workload} needs {cell['chips']} chips, JAX "
                    f"found {len(devs)}")
    bench.use_checkout_cache(ROOT)
    compiles = bench.CompileCounter()
    result, lines, info = harness.run_cell(
        spec, args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t0=T0, devs=devs[:cell["chips"]],
        compiles=compiles)
    print(json.dumps({"workload": args.workload, "seed": args.seed, **info}),
          file=sys.stderr)
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
