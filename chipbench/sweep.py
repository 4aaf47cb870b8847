#!/usr/bin/env python3
"""Sweep the offered rate of a served cell's mix on the accelerator, to find
the highest rate the service sustains (the rate its traffic file fixes at
0.8 of that).

    python3 chipbench/sweep.py --workload service-bmv-1024x384.steady \\
        --rates 20,40,60,80 --seconds 20 --seed 9

One process: set-up once, then one open-loop window per rate, in the order
given, each with its own schedule from the seed. One JSON line per rate:
offered and served rate, p50/p90 latency, how long the queue took to drain
after the last request was due, how late the generator submitted, and
whether the rate was sustained: every request completed and correct, the
served rate within 5% of the offered one, and the queue drained within
``DRAIN_OK_S``. The sweep stops after two rates in a row that were not
sustained; its last line names the knee, the highest rate below which
every rate swept was sustained.
Exits non-zero without a TPU.
"""
import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
DRAIN_OK_S = 1.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    from chipbench import bench

    bench.use_checkout_cache(ROOT)
    spec = bench.load_benchmark(ROOT)
    cell = bench.cell(spec, args.workload)
    cfg = bench.load_config(cell["config"])
    traffic = bench.load_traffic(cell["traffic"])
    kinds = {k["name"]: k for k in cfg["requests"]}
    runner = bench.load_module("runners", traffic["loop"])
    svc = runner.make_service(cfg)
    runner.warm(svc, kinds, traffic["mix"], args.seed)
    clock = time.perf_counter
    knee, missed, misses = None, False, 0
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(traffic, rate_per_s=rate)
        units0, batches0 = svc.stats.units, svc.stats.batches
        reqs = runner.draw(kinds, mix, args.seconds, args.seed + i)
        start, tickets = runner.open_loop(
            svc, reqs, kinds, int(mix["max_units"]), clock,
            args.seconds + runner.DRAIN_S)
        loop_s = clock() - start
        lat, lags, wrong = runner.judge(reqs, tickets, kinds, start)
        served = sum(map(math.isfinite, lat))
        drain_s = loop_s - reqs[-1][0]
        ok = (served == len(reqs) and not wrong and drain_s <= DRAIN_OK_S
              and served / loop_s >= 0.95 * rate)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs),
            "served_per_s": served / loop_s,
            "p50_ms": runner.nearest_rank(lat, 0.5) * 1e3,
            "p90_ms": runner.nearest_rank(lat, 0.9) * 1e3,
            "drain_s": drain_s,
            "gen_lag_max_ms": max(lags, default=0.0) * 1e3,
            "units_per_batch": (svc.stats.units - units0)
            / max(1, svc.stats.batches - batches0),
            "wrong": wrong, "sustained": ok}), flush=True)
        if ok and not missed:
            knee = rate
        missed = missed or not ok
        misses = 0 if ok else misses + 1
        if misses == 2:
            break
    svc.close()
    print(json.dumps({"knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
