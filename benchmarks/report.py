"""Generate the EXPERIMENTS.md summary tables.

Covers the perf-trajectory records (``BENCH_engine/device/apps.json`` at the
repo root — MISSING files are a hard error, not a silent skip), a per-metric
delta table against the previous committed run (``git show HEAD:BENCH_*``)
that flags >20% wall-time regressions, and the §Dry-run / §Roofline tables
from ``results/``.

    PYTHONPATH=src python -m benchmarks.report > /tmp/tables.md
"""
from __future__ import annotations

import glob
import json
import subprocess
import sys
from pathlib import Path

# repo-root-relative so reports work from any CWD
ROOT = Path(__file__).resolve().parent.parent
RESULTS_DIR = ROOT / "results"

# every bench that benchmarks/run.py persists as BENCH_<name>.json; the
# report summarizes all of them and FAILS when one is absent (a missing
# record used to vanish silently, hiding a broken bench from the PR diff)
BENCH_NAMES = ("engine", "device", "apps", "serve")

ARCH_ORDER = ["whisper-tiny", "mamba2-370m", "granite-moe-1b-a400m",
              "arctic-480b", "stablelm-3b", "yi-34b", "olmo-1b",
              "phi4-mini-3.8b", "qwen2-vl-2b", "jamba-1.5-large-398b"]
SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def load():
    cells = {}
    for f in glob.glob(str(RESULTS_DIR / "*.json")):
        d = json.load(open(f))
        if "arch" not in d:
            continue
        cells[(d["arch"], d["shape"], d.get("mesh", "?"))] = d
    return cells


def gb(x):
    return f"{x/1e9:.1f}"


def dryrun_table(cells):
    print("| arch | shape | mesh | ok | compile_s | bytes/dev (args+temp) | "
          "peak GB | collectives (AR/AG/RS/A2A GB, trip-corrected) |")
    print("|---|---|---|---|---|---|---|---|")
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            for mesh in ["16x16", "2x16x16"]:
                d = cells.get((arch, shape, mesh))
                if d is None:
                    continue
                if not d.get("ok"):
                    print(f"| {arch} | {shape} | {mesh} | FAIL | | | | "
                          f"{d.get('error','')[:60]} |")
                    continue
                m = d["memory"]
                cb = d["collective_bytes"]
                coll = "/".join(gb(cb.get(k, 0)) for k in
                                ["all-reduce", "all-gather",
                                 "reduce-scatter", "all-to-all"])
                print(f"| {arch} | {shape} | {mesh} | OK | "
                      f"{d['compile_s']} | {gb(m['args_bytes'])}+"
                      f"{gb(m['temp_bytes'])} | {gb(m['peak_bytes'])} | "
                      f"{coll} |")


def roofline_table(cells):
    print("| arch | shape | compute_s | memory_s | collective_s (operand) | "
          "wire_s | dominant | MODEL_FLOPS | useful/HLO | roofline frac | "
          "what would move the bottleneck |")
    print("|---|---|---|---|---|---|---|---|---|---|---|")
    advice = {
        "collective_s": "resharding: fewer TP all-reduces (see §Perf)",
        "memory_s": "at HBM roofline for this shape (weights+cache stream)",
        "compute_s": "MXU-bound: larger per-step batch or fewer remat passes",
    }
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            d = cells.get((arch, shape, "16x16"))
            if d is None or not d.get("ok"):
                continue
            t = d["roofline"]
            step = max(t["compute_s"], t["memory_s"], t["collective_s"])
            frac = d["model_flops_total"] / d["chips"] / 197e12 / step \
                if step else 0
            print(f"| {arch} | {shape} | {t['compute_s']:.3f} | "
                  f"{t['memory_s']:.3f} | {t['collective_s']:.3f} | "
                  f"{t.get('collective_wire_s', 0):.3f} | "
                  f"{d['dominant'].replace('_s','')} | "
                  f"{d['model_flops_total']:.2e} | "
                  f"{d['useful_flops_ratio']:.2f} | {frac:.3f} | "
                  f"{advice[d['dominant']]} |")


def hillclimb_table():
    files = sorted(glob.glob(str(RESULTS_DIR / "hillclimb" / "*.json")))
    if not files:
        return
    print("\n### Hillclimb iterations\n")
    print("| target | iteration | compute_s | memory_s | collective_s | "
          "wire_s | peak GB | dominant |")
    print("|---|---|---|---|---|---|---|---|")
    for f in files:
        d = json.load(open(f))
        tgt, name = f.split("/")[-1].replace(".json", "").split("__")
        if not d.get("ok"):
            print(f"| {tgt} | {name} | FAIL | | | | | {d.get('error','')[:40]} |")
            continue
        t = d["roofline"]
        print(f"| {tgt} | {name} | {t['compute_s']:.3f} | {t['memory_s']:.3f} "
              f"| {t['collective_s']:.3f} | {t.get('collective_wire_s',0):.3f} "
              f"| {d['memory']['peak_bytes']/1e9:.0f} | "
              f"{d['dominant'].replace('_s','')} |")


def bench_table():
    """Summarize the stable-schema BENCH_*.json perf records; exit nonzero
    when an expected record is missing instead of skipping it silently."""
    missing = [b for b in BENCH_NAMES
               if not (ROOT / f"BENCH_{b}.json").exists()]
    if missing:
        sys.exit(
            "benchmarks/report.py: missing perf records: "
            + ", ".join(f"BENCH_{b}.json" for b in missing)
            + f" — regenerate with `PYTHONPATH=src python -m benchmarks.run"
            f" --only <bench>` for: {', '.join(missing)}")
    print("| bench | quick | metric | value | derived |")
    print("|---|---|---|---|---|")
    for b in BENCH_NAMES:
        d = json.load(open(ROOT / f"BENCH_{b}.json"))
        for m in d["metrics"]:
            print(f"| {b} | {d['quick']} | {m['name']} | {m['value']:g} | "
                  f"{m['derived']} |")


def _is_walltime_metric(name: str) -> bool:
    """Metrics measured in wall microseconds (bigger = slower = worse).
    Everything else (cycles, accuracy, energy) is deterministic or
    higher-is-better and only gets a 'changed' note, not a regression flag.
    """
    return (name.startswith(("engine/", "serve/")) or name.endswith("_wall")
            or name.endswith("/total"))


REGRESSION_PCT = 20.0


def bench_delta_table() -> list:
    """Per-metric deltas vs the previous committed BENCH_*.json.

    The previous run is whatever ``git show HEAD:BENCH_<b>.json`` holds, so
    in a PR the comparison is against the branch's base state. Returns the
    list of WARNING strings (also printed) so callers/tests can assert on
    them; wall-time metrics regressing by more than ``REGRESSION_PCT``
    percent are flagged.
    """
    print("\n### Perf deltas vs previous committed run\n")
    warnings = []
    printed_header = False
    for b in BENCH_NAMES:
        cur_p = ROOT / f"BENCH_{b}.json"
        if not cur_p.exists():
            continue
        cur = json.load(open(cur_p))
        try:
            prev = json.loads(subprocess.run(
                ["git", "show", f"HEAD:BENCH_{b}.json"], cwd=ROOT,
                capture_output=True, text=True, check=True).stdout)
        except (subprocess.CalledProcessError, FileNotFoundError,
                json.JSONDecodeError):
            print(f"(no previous BENCH_{b}.json at git HEAD — baseline run)")
            continue
        if cur.get("quick") != prev.get("quick"):
            print(f"(BENCH_{b}.json quick={cur.get('quick')} vs previous "
                  f"quick={prev.get('quick')} — values not comparable, "
                  f"skipping deltas)")
            continue
        if not printed_header:
            print("| bench | metric | previous | current | delta |")
            print("|---|---|---|---|---|")
            printed_header = True
        prev_m = {m["name"]: m["value"] for m in prev["metrics"]}
        cur_names = {m["name"] for m in cur["metrics"]}
        for name, pv in prev_m.items():
            if name not in cur_names:
                # a vanished metric is exactly the silent drift this table
                # exists to catch
                print(f"| {b} | {name} | {pv:g} | — | REMOVED |")
                warnings.append(
                    f"WARNING: {name} present in previous BENCH_{b}.json but "
                    f"missing from the current run")
        for m in cur["metrics"]:
            pv = prev_m.get(m["name"])
            if pv is None:
                print(f"| {b} | {m['name']} | — | {m['value']:g} | NEW |")
                continue
            delta = (m["value"] - pv) / pv * 100 if pv else 0.0
            print(f"| {b} | {m['name']} | {pv:g} | {m['value']:g} | "
                  f"{delta:+.1f}% |")
            if _is_walltime_metric(m["name"]) and delta > REGRESSION_PCT:
                warnings.append(
                    f"WARNING: {m['name']} regressed {delta:+.1f}% "
                    f"({pv:g} -> {m['value']:g} us)")
            elif (not _is_walltime_metric(m["name"])
                  and abs(delta) > 0.1):
                warnings.append(
                    f"NOTE: {m['name']} changed {delta:+.1f}% "
                    f"(deterministic metric — expected only with an "
                    f"intentional model change)")
    for w in warnings:
        print(w)
    if printed_header and not warnings:
        print("\nno regressions above "
              f"{REGRESSION_PCT:.0f}% and no deterministic-metric drift")
    return warnings


SCALING_MIN_X = 3.0


def _derived(m: dict) -> dict:
    return dict(kv.split("=", 1) for kv in m["derived"].split(";")
                if "=" in kv)


def sharded_scaling_table() -> None:
    """Hard gate on the sharded-execution records.

    ``BENCH_engine.json`` must carry the single-device jax comparator row
    plus mesh rows for the tiled binary matvec, every mesh row must be
    bit-identical (``correct=True``), and the 8-device modeled lockstep
    throughput must be >= ``SCALING_MIN_X`` times the single-device rate.
    ``BENCH_serve.json`` must carry the parallel-bucket dispatch row.
    Missing rows exit nonzero — a bench run without
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` silently drops
    them, and that must fail loudly, not vanish from the report.
    """
    payload = json.load(open(ROOT / "BENCH_engine.json"))
    quick = bool(payload.get("quick"))
    eng = {m["name"]: m for m in payload["metrics"]}
    jax1 = [n for n in eng
            if n.startswith("engine/tiled_binary_mv_execute_")
            and n.endswith("_jax1")]
    mesh = sorted(n for n in eng
                  if n.startswith("engine/tiled_binary_mv_execute_")
                  and "_mesh" in n)
    if not jax1:
        sys.exit("benchmarks/report.py: BENCH_engine.json is missing the "
                 "single-device engine/tiled_binary_mv_execute_*_jax1 "
                 "comparator row (jax unavailable during the bench run?)")
    if not any(n.endswith("_mesh8") for n in mesh):
        sys.exit("benchmarks/report.py: BENCH_engine.json has no "
                 "engine/tiled_binary_mv_execute_*_mesh8 row — regenerate "
                 "with XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                 "so the sharded-execution rows are measured")
    base = _derived(eng[jax1[0]])
    single_tps = float(base["tiles_per_s"])
    print("\n### Sharded tile execution (modeled lockstep devices)\n")
    print("| row | devices | wall us | tiles/s (serialized) | "
          "tiles/s (modeled parallel) | scaling vs 1 dev | bit-identical |")
    print("|---|---|---|---|---|---|---|")
    print(f"| {jax1[0]} | 1 | {eng[jax1[0]]['value']:g} | {single_tps:g} | "
          f"{single_tps:g} | 1.00x | (oracle) |")
    for n in mesh:
        d = _derived(eng[n])
        if d.get("correct") != "True":
            sys.exit(f"benchmarks/report.py: {n} is not bit-identical to "
                     f"the single-device run (correct={d.get('correct')!r})")
        par = float(d["device_par_tiles_per_s"])
        print(f"| {n} | {d['devices']} | {eng[n]['value']:g} | "
              f"{float(d['tiles_per_s']):g} | {par:g} | "
              f"{par / single_tps:.2f}x | {d['correct']} |")
        if (n.endswith("_mesh8") and not quick
                and par < SCALING_MIN_X * single_tps):
            # quick geometry is 32 tiles = one packed word, where a mesh
            # cannot model a win; the gate applies to the full-size record
            sys.exit(
                f"benchmarks/report.py: {n} modeled 8-device throughput "
                f"{par:g} tiles/s is under {SCALING_MIN_X:g}x the "
                f"single-device {single_tps:g} tiles/s — sharded execution "
                f"is not paying for itself")
    srv = {m["name"]
           for m in json.load(open(ROOT / "BENCH_serve.json"))["metrics"]}
    if "serve/parallel_buckets" not in srv:
        sys.exit("benchmarks/report.py: BENCH_serve.json is missing the "
                 "serve/parallel_buckets multi-device dispatch row")
    d = _derived(next(m for m in
                      json.load(open(ROOT / "BENCH_serve.json"))["metrics"]
                      if m["name"] == "serve/parallel_buckets"))
    print(f"\nserve bucket dispatch: devices={d.get('devices')} "
          f"(used {d.get('devices_used')}), wall ratio vs serial "
          f"{d.get('wall_ratio')} ({d.get('note')})")


SLO_SCHEMA = 2   # v2: warm_restart carries runner_builds / runner_rebuilds
SLO_ROW_KEYS = ("mode", "load_factor", "offered_rps", "achieved_rps",
                "requests", "p50_ms", "p95_ms", "p99_ms",
                "mean_queue_units", "max_queue_units", "hit_rate", "batches")
SLO_COLD_KEYS = ("warm_wall_s", "compile_s", "warmup_s")
SLO_RESTART_KEYS = ("requests", "replay_wall_s", "first_batch_ms",
                    "steady_p95_ms", "compile_s", "warmup_s", "store_hits",
                    "misses", "compile_programs", "runner_builds",
                    "runner_rebuilds", "p50_ms", "p95_ms", "p99_ms")
# warm restart must land the very first batch within this factor of steady
# p95 — the batch-polymorphic runner makes this a hard gate, not a warning
RESTART_RATIO_MAX = 1.25


def validate_slo(payload: dict) -> list:
    """Schema check for ``BENCH_slo.json``; returns a list of problems.

    The contract: ≥3 offered-load rows, every row carries the full
    latency/throughput/queue/hit-rate column set, percentiles are ordered,
    exactly one row is the closed-loop capacity measurement, and the
    payload carries both a ``cold_start`` account and a ``warm_restart``
    block proving the plan-store replay ran compile-free.
    """
    errs = []
    if payload.get("schema") != SLO_SCHEMA:
        errs.append(f"schema {payload.get('schema')!r} != {SLO_SCHEMA}")
    if payload.get("bench") != "slo":
        errs.append(f"bench {payload.get('bench')!r} != 'slo'")
    cold = payload.get("cold_start")
    if not isinstance(cold, dict) \
            or any(k not in cold for k in SLO_COLD_KEYS):
        errs.append(f"cold_start block missing/incomplete: {cold!r}")
    wr = payload.get("warm_restart")
    if not isinstance(wr, dict):
        errs.append("missing warm_restart block (slo.py always emits one)")
    else:
        missing = [k for k in SLO_RESTART_KEYS if k not in wr]
        if missing:
            errs.append(f"warm_restart missing keys: {missing}")
        elif wr["compile_programs"] != 0:
            errs.append(
                f"warm_restart ran {wr['compile_programs']} compiles — the "
                f"plan-store replay must be compile-free")
    rows = payload.get("rows")
    if not isinstance(rows, list) or len(rows) < 3:
        errs.append(f"need >=3 offered-load rows, got "
                    f"{len(rows) if isinstance(rows, list) else rows!r}")
        return errs
    closed = 0
    for i, r in enumerate(rows):
        missing = [k for k in SLO_ROW_KEYS if k not in r]
        if missing:
            errs.append(f"row {i} missing keys: {missing}")
            continue
        if r["mode"] not in ("closed", "open"):
            errs.append(f"row {i} mode {r['mode']!r}")
        closed += r["mode"] == "closed"
        if not r["p50_ms"] <= r["p95_ms"] <= r["p99_ms"]:
            errs.append(f"row {i} percentiles out of order: "
                        f"{r['p50_ms']}/{r['p95_ms']}/{r['p99_ms']}")
        if r["mode"] == "open" and not r["offered_rps"] > 0:
            errs.append(f"row {i} open-loop offered_rps {r['offered_rps']!r}")
    if closed != 1:
        errs.append(f"expected exactly one closed-loop row, got {closed}")
    return errs


def _slo_row_key(r: dict) -> tuple:
    return (r["mode"], r["load_factor"])


def slo_table() -> list:
    """Summarize + schema-validate ``BENCH_slo.json`` and delta-flag p95
    regressions above ``REGRESSION_PCT`` percent vs the previous committed
    run (rows matched by ``(mode, load_factor)``). A missing or
    schema-invalid record is a hard error, mirroring :func:`bench_table`.
    Returns the WARNING strings (also printed)."""
    p = ROOT / "BENCH_slo.json"
    if not p.exists():
        sys.exit("benchmarks/report.py: missing BENCH_slo.json — regenerate "
                 "with `PYTHONPATH=src python -m benchmarks.slo [--quick]`")
    cur = json.load(open(p))
    errs = validate_slo(cur)
    if errs:
        sys.exit("benchmarks/report.py: BENCH_slo.json schema invalid: "
                 + "; ".join(errs))
    print("\n### SLO under offered load (BENCH_slo.json)\n")
    print(f"backend={cur.get('backend')} slots={cur.get('slots')} "
          f"requests/row={cur.get('requests_per_row')} "
          f"quick={cur.get('quick')}\n")
    print("| mode | load | offered rps | achieved rps | p50 ms | p95 ms | "
          "p99 ms | queue mean/max | hit rate |")
    print("|---|---|---|---|---|---|---|---|---|")
    for r in cur["rows"]:
        lf = "—" if r["load_factor"] is None else f"×{r['load_factor']:g}"
        off = "—" if r["offered_rps"] is None else f"{r['offered_rps']:.1f}"
        print(f"| {r['mode']} | {lf} | {off} | {r['achieved_rps']:.1f} | "
              f"{r['p50_ms']:.2f} | {r['p95_ms']:.2f} | {r['p99_ms']:.2f} | "
              f"{r['mean_queue_units']:.1f}/{r['max_queue_units']} | "
              f"{r['hit_rate']:.3f} |")

    warnings = []
    wr = cur.get("warm_restart") or {}
    if wr:
        print(f"\nwarm restart (plan store replay): first batch "
              f"{wr['first_batch_ms']:.2f} ms vs steady p95 "
              f"{wr['steady_p95_ms']:.2f} ms, {wr['store_hits']}/"
              f"{wr['misses']} store hits, {wr['compile_programs']} "
              f"compiles, replay {wr['replay_wall_s']:.2f} s")
        print(f"runner builds: {wr['runner_builds']} on replay "
              f"(batch-polymorphic: at most one per program x backend), "
              f"{wr['runner_rebuilds']} on re-replay of the same traffic")
        # hard gates, not warnings: the canonical packed layout makes both
        # properties structural, so any excursion is a cache/layout bug
        if wr["runner_rebuilds"] != 0:
            sys.exit(
                f"benchmarks/report.py: warm-restart re-replay built "
                f"{wr['runner_rebuilds']} runners — replaying identical "
                f"traffic on a warm service must build zero (the runner "
                f"cache is being rekeyed or evicted)")
        if wr["first_batch_ms"] > RESTART_RATIO_MAX * wr["steady_p95_ms"]:
            sys.exit(
                f"benchmarks/report.py: warm-restart first batch "
                f"{wr['first_batch_ms']:.2f} ms exceeds "
                f"{RESTART_RATIO_MAX:g}x steady-state p95 "
                f"({wr['steady_p95_ms']:.2f} ms) — store replay is not "
                f"restoring steady-state latency")
    try:
        prev = json.loads(subprocess.run(
            ["git", "show", "HEAD:BENCH_slo.json"], cwd=ROOT,
            capture_output=True, text=True, check=True).stdout)
    except (subprocess.CalledProcessError, FileNotFoundError,
            json.JSONDecodeError):
        print("\n(no previous BENCH_slo.json at git HEAD — baseline run)")
        return warnings
    if cur.get("quick") != prev.get("quick"):
        print(f"\n(BENCH_slo.json quick={cur.get('quick')} vs previous "
              f"quick={prev.get('quick')} — p95 deltas not comparable, "
              f"skipping)")
        return warnings
    pc, cc = prev.get("cold_start") or {}, cur.get("cold_start") or {}
    for k in SLO_COLD_KEYS:
        pv, cv = pc.get(k), cc.get(k)
        if pv and cv is not None:
            delta = (cv - pv) / pv * 100
            if delta > REGRESSION_PCT:
                warnings.append(
                    f"WARNING: slo cold_start {k} regressed {delta:+.1f}% "
                    f"({pv:.2f} -> {cv:.2f} s)")
    prev_rows = {_slo_row_key(r): r for r in prev.get("rows", [])
                 if all(k in r for k in SLO_ROW_KEYS)}
    for r in cur["rows"]:
        pr = prev_rows.get(_slo_row_key(r))
        if pr is None or not pr["p95_ms"]:
            continue
        delta = (r["p95_ms"] - pr["p95_ms"]) / pr["p95_ms"] * 100
        if delta > REGRESSION_PCT:
            lf = r["load_factor"]
            warnings.append(
                f"WARNING: slo {r['mode']}"
                + (f" x{lf:g}" if lf is not None else "")
                + f" p95 regressed {delta:+.1f}% "
                f"({pr['p95_ms']:.2f} -> {r['p95_ms']:.2f} ms)")
    for w in warnings:
        print(w)
    if not warnings:
        print(f"\nno SLO p95 regressions above {REGRESSION_PCT:.0f}%")
    return warnings


def main():
    cells = load()
    n_ok = sum(1 for d in cells.values() if d.get("ok"))
    print(f"<!-- generated by benchmarks/report.py: {len(cells)} cells, "
          f"{n_ok} OK -->\n")
    print("## §Perf trajectory (BENCH_*.json)\n")
    bench_table()
    bench_delta_table()
    sharded_scaling_table()
    slo_table()
    print("\n## §Dry-run\n")
    dryrun_table(cells)
    print("\n## §Roofline (single-pod 16x16, per-device terms)\n")
    roofline_table(cells)
    hillclimb_table()


if __name__ == "__main__":
    main()
