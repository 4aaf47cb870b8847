"""Benchmark harness — one function per paper table/figure + kernel micro-
benchmarks + the roofline collector. Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run [--quick]

The ``engine``, ``device``, ``apps`` and ``serve`` benches additionally
write stable-schema ``BENCH_engine.json`` / ``BENCH_device.json`` /
``BENCH_apps.json`` / ``BENCH_serve.json`` at the repo root (uploaded as a
CI artifact) so the perf trajectory is tracked across PRs.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
RESULTS_DIR = ROOT / "results"      # dryrun/roofline JSONs, CWD-independent

# benches that persist a BENCH_<name>.json perf record at the repo root
_JSON_BENCHES = ("engine", "device", "apps", "serve")
_RECORDS: dict = {}
_CUR: list = [None]


def _rec(name: str, value, derived: str = "") -> None:
    """Print one CSV row and record it for the bench's JSON artifact."""
    shown = (str(value) if not isinstance(value, float)
             else f"{value:.0f}" if abs(value) >= 100 else f"{value:.4f}")
    print(f"{name},{shown},{derived}")
    if _CUR[0] in _JSON_BENCHES:
        _RECORDS.setdefault(_CUR[0], []).append(
            {"name": name, "value": float(value), "derived": derived})


def _write_bench_json(bench: str, quick: bool) -> None:
    path = ROOT / f"BENCH_{bench}.json"
    payload = {
        "schema": 1,
        "bench": bench,
        "quick": bool(quick),
        "generated_by": "benchmarks/run.py",
        "metrics": _RECORDS.get(bench, []),
    }
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"wrote {path}", file=sys.stderr)


def _timeit(fn, n=3, warmup=1):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6  # us


def _best_of(fn, n=3, warmup=1):
    """Min-of-n wall time (us). Preferred over the mean for the engine
    comparison rows: this container's wall clock jitters 2-3x under host
    contention, and min-of-n is the stable statistic for same-work runs."""
    for _ in range(warmup):
        fn()
    return min(_timeit(fn, n=1, warmup=0) for _ in range(n))


def bench_table1_matvec(quick=False):
    """Paper Table I: matrix-vector multiplication latency [cycles]."""
    from repro.core import latency
    rows = latency.build_table1()
    print(latency.format_rows(rows, "Table I: matrix-vector mult [cycles]"),
          file=sys.stderr)
    for r in rows:
        paper = r.paper_proposed or (
            r.paper_baseline if isinstance(r.paper_baseline, int) else None)
        ratio = round(r.ours / paper, 3) if paper else ""
        print(f"table1/{r.name}/{r.config.replace(' ', '_')},"
              f"{r.ours},cycles_ratio_vs_paper={ratio}")


def bench_table2_conv(quick=False):
    """Paper Table II: 2D convolution latency [cycles]."""
    from repro.core import latency
    rows = latency.build_table2()
    print(latency.format_rows(rows, "Table II: 2D convolution [cycles]"),
          file=sys.stderr)
    for r in rows:
        paper = r.paper_proposed or (
            r.paper_baseline if isinstance(r.paper_baseline, int) else None)
        ratio = round(r.ours / paper, 3) if paper else ""
        print(f"table2/{r.name}/{r.config.replace(' ', '_')},"
              f"{r.ours},cycles_ratio_vs_paper={ratio}")


def bench_engine(quick=False):
    """Compiled engine vs the per-op interpreter, end-to-end (load+run+decode).

    Reports the single-array case, the batched multi-instance case (the
    engine's bit-plane packing simulates up to 64 crossbars per word), and
    the tiled multi-crossbar matvec that exceeds a single 1024x1024 array.
    The plain ``numpy``/``jax`` backends replay the fused macro-op schedule;
    the ``*_unfused`` rows keep the per-cycle executors measured so the
    fusion win (and any regression) stays visible across PRs. Cycle counts
    are asserted identical across every backend — fusion must never touch
    the latency model.
    """
    import numpy as np
    from repro.core import BinaryMatvecPlan, have_jax, tiled_binary_matvec

    rng = np.random.default_rng(0)
    m, n = (256, 128) if quick else (1024, 384)
    plan = BinaryMatvecPlan(m, n)
    A = rng.choice([-1, 1], size=(m, n))
    x = rng.choice([-1, 1], size=n)
    cp = plan.compile()  # exclude one-time compile from the comparison
    cycles = len(plan.program)
    assert cp.schedule.n_cycles == cycles
    segs = cp.schedule.n_segments
    # fused jax measured BEFORE unfused: the unfused runner's device
    # buffers/executables bloat the XLA arena and skew later rows on this
    # memory-tight container
    backends = ("numpy_unfused", "numpy") + (
        ("jax", "jax_unfused") if have_jax() else ())

    def run_be(be):
        _, _, c = plan.run(A, x, backend=be.replace("_unfused", "-unfused"))
        assert c == cycles, (be, c, cycles)

    t_int = _best_of(lambda: plan.run(A, x, backend="interp"), n=2, warmup=1)
    _rec(f"engine/binary_mv_{m}x{n}_interp", t_int,
         f"backend=interp;cycles={cycles}")
    for be in backends:
        t = _best_of(lambda: run_be(be), n=5, warmup=1)
        extra = f";segments={segs}" if "unfused" not in be else ""
        _rec(f"engine/binary_mv_{m}x{n}_{be}", t,
             f"speedup_vs_interp={t_int/t:.1f};cycles={cycles}{extra}")

    # batched: B independent crossbar instances in one engine call
    B = 8 if quick else 32
    mems = np.zeros((B, plan.rows, plan.cols), dtype=np.uint8)
    for b in range(B):
        plan.load_into(mems[b], rng.choice([-1, 1], size=(m, n)),
                       rng.choice([-1, 1], size=n))
    xb = plan.new_crossbar()

    def interp_batch():
        for b in range(B):
            xb.mem[:, :] = mems[b]
            xb.run(plan.program)

    t_int = _best_of(interp_batch, n=1, warmup=0)
    _rec(f"engine/binary_mv_batch{B}_interp", t_int,
         f"backend=interp;cycles={cycles}")
    for be in backends:
        t = _best_of(lambda: plan.execute_batch(
            mems, backend=be.replace("_unfused", "-unfused")), n=5, warmup=1)
        _rec(f"engine/binary_mv_batch{B}_{be}", t,
             f"speedup_vs_interp={t_int/t:.1f};cycles={cycles}")

    # wide batches (past one jax word): the regime where fusion historically
    # LOST to per-cycle numpy — measured vs per-cycle numpy as reference (the
    # interpreter would dominate the bench)
    cp._caches.pop("jax_runner", None)   # release the unfused jit + buffers
    if not quick:
        for B in (64, 128):
            mems = np.zeros((B, plan.rows, plan.cols), dtype=np.uint8)
            for b in range(B):
                plan.load_into(mems[b], rng.choice([-1, 1], size=(m, n)),
                               rng.choice([-1, 1], size=n))
            t_ref = _best_of(lambda: plan.execute_batch(
                mems, backend="numpy-unfused"), n=2, warmup=1)
            _rec(f"engine/binary_mv_batch{B}_numpy_unfused", t_ref,
                 f"backend=numpy-unfused;cycles={cycles}")
            for be in ("numpy",) + (("jax",) if have_jax() else ()):
                t = _best_of(lambda: plan.execute_batch(mems, backend=be),
                             n=2, warmup=1)
                _rec(f"engine/binary_mv_batch{B}_{be}", t,
                     f"speedup_vs_numpy_unfused={t_ref/t:.1f};"
                     f"cycles={cycles}")

    # tiled scale-out: (M, K) exceeding a single 1024x1024 crossbar
    M, K = (2048, 768) if quick else (4096, 2048)
    A = rng.choice([-1, 1], size=(M, K))
    xv = rng.choice([-1, 1], size=K)
    t0 = time.perf_counter()
    y, info = tiled_binary_matvec(A, xv)
    us = (time.perf_counter() - t0) * 1e6
    ok = bool(np.array_equal(y, np.where(A @ xv >= 0, 1, -1)))
    _rec(f"engine/tiled_binary_mv_{M}x{K}", us,
         f"tiles={info.n_tiles};cycles={info.cycles};"
         f"reduce_depth={info.reduce_depth};correct={ok}")

    # sharded tile execution: the same matvec tiled over 128-row crossbars
    # (160 tiles at 4096x2048 — the scale-out regime where the tile batch
    # exceeds one packed word, so a single device must serialize word
    # passes and extra devices genuinely absorb them; at <=32 tiles one
    # word covers the whole batch and a mesh cannot help).  Runs under
    # XLA_FLAGS=--xla_force_host_platform_device_count=8; without the flag
    # jax.device_count()==1 and the mesh rows are skipped — report.py
    # hard-fails on the committed record if they are absent.  The container
    # is a single CPU core, so device parallelism cannot appear as
    # wall-clock: each row records the honest serialized wall plus the
    # modeled lockstep-device throughput (tiles / (wall/D), every device
    # running its chunks concurrently), which is what the >=3x scaling
    # acceptance is checked against.
    if have_jax():
        import jax

        from repro.core.tiling import TiledBinaryMatvec
        from repro.distributed.mesh_exec import chunk_widths, tile_mesh

        tb = TiledBinaryMatvec(M, K, rows=128)
        load, _dec, _fin = tb.bind(A, xv)
        B = tb.n_tiles
        mems = np.zeros((B, tb.plan.rows, tb.plan.cols), dtype=np.uint8)
        for b in range(B):
            load(b, mems[b])
        ref = tb.plan.execute_batch(mems, backend="jax")   # warm + oracle
        t1 = _best_of(lambda: tb.plan.execute_batch(mems, backend="jax"),
                      n=3, warmup=0)
        _rec(f"engine/tiled_binary_mv_execute_{M}x{K}_jax1", t1,
             f"devices=1;tiles={B};tile={tb.tile_m}x{tb.tile_k};"
             f"tiles_per_s={B / (t1 / 1e6):.0f};backend={ref.backend}")
        ndev = jax.device_count()
        for D in (2, 4, 8):
            if D > ndev or B < D:
                print(f"engine: skipping mesh{D} rows "
                      f"(devices={ndev}, tiles={B})", file=sys.stderr)
                continue
            mesh = tile_mesh(D)
            res = tb.plan.execute_batch(mems, backend="jax", mesh=mesh)
            okm = bool(np.array_equal(res.mem, ref.mem)
                       and res.backend.endswith(f"+mesh{D}"))
            t = _best_of(lambda: tb.plan.execute_batch(
                mems, backend="jax", mesh=mesh), n=3, warmup=0)
            tps = B / (t / 1e6)
            _rec(f"engine/tiled_binary_mv_execute_{M}x{K}_mesh{D}", t,
                 f"devices={D};tiles={B};chunks={len(chunk_widths(B, D))};"
                 f"backend={res.backend};tiles_per_s={tps:.0f};"
                 f"device_par_tiles_per_s={tps * D:.0f};"
                 f"model=devices-lockstep;"
                 f"speedup_modeled={t1 / (t / D):.2f};correct={okm}")


def bench_device(quick=False):
    """Device subsystem: energy/EDP table for all four algorithm plans,
    Monte-Carlo fault-rate→accuracy curves (raw binary matvec + end-to-end
    BNN layer), and MIN3-TMR mitigation cost/recovery."""
    from repro.device import (binary_matvec_sweep, bnn_accuracy_sweep,
                              energy_table, format_energy_rows, format_sweep,
                              get_profile, tmr_binary_matvec)

    profile = get_profile(None)
    t0 = time.perf_counter()
    rows = energy_table(profile, quick=quick)
    us = (time.perf_counter() - t0) * 1e6
    print(format_energy_rows(
        rows, f"Energy/EDP, profile={profile.name} (all four algorithms)"),
        file=sys.stderr)
    for r in rows:
        _rec(f"device/energy/{r.name}/{r.config.replace(' ', '_')}",
             float(r.cycles),
             f"energy_nj={r.energy_nj:.3f};edp_fj_ns={r.edp_fj_ns:.3e};"
             f"gate_events={r.gate_events};init_cells={r.init_cells}")
    _rec("device/energy_table_wall", us, f"profile={profile.name}")

    rates = [1e-4, 3e-4, 1e-3, 3e-3, 1e-2]
    samples = 256 if quick else 1024
    t0 = time.perf_counter()
    pts = binary_matvec_sweep(rates, samples=samples)
    us = (time.perf_counter() - t0) * 1e6
    print(format_sweep(pts, f"Monte-Carlo binary matvec ({samples} samples "
                            f"per rate)"), file=sys.stderr)
    for p in pts:
        _rec(f"device/mc_bmv/rate_{p.rate:.0e}", p.accuracy,
             f"ber={p.bit_error_rate:.4f};sign_err={p.sign_error_rate:.4f};"
             f"samples={p.samples}")
    _rec("device/mc_bmv_wall", us, f"samples={samples};rates={len(rates)}")

    t0 = time.perf_counter()
    pts = bnn_accuracy_sweep(rates, n_inputs=samples)
    us = (time.perf_counter() - t0) * 1e6
    print(format_sweep(pts, f"Monte-Carlo BNN-layer accuracy ({samples} "
                            f"inputs per rate)"), file=sys.stderr)
    for p in pts:
        _rec(f"device/mc_bnn/rate_{p.rate:.0e}", p.accuracy,
             f"ber={p.bit_error_rate:.4f};samples={p.samples}")
    _rec("device/mc_bnn_wall", us, f"samples={samples};rates={len(rates)}")

    t0 = time.perf_counter()
    r = tmr_binary_matvec(1e-3, samples=128 if quick else 512)
    us = (time.perf_counter() - t0) * 1e6
    print(f"TMR @1e-3: err {r.err_raw:.4f} -> {r.err_tmr:.4f}, "
          f"cycles x{r.cycle_overhead:.2f}, energy x{r.energy_overhead:.2f}",
          file=sys.stderr)
    _rec("device/tmr/rate_1e-03", us,
         f"err_raw={r.err_raw:.4f};err_tmr={r.err_tmr:.4f};"
         f"cycle_overhead={r.cycle_overhead:.2f};"
         f"energy_overhead={r.energy_overhead:.2f}")


def bench_apps(quick=False):
    """End-to-end application pipelines (repro.apps): multi-layer BNN
    inference and image-processing chains, per-stage cycles/energy, plus the
    BNN's Monte-Carlo accuracy-under-faults sweep."""
    from repro.apps import BinaryMLP, demo_image, edge_pipeline
    from repro.apps.bnn import fault_sweep
    from repro.device.montecarlo import format_sweep

    # -- BNN inference -------------------------------------------------------
    model = BinaryMLP.from_config(n_layers=2 if quick else 3)
    rng = np.random.default_rng(0)
    x = rng.choice([-1, 1], size=model.dims[0])
    t0 = time.perf_counter()
    y, rep = model.forward(x)
    us = (time.perf_counter() - t0) * 1e6
    ok = bool(np.array_equal(y, model.reference(x)[0]))
    print(rep, file=sys.stderr)
    for s in rep.stages:
        _rec(f"apps/bnn/{s.name}", float(s.cycles),
             f"io_cycles={s.io_cycles};array_nj={s.array_nj:.4f};"
             f"io_nj={s.io_nj:.5f};tiles={s.n_tiles}")
    _rec("apps/bnn/total", us,
         f"cycles={rep.cycles};energy_nj={rep.energy_nj:.4f};"
         f"latency_ns={rep.latency_ns:.0f};dims={'-'.join(map(str, model.dims))};"
         f"correct={ok}")

    rates = [1e-4, 3e-4, 1e-3, 3e-3]
    samples = 128 if quick else 512
    t0 = time.perf_counter()
    pts = fault_sweep(model, rates, samples=samples)
    us = (time.perf_counter() - t0) * 1e6
    print(format_sweep(pts, f"BNN accuracy under faults ({samples} "
                            f"samples/rate, {len(model.weights)} layers)"),
          file=sys.stderr)
    for p in pts:
        _rec(f"apps/bnn_faults/rate_{p.rate:.0e}", p.accuracy,
             f"act_flip={p.bit_error_rate:.4f};samples={p.samples}")
    _rec("apps/bnn_faults_wall", us, f"samples={samples};rates={len(rates)}")

    # -- imaging chain -------------------------------------------------------
    from repro.apps.imaging import edge_reference

    img = demo_image(16, 16) if quick else demo_image(24, 24)
    pipe = edge_pipeline(img.shape, N=8, op="sobel")
    t0 = time.perf_counter()
    mag, rep = pipe.run(img)
    us = (time.perf_counter() - t0) * 1e6
    ok = bool(np.array_equal(np.asarray(mag, dtype=np.int64),
                             edge_reference(img, "sobel")))
    print(rep, file=sys.stderr)
    for s in rep.stages:
        _rec(f"apps/imaging/{s.name}", float(s.cycles),
             f"io_cycles={s.io_cycles};array_nj={s.array_nj:.3f};"
             f"io_nj={s.io_nj:.5f};tiles={s.n_tiles}")
    _rec("apps/imaging/total", us,
         f"cycles={rep.cycles};energy_nj={rep.energy_nj:.3f};"
         f"latency_ns={rep.latency_ns:.0f};image={img.shape[0]}x{img.shape[1]};"
         f"correct={ok}")


def bench_serve(quick=False):
    """Plan-cache serving layer (repro.serve.matpim): batched-bucket
    throughput vs sequential per-request execute on both engine backends,
    plan-cache hit rates, and a mixed-kind continuous-batching stream.

    The headline rows compare R mixed-shape binary-matvec requests that
    bucket onto ONE plan key: ``seq`` executes them one engine call each
    (plan reuse but no batching — what every pre-serve caller does), while
    ``batched`` coalesces the bucket onto the bit-plane batch axis in a
    single flush. Same compiled plan, same results; the speedup is pure
    request coalescing. jit compiles are excluded via a warmup flush.
    """
    from repro.core import have_jax
    from repro.serve.matpim import PlanService, ServeRequest

    rng = np.random.default_rng(0)
    R = 16 if quick else 32
    m_hi, n_hi = (256, 128) if quick else (1024, 256)  # powers of two
    # mixed shapes in (hi/2, hi] so every request pads into one pow2 bucket
    shapes = [(int(rng.integers(m_hi // 2 + 1, m_hi + 1)),
               int(rng.integers(n_hi // 2 + 1, n_hi + 1))) for _ in range(R)]
    reqs = [(rng.choice([-1, 1], size=(m, n)), rng.choice([-1, 1], size=n))
            for m, n in shapes]

    def seq(svc):
        for A, x in reqs:
            svc.submit_binary_matvec(A, x)
            svc.flush()                    # one engine call per request
        return svc

    def batched(svc):
        ts = [svc.submit_binary_matvec(A, x) for A, x in reqs]
        svc.flush()                        # one engine call per bucket
        return ts

    for be in ("numpy",) + (("jax",) if have_jax() else ()):
        svc = PlanService(backend=be)
        seq(svc)                           # warmup: compile plan + runners
        batched(svc)
        t_seq = _best_of(lambda: seq(svc), n=2, warmup=0)
        _rec(f"serve/bmv_stream{R}_seq_{be}", t_seq,
             f"backend={be};requests={R};bucket=({m_hi},{n_hi})")
        t_bat = _best_of(lambda: batched(svc), n=2, warmup=0)
        _rec(f"serve/bmv_stream{R}_batched_{be}", t_bat,
             f"speedup_vs_seq={t_seq/t_bat:.1f};"
             f"hit_rate={svc.stats.hit_rate:.3f};"
             f"batches_per_flush=1;requests={R}")

    # mixed-kind continuous-batching stream (numpy; conv jits are heavy)
    n_each = 4 if quick else 8
    stream = []
    for i in range(n_each):
        m, n = int(rng.integers(8, 48)), int(rng.integers(16, 64))
        stream.append(ServeRequest("binary_matvec",
                                   (rng.choice([-1, 1], size=(m, n)),
                                    rng.choice([-1, 1], size=n))))
        stream.append(ServeRequest("matvec",
                                   (rng.integers(0, 16, size=(m, n)),
                                    rng.integers(0, 16, size=n), 4)))
        img = rng.integers(0, 64, size=(int(rng.integers(8, 17)),
                                        int(rng.integers(8, 17))))
        stream.append(ServeRequest("conv", (img, np.array(
            [[1, 2, 1], [2, 4, 2], [1, 2, 1]]), 8)))
    # The headline mixed row is the WARM-RESTART path (what a production
    # process sees after its first boot): a cold service with async admit
    # populates the persistent plan store — recorded as the _cold row —
    # then a FRESH service replays the same stream from the store with
    # zero compiles. The committed pre-store row (1.7 req/s) was the cold
    # path; the derived string documents the semantics switch.
    import tempfile

    from repro.serve.plan_store import PlanStore

    with tempfile.TemporaryDirectory(prefix="matpim-serve-store-") as sd:
        svc = PlanService(backend="numpy", async_compile=True,
                          store=PlanStore(sd))
        t0 = time.perf_counter()
        tickets = svc.run_stream(iter(stream), slots=32)
        us = (time.perf_counter() - t0) * 1e6
        n_buckets = len({t.key for t in tickets})
        _rec("serve/mixed_stream_cold_numpy", us,
             f"requests={len(tickets)};plan_keys={n_buckets};"
             f"batches={svc.stats.batches};"
             f"hit_rate={svc.stats.hit_rate:.3f};"
             f"async_compiles={svc.stats.async_compiles};"
             f"req_per_s={len(tickets)/(us/1e6):.1f}")
        svc.close()

        svc = PlanService(backend="numpy", store=PlanStore(sd))
        t0 = time.perf_counter()
        tickets = svc.run_stream(iter(stream), slots=32)
        us = (time.perf_counter() - t0) * 1e6
        _rec("serve/mixed_stream_numpy", us,
             f"requests={len(tickets)};plan_keys={n_buckets};"
             f"batches={svc.stats.batches};"
             f"hit_rate={svc.stats.hit_rate:.3f};"
             f"evictions={svc.stats.evictions};restart=warm;"
             f"store_hits={svc.stats.store_hits};"
             f"req_per_s={len(tickets)/(us/1e6):.1f}")
        svc.close()

    # independent ready buckets dispatched across devices: a devices=4
    # service drains the same shuffled heterogeneous stream against the
    # serial comparator.  Results are asserted bit-identical; on this 1-core
    # host the wall ratio hovers near 1.0 (threads serialize on the CPU),
    # so the row's value is the honest parallel wall and the derived string
    # carries both walls plus the device spread of the dispatch.
    mixed = [stream[i] for i in
             np.random.default_rng(11).permutation(len(stream))]

    def drain(svc):
        ts = svc.run_stream(iter(mixed), slots=32)
        svc.flush()
        return ts

    ser = PlanService(backend="numpy")
    ref = drain(ser)                       # warm: compiles every plan
    t_ser = _best_of(lambda: drain(ser), n=2, warmup=0)
    par = PlanService(backend="numpy", devices=4)
    got = drain(par)                       # warm
    assert all(np.array_equal(a.result, b.result)
               for a, b in zip(ref, got)), "parallel-bucket results diverged"
    t_par = _best_of(lambda: drain(par), n=2, warmup=0)
    used = sorted({t.device for t in drain(par)})
    _rec("serve/parallel_buckets", t_par,
         f"devices=4;devices_used={len(used)};requests={len(mixed)};"
         f"serial_us={t_ser:.0f};wall_ratio={t_ser / t_par:.2f};"
         f"batches={par.stats.batches};note=1-core-host-wall;correct=True")
    ser.close()
    par.close()


def bench_slo(quick=False):
    """SLO sweep over the serving layer (see ``benchmarks/slo.py``).

    Writes ``BENCH_slo.json`` with its own richer row schema (validated by
    ``benchmarks/report.py``) and mirrors each row here as a CSV line whose
    value is the row's p95 latency in µs.
    """
    from benchmarks.slo import run_sweep, write_json

    payload = run_sweep(quick=quick)
    write_json(payload, ROOT / "BENCH_slo.json")
    for r in payload["rows"]:
        label = ("closed" if r["load_factor"] is None
                 else f"open_x{r['load_factor']:g}")
        _rec(f"slo/{label}", r["p95_ms"] * 1e3,
             f"p50_ms={r['p50_ms']:.2f};p99_ms={r['p99_ms']:.2f};"
             f"rps={r['achieved_rps']:.1f};hit_rate={r['hit_rate']:.3f};"
             f"queue_mean={r['mean_queue_units']:.1f}")


def bench_kernels(quick=False):
    """Pallas kernels (interpret mode on CPU) vs jnp oracles: wall time."""
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.binary_matmul import binary_matmul
    from repro.kernels.conv2d_shift import conv2d_shift
    from repro.kernels.splitk_matvec import splitk_matvec

    rng = np.random.default_rng(0)
    M = 128 if quick else 256
    a = ref.pack_bits(jnp.asarray(rng.choice([-1, 1], (M, 512)), jnp.float32))
    b = ref.pack_bits(jnp.asarray(rng.choice([-1, 1], (M, 512)), jnp.float32))
    us = _timeit(lambda: binary_matmul(a, b, interpret=True).block_until_ready())
    us_ref = _timeit(lambda: ref.binary_matmul_packed_ref(a, b, 512)
                     .block_until_ready())
    print(f"kernels/binary_matmul_{M}x{M}x512,{us:.0f},interp_vs_ref="
          f"{us/us_ref:.2f}")

    A = jnp.asarray(rng.standard_normal((512, 1024)), jnp.float32)
    x = jnp.asarray(rng.standard_normal(1024), jnp.float32)
    us = _timeit(lambda: splitk_matvec(A, x, interpret=True).block_until_ready())
    print(f"kernels/splitk_matvec_512x1024,{us:.0f},splitk=8way")

    img = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((3, 3)), jnp.float32)
    us = _timeit(lambda: conv2d_shift(img, k, interpret=True).block_until_ready())
    print(f"kernels/conv2d_shift_128x128_3x3,{us:.0f},im2col_free=true")


def bench_train_throughput(quick=False):
    """Reduced-config train-step wall time per arch family (CPU)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import TrainConfig, get_config
    from repro.models import build_model
    from repro.models.spec import init_params
    from repro.train import make_train_step

    archs = ["olmo-1b", "mamba2-370m"] if quick else [
        "olmo-1b", "mamba2-370m", "granite-moe-1b-a400m", "whisper-tiny"]
    for arch in archs:
        cfg = get_config(arch).reduced()
        model = build_model(cfg)
        params = init_params(model.specs(), jax.random.PRNGKey(0), cfg.dtype)
        step, opt = make_train_step(model, TrainConfig())
        s = opt.init(params)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 64)),
                                       jnp.int32),
                 "targets": jnp.asarray(rng.integers(0, cfg.vocab, (4, 64)),
                                        jnp.int32)}
        if cfg.family == "encdec":
            batch["frames"] = jnp.zeros((4, cfg.enc_seq, cfg.d_model),
                                        jnp.dtype(cfg.dtype))
        jstep = jax.jit(step)
        p, st, _ = jstep(params, s, batch)  # compile

        def run():
            nonlocal p, st
            p, st, m = jstep(p, st, batch)
            jax.block_until_ready(m["loss"])

        us = _timeit(run)
        toks = 4 * 64
        print(f"train/{arch}_smoke,{us:.0f},tok_per_s={toks/(us/1e6):.0f}")


def bench_roofline(quick=False):
    """Summarize the dry-run roofline JSONs (results/, repo-root-relative
    so reports work from any CWD)."""
    import glob
    files = sorted(glob.glob(str(RESULTS_DIR / "*.json")))
    if not files:
        print("roofline/none,0,run_dryrun_first=true")
        return
    for f in files:
        d = json.load(open(f))
        if not d.get("ok"):
            print(f"roofline/{d['arch']}_{d['shape']}_{d.get('mesh')},0,FAILED")
            continue
        t = d["roofline"]
        terms = {k: t[k] for k in ("compute_s", "memory_s", "collective_s")}
        bound = max(terms, key=terms.get).replace("_s", "")
        step_s = max(terms.values())
        mfu = (d["model_flops_total"] / d["chips"] / 197e12) / step_s \
            if step_s else 0
        print(f"roofline/{d['arch']}_{d['shape']}_{d['mesh']},"
              f"{step_s*1e6:.0f},bound={bound};roofline_frac={mfu:.3f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    # persistent XLA cache (same as tests/conftest.py): jit compiles are
    # excluded from timed regions via warmups, so this only trims benchmark
    # start-up, locally and in the CI bench/nightly jobs
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()
    benches = {
        "table1": bench_table1_matvec,
        "table2": bench_table2_conv,
        "engine": bench_engine,
        "device": bench_device,
        "apps": bench_apps,
        "serve": bench_serve,
        "slo": bench_slo,
        "kernels": bench_kernels,
        "train": bench_train_throughput,
        "roofline": bench_roofline,
    }
    print("name,us_per_call,derived")
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        _CUR[0] = name
        fn(quick=args.quick)
        _CUR[0] = None
        if name in _JSON_BENCHES:
            _write_bench_json(name, args.quick)


if __name__ == "__main__":
    main()
