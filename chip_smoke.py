#!/usr/bin/env python3
"""Run the served crossbar-replay path once on a TPU and check every result.

    python chip_smoke.py             # one chip: the served main path
    python chip_smoke.py --chips 4   # four chips: the multi-device paths only

One chip, at the paper's crossbar geometry (1024x1024 arrays, 32
partitions), each phase checked against a plain numpy reference that does
not go through the simulator:

* ``serve``    a ``PlanService(backend="jax")`` answers requests of each
               served kind: the paper's 1024x384 binary matvec (Table I), a
               4096x2048 binary matvec tiled over many arrays, the paper's
               1024x8 N=32 matvec (8112 cycles, replayed on the unfused
               scan) and a 1024x8 3x3 N=32 convolution (Table II). Results
               equal sign(A@x), A@x mod 2^64 and the integer correlation
               mod 2^32 exactly.
* ``batch``    1024 crossbars of ``BinaryMatvecPlan(1024, 384)`` (32 packed
               words, the Monte-Carlo sample count of the device benchmark)
               through ``execute_batch(backend="jax")``: bit for bit the
               numpy-fused backend's memory, cycles and stats.
* ``faults``   32 crossbars under ``FaultModel(p_switch=1e-3)`` on the jax
               faulty scan; the ideal ``FaultModel()`` equals the fault-free
               run bit for bit, and a repeated seed repeats the draws. A
               sampled ``FaultRealization`` (on a 64x256 array: its masks
               are per cell and cycle) replays on jax-fused bit for bit as
               on numpy-fused.
* ``pallas``   ``backend="pallas"`` on the paper's binary matvec and on the
               paper's matvec and conv shapes at N=8, the widest N whose f32
               accumulation is exact (at N=32 ``pallas_eligible`` refuses);
               the label is exactly ``pallas`` and the decoded results equal
               the jax replay.

Four chips (``--chips 4``): the 160-tile 4096x2048 binary matvec on a
4-device tile mesh equals the one-device run bit for bit, and a
``PlanService(devices=4)`` spreads a mixed stream over several devices with
results identical to ``devices=1``.

Each phase prints one JSON line (device kind, wall seconds, XLA compile
seconds inside the phase, backend labels, peak device bytes). The last line
is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or without the repository's ``src/`` beside it, the script
exits non-zero and prints no result. It runs in one process and starts no
other.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import warnings
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"
GEOM = dict(rows=1024, cols=1024, parts=32)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    """A phase's result differs from its reference."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def signs(A: np.ndarray, x: np.ndarray) -> np.ndarray:
    """sign(A @ x) with ties to +1 (the in-array majority threshold)."""
    return np.where(A.astype(np.int64) @ x.astype(np.int64) >= 0, 1, -1)


def matvec_ref(A: np.ndarray, x: np.ndarray, N: int) -> np.ndarray:
    """A @ x mod 2^(2N) in exact Python integers."""
    return (A.astype(object) @ x.astype(object)) % (1 << (2 * N))


def corr_ref(img: np.ndarray, K: np.ndarray, N: int) -> np.ndarray:
    """Valid 2D correlation mod 2^N in exact Python integers."""
    k = K.shape[0]
    oh, ow = img.shape[0] - k + 1, img.shape[1] - k + 1
    out = np.zeros((oh, ow), dtype=object)
    for v in range(k):
        for h in range(k):
            out += img[v:v + oh, h:h + ow].astype(object) * int(K[v, h])
    return out % (1 << N)


def same(a, b) -> bool:
    return np.array_equal(np.asarray(a, dtype=object),
                          np.asarray(b, dtype=object))


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------


def phase_serve(rng, geom=GEOM, bmv=(1024, 384), tiled=(4096, 2048),
                mv=(1024, 8, 32), conv=(1024, 8, 3, 32)) -> dict:
    from repro.serve.matpim import PlanService

    svc = PlanService(backend="jax", store=False, **geom)
    checks = []

    def pm1(shape):
        return rng.choice(np.array([-1, 1], np.int8), size=shape)

    for m, k in (bmv, bmv, tiled, tiled):
        A, x = pm1((m, k)), pm1(k)
        checks.append(("binary_matvec", (m, k),
                       svc.submit_binary_matvec(A, x), signs(A, x)))
    m, k, N = mv
    for _ in range(2):
        A = rng.integers(0, 1 << N, size=(m, k), dtype=np.int64)
        x = rng.integers(0, 1 << N, size=k, dtype=np.int64)
        checks.append(("matvec", (m, k), svc.submit_matvec(A, x, N),
                       matvec_ref(A, x, N)))
    H, W, kk, N = conv
    for _ in range(2):
        img = rng.integers(0, 1 << N, size=(H, W), dtype=np.int64)
        K = rng.integers(0, 1 << N, size=(kk, kk), dtype=np.int64)
        checks.append(("conv", (H, W), svc.submit_conv(img, K, N),
                       corr_ref(img, K, N)))
    svc.flush()
    for kind, shape, t, want in checks:
        check(t.done and same(t.result, want),
              f"serve {kind} {shape}: result differs from numpy")
    return {"requests": len(checks), "batches": svc.stats.batches,
            "crossbars": svc.stats.units,
            "cycles": {f"{kind}{list(shape)}": t.cycles
                       for kind, shape, t, _ in checks}}


def _bmv_batch(rng, plan, B):
    """``B`` loaded crossbar images of a binary matvec plan + references."""
    A = rng.choice(np.array([-1, 1], np.int8), size=(B, plan.m, plan.n))
    x = rng.choice(np.array([-1, 1], np.int8), size=(B, plan.n))
    mems = np.zeros((B, plan.rows, plan.cols), np.uint8)
    for b in range(B):
        plan.load_into(mems[b], A[b], x[b])
    want = np.where(np.einsum("bmn,bn->bm", A.astype(np.int32),
                              x.astype(np.int32)) >= 0, 1, -1)
    return mems, want


def phase_batch(rng, geom=GEOM, shape=(1024, 384), B=1024) -> dict:
    from repro.core import BinaryMatvecPlan

    plan = BinaryMatvecPlan(*shape, **geom)
    mems, want = _bmv_batch(rng, plan, B)
    res = plan.execute_batch(mems, backend="jax")
    ys = np.stack([plan.decode_y(res.mem[b]) for b in range(B)])
    check(np.array_equal(ys, want), "batch: decoded signs differ from numpy")
    ref = plan.execute_batch(mems, backend="numpy-fused")
    check(res.cycles == ref.cycles and res.stats == ref.stats,
          "batch: cycles or stats differ from numpy-fused")
    check(np.array_equal(res.mem, ref.mem),
          "batch: memory differs from numpy-fused")
    return {"backend": res.backend, "crossbars": B,
            "packed_words": -(-B // 32), "cycles": res.cycles}


def phase_faults(rng, geom=GEOM, shape=(1024, 384), B=32,
                 p_switch=1e-3) -> dict:
    from repro.core import BinaryMatvecPlan
    from repro.device.faults import FaultModel, FaultRealization

    plan = BinaryMatvecPlan(*shape, **geom)
    cp = plan.compile()
    mems, want = _bmv_batch(rng, plan, B)
    free = plan.execute_batch(mems, backend="jax")
    ideal = plan.execute_batch(mems, backend="jax", faults=FaultModel(),
                               rng=7)
    check("jax_runner_faulty" in cp._caches,
          "faults: the faulty scan runner did not run")
    check(np.array_equal(ideal.mem, free.mem),
          "faults: the ideal FaultModel differs from the fault-free run")
    fm = FaultModel(p_switch=p_switch)
    faulty = plan.execute_batch(mems, backend="jax", faults=fm, rng=11)
    again = plan.execute_batch(mems, backend="jax", faults=fm, rng=11)
    check(np.array_equal(faulty.mem, again.mem),
          "faults: the same seed drew different faults")
    check(faulty.cycles == free.cycles, "faults: faults changed the cycles")
    ys = np.stack([plan.decode_y(faulty.mem[b]) for b in range(B)])

    # explicit per-cycle masks replay bit for bit on every backend; their
    # host arrays are per cell and cycle, so this runs on a 64x256 array
    small = BinaryMatvecPlan(16, 24, rows=64, cols=256, parts=8)
    scp = small.compile()
    smems, _ = _bmv_batch(rng, small, B)
    real = FaultRealization.sample(
        FaultModel(p_sa0=2e-3, p_sa1=1e-3, p_switch=1e-3, p_init=1e-3),
        B, small.rows, small.cols, scp.n_cycles, scp.W, scp.I, rng=3)
    rj = small.execute_batch(smems, backend="jax-fused", faults=real)
    rn = small.execute_batch(smems, backend="numpy-fused", faults=real)
    check(np.array_equal(rj.mem, rn.mem),
          "faults: a FaultRealization replays differently on jax-fused")
    return {"backend": faulty.backend, "crossbars": B, "p_switch": p_switch,
            "cells_changed": int((faulty.mem != free.mem).sum()),
            "signs_wrong": int((ys != want).sum()),
            "realization_backend": rj.backend}


def phase_pallas(rng, geom=GEOM, bmv=(1024, 384), mv=(1024, 8, 8),
                 conv=(1024, 4, 3, 8), B=4) -> dict:
    from repro.core import BinaryMatvecPlan, MatvecPlan
    from repro.core.conv import ConvPlan
    from repro.core.engine import execute

    cases = []
    plan = BinaryMatvecPlan(*bmv, **geom)
    ops = [(rng.choice([-1, 1], size=bmv), rng.choice([-1, 1], size=bmv[1]))
           for _ in range(B)]
    cases.append(("binary_matvec", plan, ops, plan.decode_y,
                  [signs(A, x) for A, x in ops]))
    m, n, N = mv
    plan = MatvecPlan(m, n, N, **geom)
    ops = [(rng.integers(0, 1 << N, size=(m, n)),
            rng.integers(0, 1 << N, size=n)) for _ in range(B)]
    cases.append(("matvec", plan, ops, plan.decode_y,
                  [matvec_ref(A, x, N) for A, x in ops]))
    m, n, k, N = conv
    plan = ConvPlan(m, n, k, N, **geom)
    K0 = rng.integers(0, 1 << N, size=(k, k))
    # a program specialized on the kernel serves that one kernel only
    shared = plan.specialize or plan.stream_kernel
    ops = [(rng.integers(0, 1 << N, size=(m, n)),
            K0 if shared else rng.integers(0, 1 << N, size=(k, k)))
           for _ in range(B)]
    plan.ensure_program(K0)
    cases.append(("conv", plan, ops, plan.decode_out,
                  [corr_ref(A, K, N) for A, K in ops]))

    labels = {}
    for kind, plan, ops, decode, want in cases:
        mems = np.zeros((B, plan.rows, plan.cols), np.uint8)
        for b, (a, v) in enumerate(ops):
            plan.load_into(mems[b], a, v)
        cp = plan.compile()
        rp = execute(cp, mems, backend="pallas")
        rj = execute(cp, mems, backend="jax")
        check(rp.backend == "pallas",
              f"pallas {kind}: ran as {rp.backend!r}, not 'pallas'")
        check(rp.cycles == rj.cycles and rp.stats == rj.stats,
              f"pallas {kind}: accounting differs from the trace")
        for b in range(B):
            check(same(decode(rp.mem[b]), decode(rj.mem[b])),
                  f"pallas {kind}: decode differs from the jax replay")
            check(same(decode(rp.mem[b]), want[b]),
                  f"pallas {kind}: result differs from numpy")
        labels[kind] = {"pallas": rp.backend, "replay": rj.backend}
    return {"backends": labels, "instances": B}


# ---------------------------------------------------------------------------
# Four-chip phases
# ---------------------------------------------------------------------------


def phase_mesh(rng, devices=4, shape=(4096, 2048), rows=128) -> dict:
    from repro.core.tiling import TiledBinaryMatvec, majority_sign
    from repro.distributed.mesh_exec import tile_mesh

    M, K = shape
    tb = TiledBinaryMatvec(M, K, rows=rows)
    A = rng.choice(np.array([-1, 1], np.int8), size=(M, K))
    x = rng.choice(np.array([-1, 1], np.int8), size=K)
    load, decode, finalize = tb.bind(A, x)
    mems = np.zeros((tb.n_tiles, tb.plan.rows, tb.plan.cols), np.uint8)
    for b in range(tb.n_tiles):
        load(b, mems[b])
    one = tb.plan.execute_batch(mems, backend="jax")
    res = tb.plan.execute_batch(mems, backend="jax",
                                mesh=tile_mesh(devices))
    check(res.backend.endswith(f"+mesh{devices}"),
          f"mesh: ran as {res.backend!r}, not on the {devices}-device mesh")
    check(np.array_equal(res.mem, one.mem) and res.cycles == one.cycles,
          "mesh: memory differs from the one-device run")
    pop, _ = finalize([decode(b, res.mem[b]) for b in range(tb.n_tiles)])
    check(np.array_equal(majority_sign(pop, K), signs(A, x)),
          "mesh: decoded signs differ from numpy")
    return {"backend": res.backend, "one_device": one.backend,
            "tiles": tb.n_tiles}


# the mixed stream's four buckets, four requests each: (kind, m, k, N).
# Matvec N=32 and conv replay on the unfused scan, which compiles in
# seconds; a fused N=8 matvec would take most of a minute per device.
MIX = (("binary_matvec", 1024, 384, 1), ("binary_matvec", 200, 100, 1),
       ("matvec", 256, 8, 32), ("conv", 64, 8, 8))


def _mixed_stream(rng, mix=MIX):
    from repro.serve.matpim import ServeRequest

    reqs = []
    for i in range(16):
        kind, m, k, N = mix[i % len(mix)]
        if kind == "binary_matvec":
            args = (rng.choice([-1, 1], size=(m, k)),
                    rng.choice([-1, 1], size=k))
        elif kind == "matvec":
            args = (rng.integers(0, 1 << N, size=(m, k), dtype=np.int64),
                    rng.integers(0, 1 << N, size=k, dtype=np.int64), N)
        else:
            args = (rng.integers(0, 1 << N, size=(m, k), dtype=np.int64),
                    rng.integers(0, 1 << N, size=(3, 3), dtype=np.int64), N)
        reqs.append(ServeRequest(kind, args))
    return reqs


def phase_serve_devices(rng, devices=4, geom=GEOM, mix=MIX) -> dict:
    from repro.serve.matpim import PlanService

    reqs = _mixed_stream(rng, mix)
    results = {}
    for d in (devices, 1):
        svc = PlanService(backend="jax", store=False, devices=d, **geom)
        try:
            for r in reqs:
                svc.submit(r.kind, *r.args, **r.kwargs)
            results[d] = svc.flush()
        finally:
            svc.close()
    par, ser = (sorted(results[d], key=lambda t: t.uid)
                for d in (devices, 1))
    check(len(par) == len(ser) == len(reqs), "serve: requests lost")
    for a, b in zip(par, ser):
        check(same(a.result, b.result) and a.cycles == b.cycles,
              f"serve: devices={devices} differs from devices=1")
    used = sorted({t.device for t in par})
    check(len(used) > 1, f"serve: devices={devices} used only {used}")
    return {"requests": len(reqs), "devices_used": used}


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def _peak_bytes(devs) -> list:
    out = []
    for d in devs:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def run_phases(phases, devs, seed: int) -> None:
    """Run each phase, print its JSON line; raise on the first failure."""
    import jax

    from repro.obs import metrics

    compile_s = [0.0]

    def on_event(event, duration, **_kw):
        if event == BACKEND_COMPILE_EVENT:
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    rng = np.random.default_rng(seed)
    reg = metrics.registry()
    prefix = "engine.execute.calls."
    for name, fn in phases:
        before = {n: reg.get(n).value for n in reg.names()
                  if n.startswith(prefix)}
        compile_s[0] = 0.0
        t0 = time.perf_counter()
        info = fn(rng)
        wall = time.perf_counter() - t0
        labels = sorted(n[len(prefix):] for n in reg.names()
                        if n.startswith(prefix)
                        and reg.get(n).value > before.get(n, 0))
        print(json.dumps({
            "phase": name, "match": True,
            "device_kind": devs[0].device_kind, "wall_s": wall,
            "compile_s": compile_s[0], "engine_backends": labels,
            "peak_bytes_in_use": _peak_bytes(devs), **info}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served main path; 4: the multi-device "
                         "paths and their one-device comparisons only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2

    from repro.compile_cache import configure_compile_cache
    from repro.distributed.mesh_exec import MeshDeclinedWarning

    configure_compile_cache()
    # repo code on this path may raise no deprecation, and a mesh that runs
    # on one device is a failure here, not a fallback
    warnings.filterwarnings("error", category=DeprecationWarning,
                            module=r"repro(\.|$)")
    warnings.simplefilter("error", MeshDeclinedWarning)
    if args.chips == 1:
        phases = [("serve", phase_serve), ("batch", phase_batch),
                  ("faults", phase_faults), ("pallas", phase_pallas)]
        devs = devs[:1]
    else:
        phases = [("mesh", phase_mesh),
                  ("serve_devices", phase_serve_devices)]
    try:
        run_phases(phases, devs, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
