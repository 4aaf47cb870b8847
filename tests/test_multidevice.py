"""Cross-device conformance: sharded tile execution vs single device.

The contract under test: mapping the engine's tile batch axis onto a
``("tiles",)`` jax mesh (``distributed.mesh_exec``) changes WHERE chunks
execute, never what they compute — all four plan kinds are bit-identical
between one device and 8 virtual devices, fault runs and undersized batches
fall back to the single-device chunk loop bit-identically, and the serving
layer's multi-device bucket dispatch returns per-ticket results identical
to the serial loop for a shuffled heterogeneous stream.

Most sharding tests need >= 8 local jax devices, which CPU hosts only have
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (plus
``MATPIM_MULTIDEVICE=1`` to satisfy the conftest guard). In a plain tier-1
run those tests skip and :func:`test_subprocess_eight_device_leg` re-runs
this file in a subprocess with the flags set, so the sharded paths execute
on every PR even when CI forgets the env.
"""
import os
import subprocess
import sys
import warnings
from pathlib import Path

import numpy as np
import pytest

from repro.core import have_jax
from repro.core.tiling import TiledBinaryMatvec, TiledConv2d, TiledMatvec
from repro.device.faults import FaultModel, FaultRealization
from repro.distributed.mesh_exec import MeshDeclinedWarning, chunk_widths
from repro.serve.matpim import PlanService, ServeRequest

GEOM = dict(rows=64, cols=256, parts=8)
SRC = str(Path(__file__).resolve().parent.parent / "src")


def _n_devices() -> int:
    if not have_jax():
        return 0
    import jax
    return len(jax.devices())


needs_jax = pytest.mark.skipif(not have_jax(), reason="needs jax")
multidev = pytest.mark.skipif(
    _n_devices() < 8,
    reason="needs 8 virtual devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


# ---------------------------------------------------------------------------
# Chunking + placement (pure host logic, runs everywhere)
# ---------------------------------------------------------------------------


def test_chunk_widths_balanced_multiple_of_devices():
    assert chunk_widths(20, 8) == [3, 3, 3, 3, 2, 2, 2, 2]
    assert chunk_widths(8, 8) == [1] * 8
    for B, D in ((16, 8), (300, 4), (9, 3), (1000, 8)):
        w = chunk_widths(B, D)
        assert sum(w) == B and len(w) % D == 0
        assert max(w) - min(w) <= 1 and max(w) <= 32


def test_chunk_widths_rejects_underfilled_mesh():
    with pytest.raises(ValueError):
        chunk_widths(7, 8)


@needs_jax
def test_single_device_mesh_is_a_no_op():
    """On a 1-device mesh the sharded path declines and the engine runs on
    its one device — the single-device contract — with no decline warning
    (there is nothing to shard)."""
    from repro.distributed.mesh_exec import mesh_devices, tile_mesh, \
        try_run_sharded

    mesh = tile_mesh(1)
    assert mesh_devices(mesh) == 1
    t = TiledBinaryMatvec(64, 416, **GEOM)
    cp = t.plan.compile()
    mems = np.zeros((8, t.plan.rows, t.plan.cols), np.uint8)
    assert try_run_sharded(cp, mems, "fused", mesh) is None
    rng = np.random.default_rng(0)
    A = rng.choice([-1, 1], size=(64, 416))
    x = rng.choice([-1, 1], size=416)
    y0, r0 = t.run(A, x, backend="jax")
    with warnings.catch_warnings():
        warnings.simplefilter("error", MeshDeclinedWarning)
        y1, r1 = t.run(A, x, backend="jax", mesh=mesh)
    assert "+mesh" not in r1.backend
    np.testing.assert_array_equal(y0, y1)


# ---------------------------------------------------------------------------
# 8-virtual-device conformance (the sharded paths themselves)
# ---------------------------------------------------------------------------


def _wrappers():
    """One tiled wrapper + operand pair per plan kind, all with >= 8 tiles
    so an 8-device mesh is fully populated."""
    rng = np.random.default_rng(11)
    out = {}
    t = TiledBinaryMatvec(256, 416, **GEOM)            # 4 x 4 = 16 tiles
    out["binary_matvec"] = (t, (rng.choice([-1, 1], size=(256, 416)),
                                rng.choice([-1, 1], size=416)))
    t = TiledMatvec(128, 72, 4, **GEOM)                # 2 x 4 = 8 tiles
    out["matvec"] = (t, (rng.integers(0, 16, size=(128, 72)),
                         rng.integers(0, 16, size=72)))
    t = TiledConv2d(14, 26, 3, 4, tile_m=8, tile_n=8, **GEOM)   # 8 tiles
    out["conv"] = (t, (rng.integers(0, 16, size=(14, 26)),
                       rng.integers(0, 16, size=(3, 3))))
    t = TiledConv2d(14, 26, 3, 1, tile_m=8, tile_n=8, binary=True,
                    **GEOM)                            # 8 tiles
    out["binary_conv"] = (t, (rng.choice([-1, 1], size=(14, 26)),
                              rng.choice([-1, 1], size=(3, 3))))
    return out


@multidev
@pytest.mark.parametrize("kind", ["binary_matvec", "matvec", "conv",
                                  "binary_conv"])
def test_all_kinds_bit_identical_on_8_devices(kind):
    from repro.distributed.mesh_exec import tile_mesh

    t, ops = _wrappers()[kind]
    assert t.n_tiles >= 8
    y0, r0 = t.run(*ops, backend="jax")
    y1, r1 = t.run(*ops, backend="jax", mesh=tile_mesh(8))
    assert "+mesh" not in r0.backend
    assert r1.backend.endswith("+mesh8"), r1.backend
    np.testing.assert_array_equal(np.asarray(y0, dtype=object),
                                  np.asarray(y1, dtype=object))
    assert r0.cycles == r1.cycles


@multidev
def test_ambient_mesh_via_use_mesh():
    from repro.distributed.mesh_exec import tile_mesh
    from repro.distributed.sharding import use_mesh

    t, (A, x) = _wrappers()["binary_matvec"]
    y0, _ = t.run(A, x, backend="jax")
    with use_mesh(tile_mesh(8)):
        y1, r1 = t.run(A, x, backend="jax")
    assert r1.backend.endswith("+mesh8")
    np.testing.assert_array_equal(y0, y1)
    # mesh deactivates with the context: back to the single-device label
    _, r2 = t.run(A, x, backend="jax")
    assert "+mesh" not in r2.backend


@multidev
def test_batch_smaller_than_mesh_falls_back():
    from repro.distributed.mesh_exec import tile_mesh

    t = TiledBinaryMatvec(64, 416, **GEOM)             # 1 x 4 = 4 tiles < 8
    rng = np.random.default_rng(3)
    A = rng.choice([-1, 1], size=(64, 416))
    x = rng.choice([-1, 1], size=416)
    y0, _ = t.run(A, x, backend="jax")
    with pytest.warns(MeshDeclinedWarning, match="smaller than the mesh"):
        y1, r1 = t.run(A, x, backend="jax", mesh=tile_mesh(8))
    assert "+mesh" not in r1.backend
    np.testing.assert_array_equal(y0, y1)


@multidev
def test_fixed_fault_realization_masks_identical_under_mesh():
    """Fault runs stay on the audited single-device paths: an explicit
    FaultRealization replays bit-identically with and without a mesh."""
    from repro.distributed.mesh_exec import tile_mesh

    t, (A, x) = _wrappers()["binary_matvec"]
    cp = t.plan.compile()
    real = FaultRealization.sample(
        FaultModel(p_sa0=0.002, p_sa1=0.001), t.n_tiles, t.plan.rows,
        t.plan.cols, cp.n_cycles, cp.W, cp.I, rng=42)
    y0, r0 = t.run(A, x, backend="jax", faults=real)
    with pytest.warns(MeshDeclinedWarning, match="fault runs"):
        y1, r1 = t.run(A, x, backend="jax", faults=real, mesh=tile_mesh(8))
    assert "+mesh" not in r1.backend
    np.testing.assert_array_equal(y0, y1)
    # and a sampled FaultModel stream: same seed, same draws, mesh or not
    fm = FaultModel(p_sa0=0.002)
    yf0, _ = t.run(A, x, backend="numpy", faults=fm, rng=7)
    with pytest.warns(MeshDeclinedWarning, match="fault runs"):
        yf1, _ = t.run(A, x, backend="numpy", faults=fm, rng=7,
                       mesh=tile_mesh(8))
    np.testing.assert_array_equal(yf0, yf1)


# ---------------------------------------------------------------------------
# Serving layer: multi-device bucket dispatch vs the serial loop
# ---------------------------------------------------------------------------


def _mixed_stream(rng, n=24):
    reqs = []
    for i in range(n):
        pick = i % 4
        if pick == 0:
            m, k = int(rng.integers(2, 20)), int(rng.integers(4, 40))
            reqs.append(ServeRequest("binary_matvec",
                                     (rng.choice([-1, 1], size=(m, k)),
                                      rng.choice([-1, 1], size=k))))
        elif pick == 1:
            m, k = int(rng.integers(2, 12)), int(rng.integers(2, 10))
            reqs.append(ServeRequest("matvec",
                                     (rng.integers(0, 16, size=(m, k)),
                                      rng.integers(0, 16, size=k), 4)))
        elif pick == 2:
            h, w = int(rng.integers(6, 14)), int(rng.integers(6, 14))
            reqs.append(ServeRequest(
                "conv", (rng.integers(0, 16, size=(h, w)),
                         rng.integers(0, 8, size=(3, 3)), 6)))
        else:
            h, w = int(rng.integers(6, 14)), int(rng.integers(6, 14))
            reqs.append(ServeRequest(
                "binary_conv", (rng.choice([-1, 1], size=(h, w)),
                                rng.choice([-1, 1], size=(3, 3)))))
    perm = rng.permutation(len(reqs))
    return [reqs[int(i)] for i in perm]


def test_stream_multi_device_dispatch_bit_identical():
    """A shuffled heterogeneous stream served with devices=4 (overlapped
    buckets) returns per-ticket results identical to the serial loop."""
    reqs = _mixed_stream(np.random.default_rng(21))
    serial = PlanService(**GEOM)
    t_serial = serial.run_stream(list(reqs), slots=48)
    par = PlanService(**GEOM, devices=4)
    try:
        t_par = par.run_stream(list(reqs), slots=48)
        assert par.devices == 4
        assert len(t_par) == len(t_serial) == len(reqs)
        for a, b in zip(t_serial, t_par):
            assert a.kind == b.kind and b.done
            np.testing.assert_array_equal(np.asarray(a.result, dtype=object),
                                          np.asarray(b.result, dtype=object))
            assert a.cycles == b.cycles
        assert {t.device for t in t_par} <= set(range(4))
        # reconciliation survives the parallel scatter
        s = par.stats
        assert s.hits + s.misses == s.requests == len(reqs)
        assert s.units == sum(t.n_units for t in t_par)
    finally:
        par.close()


def test_flush_multi_device_matches_submit_order_results():
    rng = np.random.default_rng(5)
    svc = PlanService(**GEOM, devices=3)
    try:
        pairs = []
        for _ in range(9):
            m, k = int(rng.integers(2, 30)), int(rng.integers(4, 60))
            A = rng.choice([-1, 1], size=(m, k))
            x = rng.choice([-1, 1], size=k)
            pairs.append(((A, x), svc.submit_binary_matvec(A, x)))
        done = svc.flush()
        assert len(done) == 9 and all(t.done for t in done)
        for (A, x), t in pairs:
            dots = A @ x
            want = np.where(dots >= 0, 1, -1)
            np.testing.assert_array_equal(t.result, want)
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# Tier-1 subprocess leg: force 8 virtual devices even when CI didn't
# ---------------------------------------------------------------------------


@needs_jax
@pytest.mark.skipif(os.environ.get("MATPIM_MULTIDEVICE") == "1",
                    reason="already inside the multi-device leg")
def test_subprocess_eight_device_leg():
    """Re-run this file's sharding tests under 8 virtual CPU devices so the
    sharded executor paths run on every PR, not only in the CI leg that
    remembers to set XLA_FLAGS."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["MATPIM_MULTIDEVICE"] = "1"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-k", "not subprocess and not stream_multi_device and not flush_multi",
         __file__],
        env=env, capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, \
        f"multi-device leg failed:\n{out.stdout}\n{out.stderr}"
    # the leg must actually exercise the 8-device tests, not skip them all
    import re
    m = re.search(r"(\d+) passed", out.stdout)
    assert m and int(m.group(1)) >= 10, out.stdout
