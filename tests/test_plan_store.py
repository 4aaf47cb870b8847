"""Persistent plan store (`repro.serve.plan_store`).

The contract under test: compiled plans round-trip through disk
bit-identically for all four algorithm kinds and every replay backend,
every possible bad input (schema bump, truncation, garbage, digest
mismatch) loads as a clean MISS rather than an error, concurrent writers
never expose a torn entry (atomic tmp+rename), and a service rebuilt from a
populated store replays heterogeneous traffic with ZERO
``compile_program`` invocations.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import (BinaryConvPlan, BinaryMatvecPlan, ConvPlan,
                        MatvecPlan, have_jax)
from repro.core.engine import execute
from repro.obs import metrics
from repro.serve import plan_store
from repro.serve.matpim import PlanService
from repro.serve.plan_store import PlanStore, program_key, store_key

GEOM = dict(rows=64, cols=256, parts=8)
SRC = str(Path(__file__).resolve().parent.parent / "src")

KINDS = ("binary_matvec", "matvec", "conv", "binary_conv")


def _build_plan(kind):
    """One small compiled-able plan per algorithm kind."""
    if kind == "binary_matvec":
        return BinaryMatvecPlan(4, 16, **GEOM)
    if kind == "matvec":
        return MatvecPlan(4, 8, 2, **GEOM)
    if kind == "conv":
        p = ConvPlan(6, 6, 2, 4, **GEOM)
        p.ensure_program(np.array([[1, 2], [2, 1]]))
        return p
    p = BinaryConvPlan(6, 8, 2, **GEOM)   # n must divide across parts
    p.ensure_program(np.array([[1, -1], [-1, 1]]))
    return p


def _store(tmp_path):
    return PlanStore(tmp_path / "store")


# ---------------------------------------------------------------------------
# Round trip: compile -> serialize -> deserialize -> execute bit-identically
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_roundtrip_bit_identical_all_kinds(kind, tmp_path):
    plan = _build_plan(kind)
    cp = plan.compile()
    store = _store(tmp_path)
    key = ("entry", kind)
    assert store.put(key, cp)
    cp2 = store.load(key)
    assert cp2 is not None and store.hits == 1 and store.corrupt == 0
    assert cp2 is not cp                      # a real deserialization
    assert cp2.stats == cp.stats and cp2.n_cycles == cp.n_cycles
    if cp.schedule is not None:
        assert cp2.schedule.summary() == cp.schedule.summary()

    rng = np.random.default_rng(7)
    mems = rng.integers(0, 2, size=(3, plan.rows, plan.cols),
                        dtype=np.uint8)
    backends = ["numpy", "numpy-unfused"] + (["jax"] if have_jax() else [])
    for backend in backends:
        a = execute(cp, mems, backend=backend)
        b = execute(cp2, mems, backend=backend)
        np.testing.assert_array_equal(np.asarray(a.mem), np.asarray(b.mem))
        assert a.cycles == b.cycles and a.stats == b.stats


def test_adopt_compiled_rejects_geometry_mismatch(tmp_path):
    cp = _build_plan("binary_matvec").compile()
    other = BinaryMatvecPlan(4, 16, rows=128, cols=512, parts=8)
    other.program  # built in ctor
    with pytest.raises(ValueError, match="geometry"):
        other.adopt_compiled(cp)


# ---------------------------------------------------------------------------
# Invalidation: schema bumps, corruption, digest mismatch -> clean misses
# ---------------------------------------------------------------------------


def test_schema_bump_loads_as_empty(tmp_path, monkeypatch):
    store = _store(tmp_path)
    key = ("k",)
    store.put(key, _build_plan("binary_matvec").compile())
    monkeypatch.setattr(plan_store, "SCHEMA", plan_store.SCHEMA + 1)
    fresh = PlanStore(store.path)
    assert fresh.load(key) is None
    assert fresh.corrupt == 1 and fresh.misses == 1 and fresh.hits == 0
    # the stale entry was dropped so the next writer replaces it
    assert not fresh.entry_path(key).exists()


@pytest.mark.parametrize("mangle", ["truncate", "garbage"])
def test_corrupt_entry_loads_as_miss(tmp_path, mangle):
    store = _store(tmp_path)
    key = ("k",)
    store.put(key, _build_plan("matvec").compile())
    p = store.entry_path(key)
    data = p.read_bytes()
    p.write_bytes(data[: len(data) // 3] if mangle == "truncate"
                  else b"this is not a zipfile")
    fresh = PlanStore(store.path)
    assert fresh.load(key) is None and fresh.corrupt == 1
    # a clean re-put recovers the slot
    assert fresh.put(key, _build_plan("matvec").compile())
    assert fresh.load(key) is not None


def test_renamed_entry_fails_plan_key_check(tmp_path):
    store = _store(tmp_path)
    store.put(("a",), _build_plan("binary_matvec").compile())
    # impersonate another key by renaming the file to its digest
    os.rename(store.entry_path(("a",)), store.entry_path(("b",)))
    fresh = PlanStore(store.path)
    assert fresh.load(("b",)) is None and fresh.corrupt == 1


def test_store_key_is_process_stable(tmp_path):
    # digests must be derivable in another process (file names survive
    # restarts); repr-based hashing breaks if someone switches to hash()
    key = ("binary_matvec", (8, 16), (64, 256, 8), True, "numpy")
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[1]);"
         "from repro.serve.plan_store import store_key;"
         f"print(store_key({key!r}))", SRC],
        capture_output=True, text=True, check=True,
        env={**os.environ, "PYTHONHASHSEED": "12345"})
    assert out.stdout.strip() == store_key(key)


def test_program_key_stable_across_recompiles():
    plan = BinaryMatvecPlan(4, 16, **GEOM)
    k1 = program_key(plan.compile())
    plan._compiled = None                    # simulate cache eviction
    k2 = program_key(plan.compile())
    fresh = BinaryMatvecPlan(plan.m, plan.n, **GEOM)
    k3 = program_key(fresh.compile())
    assert k1 == k2 == k3
    other = BinaryMatvecPlan(plan.m, plan.n * 2, **GEOM)
    assert program_key(other.compile()) != k1


# ---------------------------------------------------------------------------
# Concurrent writers: atomic rename means readers never see a torn entry
# ---------------------------------------------------------------------------

_WRITER = textwrap.dedent("""
    import sys
    sys.path.insert(0, sys.argv[2])
    from repro.core import BinaryMatvecPlan
    from repro.serve.plan_store import PlanStore
    store = PlanStore(sys.argv[1])
    cp = BinaryMatvecPlan(8, 32, rows=64, cols=256, parts=8).compile()
    for _ in range(int(sys.argv[3])):
        assert store.put(("shared",), cp)
""")


def test_two_process_concurrent_writers_atomic(tmp_path):
    store = _store(tmp_path)
    procs = [subprocess.Popen(
        [sys.executable, "-c", _WRITER, str(store.path), SRC, "10"])
        for _ in range(2)]
    reader = PlanStore(store.path)
    loads = 0
    while any(p.poll() is None for p in procs):
        if reader.load(("shared",)) is not None:
            loads += 1
    assert all(p.wait() == 0 for p in procs)
    # no torn read ever surfaced while both writers raced the same entry
    assert reader.corrupt == 0
    assert reader.load(("shared",)) is not None
    assert reader.keys() == [store_key(("shared",))]


# ---------------------------------------------------------------------------
# End-to-end restart: rebuilt service replays traffic with zero compiles
# ---------------------------------------------------------------------------


def _traffic(svc, rng):
    tickets = []
    for i in range(8):
        m, k = int(rng.integers(2, 10)), int(rng.integers(4, 20))
        if i % 2:
            tickets.append(svc.submit(
                "matvec", rng.integers(0, 16, size=(m, k)),
                rng.integers(0, 16, size=k), 4))
        else:
            tickets.append(svc.submit(
                "binary_matvec", rng.choice([-1, 1], size=(m, k)),
                rng.choice([-1, 1], size=k)))
    img = rng.integers(0, 64, size=(10, 12))
    tickets.append(svc.submit(
        "conv", img, np.array([[1, 2, 1], [2, 4, 2], [1, 2, 1]]), 8))
    svc.flush()
    return tickets


def test_restart_round_trip_zero_compiles_bit_identical(tmp_path):
    store = _store(tmp_path)
    cold = PlanService(**GEOM, store=store)
    first = _traffic(cold, np.random.default_rng(3))
    assert cold.stats.misses > 0 and cold.stats.store_hits == 0
    assert len(store) == cold.stats.misses   # every miss was persisted

    base = metrics.counter("compile.programs").value
    warm = PlanService(**GEOM, store=store)
    second = _traffic(warm, np.random.default_rng(3))
    assert metrics.counter("compile.programs").value == base, \
        "restarted service recompiled despite a populated store"
    assert warm.stats.store_hits == warm.stats.misses > 0
    for a, b in zip(first, second):
        assert a.kind == b.kind
        np.testing.assert_array_equal(np.asarray(a.result),
                                      np.asarray(b.result))
        assert a.cycles == b.cycles


def test_restart_round_trip_async_admit_path(tmp_path):
    store = _store(tmp_path)
    cold = PlanService(**GEOM, store=store, async_compile=True)
    first = _traffic(cold, np.random.default_rng(5))
    cold.close()

    base = metrics.counter("compile.programs").value
    warm = PlanService(**GEOM, store=store, async_compile=True)
    second = _traffic(warm, np.random.default_rng(5))
    warm.close()
    assert metrics.counter("compile.programs").value == base
    assert warm.stats.store_hits == warm.stats.misses > 0
    for a, b in zip(first, second):
        np.testing.assert_array_equal(np.asarray(a.result),
                                      np.asarray(b.result))


def test_env_default_store(tmp_path, monkeypatch):
    """$MATPIM_PLAN_STORE names the default path for every new service."""
    monkeypatch.setenv(plan_store.STORE_ENV, str(tmp_path / "envstore"))
    plan_store.reset_default_store()
    try:
        svc = PlanService(**GEOM)
        assert svc.store is not None
        assert svc.store.path == tmp_path / "envstore"
        svc.submit("binary_matvec", np.ones((3, 9), int),
                   np.ones(9, int))
        svc.flush()
        assert len(svc.store) == 1
        # store=False opts a service out even with the env set
        assert PlanService(**GEOM, store=False).store is None
    finally:
        plan_store.reset_default_store()


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placed_from_outside(tmp_path, monkeypatch, from_env):
    """$JAX_COMPILATION_CACHE_DIR, when set, is the only compile cache;
    otherwise it is the checkout's fixed .jax_cache. Building a store (or
    a service on one) never moves it."""
    import jax

    from repro import compile_cache

    want = str(tmp_path / "xla-cache") if from_env \
        else str(compile_cache.DEFAULT_CACHE_DIR)
    if from_env:
        monkeypatch.setenv(compile_cache.CACHE_ENV, want)
    else:
        monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    try:
        assert compile_cache.configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        PlanService(**GEOM, store=PlanStore(tmp_path / "store"))
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        monkeypatch.undo()                  # the suite's own placement
        compile_cache.configure_compile_cache()


# ---------------------------------------------------------------------------
# Off-path executor prewarm: a store-hit plan must not pay its ~1s runner
# warm-up on the first request (ROADMAP: dominant restart cost)
# ---------------------------------------------------------------------------


def test_store_hit_prewarms_executors_off_path(tmp_path):
    store = _store(tmp_path)
    cold = PlanService(**GEOM, store=store)
    first = _traffic(cold, np.random.default_rng(9))
    assert cold.stats.prewarms == 0          # nothing arrived pre-compiled

    warm = PlanService(**GEOM, store=store)
    assert warm.prewarm                      # default: on for store-backed
    second = _traffic(warm, np.random.default_rng(9))
    warm.close()
    s = warm.stats
    assert s.store_hits == s.misses > 0
    # every store hit queued an off-path warm-up, accounted as warmup_s
    assert s.prewarms == s.store_hits
    assert s.warmup_s > 0
    # PR-8 reconciliation identities survive the prewarm accounting
    assert s.hits + s.misses == s.requests
    for a, b in zip(first, second):
        np.testing.assert_array_equal(np.asarray(a.result),
                                      np.asarray(b.result))


def test_prewarm_opt_out_restores_inline_warmup(tmp_path):
    store = _store(tmp_path)
    cold = PlanService(**GEOM, store=store)
    _traffic(cold, np.random.default_rng(13))

    warm = PlanService(**GEOM, store=store, prewarm=False)
    _traffic(warm, np.random.default_rng(13))
    assert warm._pool is None                # no worker threads spawned
    s = warm.stats
    assert s.store_hits == s.misses > 0 and s.prewarms == 0
    assert s.warmup_s > 0                    # first batch pays it inline


def test_failed_prewarm_is_recorded(tmp_path, monkeypatch):
    """A warm-up that raises is counted and its traceback kept; the first
    batch then warms inline and still serves correct results."""
    store = _store(tmp_path)
    cold = PlanService(**GEOM, store=store)
    first = _traffic(cold, np.random.default_rng(17))

    def broken_warm(self, cp):
        raise RuntimeError("warm-up exploded")

    monkeypatch.setattr(PlanService, "_warm_executors", broken_warm)
    warm = PlanService(**GEOM, store=store)
    second = _traffic(warm, np.random.default_rng(17))
    warm.close()
    s = warm.stats
    assert s.store_hits == s.misses > 0
    assert s.prewarm_errors == s.store_hits and s.prewarms == 0
    assert "RuntimeError: warm-up exploded" in warm.last_prewarm_error
    for a, b in zip(first, second):
        np.testing.assert_array_equal(np.asarray(a.result),
                                      np.asarray(b.result))
