"""Plan-cache serving layer + execution-accounting regressions.

Covers: heterogeneous request batching bit-exactness vs per-request
execution (shuffled-stream property, with and without a fixed
FaultRealization), fault-model serving, LRU cache eviction correctness
(including release of evicted plans' jitted-runner caches), the
continuous-batching stream loop, the pipeline layer's shared plan source,
and the two stateful-accounting regressions this PR fixes:

* ``CrossbarPlan.execute(mem, xbar=...)`` resets ``cycles``/``stats`` on a
  reused crossbar (previously they accumulated across calls);
* ``CompiledProgram._caches`` is a bounded LRU with ``clear_caches()``
  (previously one runner per (kind, dtype, fault key) leaked forever).
"""
import gc
import weakref

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import BinaryMatvecPlan
from repro.core.compile import CACHE_MAX_ENTRIES, RunnerCache
from repro.device.faults import FaultModel, FaultRealization
from repro.serve.matpim import (PlanService, ServeRequest, bucket_up,
                                get_default_service, reset_default_service)

GEOM = dict(rows=64, cols=256, parts=8)


def _bmv_oracle(A, x):
    return np.where(A @ x >= 0, 1, -1)


def _mixed_requests(rng, n):
    """Alternating binary/full-precision matvec requests, mixed shapes."""
    reqs = []
    for i in range(n):
        m, k = int(rng.integers(2, 10)), int(rng.integers(4, 20))
        if i % 2:
            A = rng.integers(0, 16, size=(m, k))
            x = rng.integers(0, 16, size=k)
            reqs.append(("matvec", (A, x, 4)))
        else:
            A = rng.choice([-1, 1], size=(m, k))
            x = rng.choice([-1, 1], size=k)
            reqs.append(("binary_matvec", (A, x)))
    return reqs


def _oracle(kind, args):
    if kind == "binary_matvec":
        A, x = args
        return _bmv_oracle(A, x)
    A, x, N = args
    return (A.astype(object) @ x.astype(object)) % (1 << (2 * N))


# ---------------------------------------------------------------------------
# Batched service vs oracles / sequential execution
# ---------------------------------------------------------------------------


def test_mixed_stream_matches_oracles():
    rng = np.random.default_rng(0)
    svc = PlanService(**GEOM)
    reqs = _mixed_requests(rng, 12)
    tickets = [svc.submit(kind, *args) for kind, args in reqs]
    done = svc.flush()
    assert len(done) == len(tickets) and all(t.done for t in tickets)
    for t, (kind, args) in zip(tickets, reqs):
        want = _oracle(kind, args)
        assert np.array_equal(np.asarray(t.result, dtype=object),
                              np.asarray(want, dtype=object)), kind
        assert t.cycles and t.cycles > 0 and t.batch_units >= t.n_units
    # mixed shapes collapse into few pow2 buckets => real cache reuse
    assert svc.stats.requests == 12
    assert svc.stats.hit_rate >= 0.5
    assert svc.stats.batches == len({t.key for t in tickets})


def test_conv_requests_crop_to_true_region():
    rng = np.random.default_rng(1)
    svc = PlanService()                     # default geometry for conv plans
    img = rng.integers(0, 64, size=(10, 13))
    K = np.array([[1, 2, 1], [2, 4, 2], [1, 2, 1]])
    t = svc.submit_conv(img, K, N=8)
    b = svc.submit_binary_conv(rng.choice([-1, 1], size=(9, 9)),
                               rng.choice([-1, 1], size=(3, 3)))
    svc.flush()
    want = np.zeros((8, 11), dtype=object)
    for i in range(8):
        for j in range(11):
            want[i, j] = int((img[i:i + 3, j:j + 3] * K).sum()) % 256
    assert np.array_equal(np.asarray(t.result, dtype=object), want)
    assert b.result.shape == (7, 7) and set(np.unique(b.result)) <= {-1, 1}


def test_distinct_kernel_convs_share_one_plan():
    """Kernel-independent conv programs serve every kernel of a shape: two
    requests with different kernels hit one cached plan and coalesce."""
    rng = np.random.default_rng(2)
    svc = PlanService()
    img1 = rng.integers(0, 64, size=(9, 9))
    img2 = rng.integers(0, 64, size=(10, 12))  # same (16, 16) bucket
    K1 = np.array([[0, 1, 0], [1, -4, 1], [0, 1, 0]])
    K2 = np.array([[1, 2, 1], [0, 0, 0], [-1, -2, -1]])
    t1 = svc.submit_conv(img1, K1, N=8)
    t2 = svc.submit_conv(img2, K2, N=8)
    svc.flush()
    assert t1.key == t2.key and svc.stats.misses == 1
    assert t1.batch_units == t2.batch_units == t1.n_units + t2.n_units
    for t, img, K in ((t1, img1, K1), (t2, img2, K2)):
        oh, ow = img.shape[0] - 2, img.shape[1] - 2
        want = np.zeros((oh, ow), dtype=object)
        for i in range(oh):
            for j in range(ow):
                want[i, j] = int((img[i:i + 3, j:j + 3] * K).sum()) % 256
        assert np.array_equal(np.asarray(t.result, dtype=object), want)
    assert svc.stats.compile_s > 0   # conv program build is priced at miss


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000))
def test_shuffled_stream_bit_identical_to_sequential(seed):
    """Property (ideal device): coalesced execution of a shuffled
    mixed-shape stream == sequential per-request execution."""
    rng = np.random.default_rng(seed)
    reqs = _mixed_requests(rng, 8)
    seq = PlanService(**GEOM)
    want = []
    for kind, args in reqs:
        t = seq.submit(kind, *args)
        seq.flush()                        # one engine call per request
        want.append(t.result)
    shuf = PlanService(**GEOM)
    order = rng.permutation(len(reqs))
    tickets = {}
    for i in order:
        kind, args = reqs[i]
        tickets[i] = shuf.submit(kind, *args)
    shuf.flush()                           # one engine call per bucket
    for i, w in enumerate(want):
        assert np.array_equal(np.asarray(tickets[i].result, dtype=object),
                              np.asarray(w, dtype=object)), i
    assert shuf.stats.batches < seq.stats.batches  # it actually coalesced


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 10_000))
def test_shuffled_stream_bit_identical_under_fixed_realization(seed):
    """Property (faulty device): with a fixed per-request FaultRealization
    the shuffled, coalesced stream stays bit-identical to sequential
    per-request execution — explicit masks make batching order-free."""
    rng = np.random.default_rng(seed)
    model = FaultModel.uniform(3e-3)
    base = _mixed_requests(rng, 6)
    # sample one realization per request against its bucket plan's trace
    probe = PlanService(**GEOM)
    reals = []
    for j, (kind, args) in enumerate(base):
        t = probe.submit(kind, *args)
        w = probe._queue[-1].wrapper
        cp = w.plan.compile()
        reals.append(FaultRealization.sample(
            model, t.n_units, w.plan.rows, w.plan.cols,
            cp.n_cycles, cp.W, cp.I, rng=np.random.default_rng(seed + j)))
    probe._queue.clear()

    seq = PlanService(**GEOM)
    want = []
    for (kind, args), r in zip(base, reals):
        t = seq.submit(kind, *args, faults=r)
        seq.flush()
        want.append(t.result)

    shuf = PlanService(**GEOM)
    tickets = {}
    for i in rng.permutation(len(base)):
        kind, args = base[i]
        tickets[i] = shuf.submit(kind, *args, faults=reals[i])
    shuf.flush()
    for i, w in enumerate(want):
        assert np.array_equal(np.asarray(tickets[i].result, dtype=object),
                              np.asarray(w, dtype=object)), i


def test_fault_model_bucketing_and_effect():
    rng = np.random.default_rng(3)
    svc = PlanService(**GEOM)
    model = FaultModel.uniform(0.2)        # violent: outputs must differ
    A = rng.choice([-1, 1], size=(8, 16))
    x = rng.choice([-1, 1], size=16)
    t_ideal = svc.submit_binary_matvec(A, x)
    t_f1 = svc.submit_binary_matvec(A, x, faults=model)
    t_f2 = svc.submit_binary_matvec(A, x, faults=model)
    svc.flush()
    # same model + same plan coalesce; ideal runs in its own batch
    assert t_f1.batch_units == t_f2.batch_units == 2 and t_ideal.batch_units == 1
    assert np.array_equal(t_ideal.result, _bmv_oracle(A, x))
    assert not np.array_equal(t_f1.result, t_ideal.result) \
        or not np.array_equal(t_f2.result, t_ideal.result)
    with pytest.raises(ValueError):        # realization batch must match units
        svc.submit_binary_matvec(A, x, faults=FaultRealization.sample(
            model, 5, GEOM["rows"], GEOM["cols"], 3, 2, 1))


# ---------------------------------------------------------------------------
# Cache bound / eviction
# ---------------------------------------------------------------------------


def test_lru_eviction_counts_and_recompiles():
    rng = np.random.default_rng(4)
    svc = PlanService(max_plans=2, bucket=False, **GEOM)
    ops = []
    for k in (6, 10, 14):                  # three distinct exact-shape plans
        A = rng.choice([-1, 1], size=(4, k))
        x = rng.choice([-1, 1], size=k)
        ops.append((A, x))
        svc.submit_binary_matvec(A, x)
        svc.flush()
    assert svc.stats.misses == 3 and svc.stats.evictions == 1
    assert len(svc.cached_keys()) == 2
    # the first shape was evicted; resubmitting is a miss and still correct
    t = svc.submit_binary_matvec(*ops[0])
    svc.flush()
    assert svc.stats.misses == 4 and svc.stats.evictions == 2
    assert np.array_equal(t.result, _bmv_oracle(*ops[0]))


def test_eviction_releases_jitted_runner_caches():
    """Regression: evicted plans must drop their executor memoizations —
    the unbounded-_caches leak under a long-lived service."""

    class Sentinel:                        # stands in for a jitted runner
        pass

    rng = np.random.default_rng(5)
    svc = PlanService(max_plans=1, bucket=False, **GEOM)
    svc.submit_binary_matvec(rng.choice([-1, 1], size=(4, 8)),
                             rng.choice([-1, 1], size=8))
    done = svc.flush()
    w = svc._plans[done[0].key]
    cp = w.plan.compile()
    assert len(cp._caches) > 0             # numpy replay plan memoized
    sent = Sentinel()
    cp._caches[("jax_fused", "uint8")] = sent
    ref = weakref.ref(sent)
    del sent
    # admit a second plan: the first is evicted and its caches cleared
    svc.submit_binary_matvec(rng.choice([-1, 1], size=(4, 12)),
                             rng.choice([-1, 1], size=12))
    svc.flush()
    assert svc.stats.evictions == 1
    assert len(cp._caches) == 0
    gc.collect()
    assert ref() is None, "evicted runner object still referenced"


def test_compiled_caches_bounded_lru():
    """Regression: CompiledProgram._caches is bounded (was a bare dict that
    retained one runner per key forever)."""
    plan = BinaryMatvecPlan(2, 8, rows=16, cols=64, parts=2)
    cp = plan.compile()
    cp.clear_caches()
    for i in range(3 * CACHE_MAX_ENTRIES):
        cp._caches[("runner", i)] = object()
    assert len(cp._caches) == CACHE_MAX_ENTRIES
    assert cp._caches.evictions == 2 * CACHE_MAX_ENTRIES
    assert ("runner", 0) not in cp._caches
    assert ("runner", 3 * CACHE_MAX_ENTRIES - 1) in cp._caches
    # LRU: touching an old entry protects it from the next eviction
    cp._caches.get(("runner", 2 * CACHE_MAX_ENTRIES))
    cp._caches[("fresh", 0)] = object()
    assert ("runner", 2 * CACHE_MAX_ENTRIES) in cp._caches
    cp.clear_caches()
    assert len(cp._caches) == 0


def test_runner_cache_is_dict_like():
    c = RunnerCache(max_entries=2)
    c["a"] = 1
    assert c.get("a") == 1 and c.get("zz", 7) == 7 and "a" in c
    assert c.pop("a") == 1 and c.pop("a", None) is None and len(c) == 0


# ---------------------------------------------------------------------------
# execute() accounting regression
# ---------------------------------------------------------------------------


def test_execute_reused_xbar_resets_counters():
    """Regression: repeated execute(mem, xbar=...) on one crossbar used to
    return ACCUMULATED cycles/stats (execute_batch's interp path reset
    them; execute did not)."""
    plan = BinaryMatvecPlan(2, 8, rows=16, cols=64, parts=2)
    mem = np.zeros((16, 64), dtype=np.uint8)
    plan.load_into(mem, np.ones((2, 8)), np.ones(8))
    xb = plan.new_crossbar()
    _, c1, s1 = plan.execute(mem, xbar=xb)
    _, c2, s2 = plan.execute(mem, xbar=xb)
    assert c1 == c2 == plan.cycles
    assert s1 == s2
    # and both match the compiled backend's per-call accounting
    _, c3, s3 = plan.execute(mem)
    assert c3 == c1 and s3 == s1
    # run_program (plan.run(..., xbar=)) shares the same per-call contract
    _, _, c4 = plan.run(np.ones((2, 8)), np.ones(8), xbar=xb)
    _, _, c5 = plan.run(np.ones((2, 8)), np.ones(8), xbar=xb)
    assert c4 == c5 == plan.cycles


# ---------------------------------------------------------------------------
# Continuous batching loop + shared pipeline plan source
# ---------------------------------------------------------------------------


def test_run_stream_continuous_batching():
    rng = np.random.default_rng(6)
    svc = PlanService(**GEOM)
    reqs, want = [], []
    for _ in range(9):
        m, k = int(rng.integers(2, 8)), int(rng.integers(4, 16))
        A = rng.choice([-1, 1], size=(m, k))
        x = rng.choice([-1, 1], size=k)
        reqs.append(ServeRequest("binary_matvec", (A, x)))
        want.append(_bmv_oracle(A, x))
    with pytest.raises(ValueError, match="slots"):
        svc.run_stream(iter(reqs), slots=0)
    tickets = svc.run_stream(iter(reqs), slots=3)
    assert len(tickets) == 9 and all(t.done for t in tickets)
    for t, w in zip(tickets, want):
        assert np.array_equal(t.result, w)
        assert t.wall_s is not None and t.wall_s >= 0
        assert t.queue_steps >= 0
    assert svc.stats.batches >= 3          # slot budget forced several steps


def test_run_stream_accounting_shuffled_heterogeneous():
    """run_stream bookkeeping under a shuffled mixed-shape stream: per-ticket
    queue_steps / batch_units stay within the slot budget's implications and
    the aggregate CacheStats counters reconcile exactly."""
    rng = np.random.default_rng(11)
    base = _mixed_requests(rng, 14)
    order = rng.permutation(len(base))
    reqs = [ServeRequest(*base[i]) for i in order]
    svc = PlanService(**GEOM)
    slots = 6
    tickets = svc.run_stream(iter(reqs), slots=slots)

    assert len(tickets) == len(reqs) and all(t.done for t in tickets)
    for t, i in zip(tickets, order):
        want = _oracle(*base[i])
        assert np.array_equal(np.asarray(t.result, dtype=object),
                              np.asarray(want, dtype=object))
    # aggregate accounting reconciles with the per-ticket view
    assert svc.stats.requests == len(reqs)
    assert svc.stats.units == sum(t.n_units for t in tickets)
    assert svc.stats.batches == len({(t.key, t.batch_wall_s)
                                     for t in tickets})
    # slot occupancy: admission stops once pending_units reaches the slot
    # budget, so no batch exceeds slots + (largest single request - 1)
    max_units = max(t.n_units for t in tickets)
    assert all(t.batch_units <= slots + max_units - 1 for t in tickets)
    assert any(t.batch_units > 1 for t in tickets)   # it actually coalesced
    # queue_steps: bounded by the number of steps the loop actually ran
    assert all(0 <= t.queue_steps <= svc._step for t in tickets)
    assert svc.pending_units == 0


def test_wall_s_measures_submit_to_decode_latency():
    """Regression: wall_s used to be the engine-batch wall, identical for
    every ticket in a batch. It is now true per-request latency (submit ->
    decoded), so a ticket that sat in the queue shows the queueing time;
    the batch wall moved to batch_wall_s."""
    import time
    rng = np.random.default_rng(12)
    svc = PlanService(**GEOM)
    A = rng.choice([-1, 1], size=(4, 8))
    x = rng.choice([-1, 1], size=8)
    t = svc.submit_binary_matvec(A, x)
    time.sleep(0.05)                         # request waits in the queue
    svc.flush()
    assert t.wall_s >= 0.05                  # queueing is part of latency
    assert t.batch_wall_s is not None and t.batch_wall_s < t.wall_s
    assert t.batch_wall_s > 0


def test_warmup_s_accrues_only_on_first_execution_per_plan():
    """Regression: a plan's first engine batch (jit tracing etc.) used to be
    priced as steady-state execute time. It now lands in stats.warmup_s,
    once per cached plan, again after eviction forces a rebuild."""
    rng = np.random.default_rng(13)
    svc = PlanService(max_plans=1, bucket=False, **GEOM)
    A = rng.choice([-1, 1], size=(4, 8))
    x = rng.choice([-1, 1], size=8)
    svc.submit_binary_matvec(A, x)
    svc.flush()
    first = svc.stats.warmup_s
    assert first > 0
    svc.submit_binary_matvec(A, x)           # same plan, warm now
    svc.flush()
    assert svc.stats.warmup_s == first
    # evict the plan; the rebuilt plan warms up again
    svc.submit_binary_matvec(rng.choice([-1, 1], size=(4, 12)),
                             rng.choice([-1, 1], size=12))
    svc.flush()
    svc.submit_binary_matvec(A, x)
    svc.flush()
    assert svc.stats.evictions >= 2 and svc.stats.warmup_s > first


def test_minority_bucket_not_starved():
    """Fullest-first alone would starve a lone odd-shaped request under a
    sustained popular stream; aging bounds its queue delay."""
    rng = np.random.default_rng(8)
    svc = PlanService(max_starve_steps=3, **GEOM)
    A_pop = rng.choice([-1, 1], size=(4, 8))
    x_pop = rng.choice([-1, 1], size=8)
    A_odd = rng.choice([-1, 1], size=(4, 24))    # different bucket
    x_odd = rng.choice([-1, 1], size=24)
    odd = svc.submit_binary_matvec(A_odd, x_odd)
    for _ in range(10):                          # popular bucket always fuller
        svc.submit_binary_matvec(A_pop, x_pop)
        svc.submit_binary_matvec(A_pop, x_pop)
        svc.step()
        if odd.done:
            break
    assert odd.done and odd.queue_steps <= 3 + 1
    assert np.array_equal(odd.result, _bmv_oracle(A_odd, x_odd))
    svc.flush()


def test_unfused_service_policy():
    svc = PlanService(fuse=False, **GEOM)
    assert svc.backend == "numpy"        # passed through; the trace decides
    A = np.ones((3, 9), dtype=int)
    t = svc.submit_binary_matvec(A, np.ones(9, dtype=int))
    svc.flush()
    assert np.array_equal(t.result, [1, 1, 1])
    (w,) = svc._plans.values()
    cp = w.plan._compiled
    assert cp.schedule is None           # compiled unfused and run unfused
    assert "numpy_plan" in cp._caches
    assert "numpy_fused_plan" not in cp._caches


def test_pipeline_stages_share_default_service():
    from repro.apps.pipeline import BinaryMatvecStage, Pipeline

    reset_default_service()
    try:
        rng = np.random.default_rng(7)
        W1 = rng.choice([-1, 1], size=(16, 16))
        W2 = rng.choice([-1, 1], size=(16, 16))  # same shape, new weights
        s1 = BinaryMatvecStage(W1, rows=64, cols=256, parts=8)
        s2 = BinaryMatvecStage(W2, rows=64, cols=256, parts=8)
        svc = get_default_service()
        assert svc.stats.misses == 1 and svc.stats.hits == 1
        assert s1.tiled is s2.tiled              # one compiled plan, shared
        x = rng.choice([-1, 1], size=16)
        y, rep = Pipeline([s1, s2]).run(x)
        want = _bmv_oracle(W2, _bmv_oracle(W1, x))
        assert np.array_equal(y, want)
        # an isolated service keeps its own cache, and its geometry is the
        # default for stage plans fetched through it
        iso = PlanService(**GEOM)
        s3 = BinaryMatvecStage(W1, service=iso)
        assert iso.stats.misses == 1 and s3.tiled is not s1.tiled
        assert (s3.tiled.plan.rows, s3.tiled.plan.cols,
                s3.tiled.plan.parts) == (64, 256, 8)
    finally:
        reset_default_service()


def test_bucket_up():
    assert [bucket_up(v) for v in (1, 8, 9, 17, 100)] == [8, 8, 16, 32, 128]


# -- stats schema + reconciliation (async vs sync accounting) ---------------


def test_cache_stats_field_whitelist():
    """Every stat field is load-bearing for a reconciliation identity
    somewhere (tests, benchmarks/slo.py cold_start, report.py tables).
    Adding a field here without auditing those consumers silently skews
    the served-cost accounting — so additions must update this whitelist
    deliberately."""
    import dataclasses as dc

    from repro.serve.matpim import CacheStats

    expected = {"hits", "misses", "evictions", "requests", "batches",
                "units", "compile_s", "warmup_s", "async_compiles",
                "store_hits", "prewarms", "prewarm_errors"}
    fields = {f.name for f in dc.fields(CacheStats)}
    assert fields == expected, (
        f"CacheStats schema drifted: added={sorted(fields - expected)} "
        f"removed={sorted(expected - fields)} — audit every stats "
        f"consumer, then update this whitelist")
    assert set(CacheStats().as_dict()) == expected | {"hit_rate"}


@pytest.mark.parametrize("async_compile", [False, True])
def test_stats_reconciliation_identities(async_compile):
    """hits + misses == requests, and a warm replay adds exactly zero to
    the cold-cost account (compile_s + warmup_s) — on BOTH admit paths."""
    rng = np.random.default_rng(11)
    reqs = _mixed_requests(rng, 12)
    svc = PlanService(**GEOM, async_compile=async_compile)
    try:
        tickets = [svc.submit(k, *args) for k, args in reqs]
        svc.flush()
        s = svc.stats
        assert s.hits + s.misses == s.requests == len(reqs)
        assert s.units == sum(t.n_units for t in tickets)
        assert s.batches > 0
        assert s.compile_s > 0.0 and s.warmup_s >= 0.0
        assert s.store_hits == 0                 # no store configured
        if async_compile:
            assert 0 <= s.async_compiles <= s.misses
        else:
            assert s.async_compiles == 0

        cold_compile_s, cold_warmup_s = s.compile_s, s.warmup_s
        cold_misses = s.misses
        replay = [svc.submit(k, *args) for k, args in reqs]
        svc.flush()
        assert all(t.done for t in replay)
        s = svc.stats
        assert s.hits + s.misses == s.requests == 2 * len(reqs)
        assert s.misses == cold_misses           # replay is all hits
        # the identity: cold cost is attributed once, never re-accrued
        assert s.compile_s == cold_compile_s
        assert s.warmup_s == cold_warmup_s
    finally:
        svc.close()
