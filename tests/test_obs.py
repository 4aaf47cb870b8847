"""Telemetry subsystem (repro.obs): span tracer, metrics registry, and the
instrumentation threaded through compile/engine/autotune/serve.

Covers the ISSUE-7 acceptance contract: the disabled tracing path adds <2%
to ``engine.execute``, and a Chrome-trace JSON recorded from a mixed
request stream is structurally loadable by Perfetto (object form, complete
events, per-thread time containment).
"""
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import BinaryMatvecPlan
from repro.core.engine import execute
from repro.obs import metrics, trace
from repro.serve.matpim import PlanService, ServeRequest

sys.path.insert(0, str(Path(__file__).parent.parent))  # benchmarks/ imports

GEOM = dict(rows=64, cols=256, parts=8)


@pytest.fixture
def tracer():
    """Enabled tracer, always disabled again (even on failure)."""
    tr = trace.enable()
    yield tr
    trace.disable()


@pytest.fixture(autouse=True)
def _fresh_metrics():
    metrics.reset_metrics()
    yield


# ---------------------------------------------------------------------------
# trace.py
# ---------------------------------------------------------------------------


def test_disabled_span_is_shared_noop():
    assert not trace.enabled()
    s1 = trace.span("a", x=1)
    s2 = trace.span("b")
    assert s1 is s2                      # singleton: no per-call allocation
    with s1 as s:
        assert s.set(y=2) is s           # attrs accepted and dropped
    assert trace.get_tracer() is None
    assert trace.save("/tmp/never-written.json") is False


def test_span_nesting_depth_and_event_fields(tracer):
    with trace.span("outer", tag="t"):
        with trace.span("inner") as s:
            s.set(step=3)
    evs = tracer.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # exit order
    inner, outer = evs
    for e in evs:
        assert e["ph"] == "X"
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert e["pid"] and e["tid"]
    assert outer["args"]["depth"] == 0 and outer["args"]["tag"] == "t"
    assert inner["args"]["depth"] == 1 and inner["args"]["step"] == 3
    # time containment: inner lies inside outer on the same track
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_disable_returns_tracer_and_stops_recording(tracer):
    with trace.span("kept"):
        pass
    tr = trace.disable()
    assert tr is tracer and not trace.enabled()
    with trace.span("dropped"):
        pass
    assert [e["name"] for e in tr.events()] == ["kept"]
    trace.enable()                        # fixture's disable() needs a tracer


def test_chrome_trace_save_roundtrip(tracer, tmp_path):
    with trace.span("a"):
        pass
    p = tmp_path / "sub" / "trace.json"
    tracer.save(p)                        # creates parent dirs
    d = json.loads(p.read_text())
    assert d["displayTimeUnit"] == "ms"
    assert [e["name"] for e in d["traceEvents"]] == ["a"]


def _profiled(tmp_path, fn, prefix):
    """Run ``fn`` under ``jax.profiler.trace``; its result and the host
    events whose names start with ``prefix``, as ``(name, start_ns, end_ns,
    stats)`` in start order."""
    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
            {k: v for k, v in e.stats})
           for plane in pd.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(prefix)]
    return out, sorted(evs, key=lambda ev: ev[1])


@pytest.mark.parametrize("obs_on", [True, False], ids=["enabled", "disabled"])
def test_span_reaches_the_profiler_with_its_args(tmp_path, obs_on):
    tr = trace.enable() if obs_on else None
    try:
        def traced():
            with trace.span("obs.outer", call=7):
                with trace.span("obs.inner", word=2, bytes=4096) as sp:
                    assert sp is not trace._NULL_SPAN
                    sp.set(resolved="jax-fused")

        _, evs = _profiled(tmp_path, traced, "obs.")
    finally:
        if obs_on:
            trace.disable()
    assert [name for name, *_ in evs] == ["obs.outer", "obs.inner"]
    (_, o0, o1, outer), (_, i0, i1, inner) = evs
    assert o0 <= i0 and i1 <= o1
    assert outer == {"call": 7}
    assert inner == {"word": 2, "bytes": 4096, "resolved": "jax-fused"}
    if obs_on:     # the perf_counter events are recorded as before
        got = [(e["name"], e["args"]) for e in tr.events()]
        assert got == [("obs.inner", {"depth": 1, "word": 2, "bytes": 4096,
                                      "resolved": "jax-fused"}),
                       ("obs.outer", {"depth": 0, "call": 7})]
    # no profiler collecting and tracing disabled: the shared no-op again
    assert trace.span("obs.after", x=1) is trace._NULL_SPAN


# ---------------------------------------------------------------------------
# metrics.py
# ---------------------------------------------------------------------------


def test_registry_get_or_create_and_type_conflict():
    reg = metrics.MetricsRegistry()
    c = reg.counter("x")
    assert reg.counter("x") is c
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")
    reg.gauge("g").set(7)
    assert reg.names() == ["g", "x"]
    snap = reg.snapshot()
    assert snap["x"] == {"type": "counter", "value": 3.5}
    assert snap["g"] == {"type": "gauge", "value": 7}
    json.dumps(snap)                      # stable JSON contract
    reg.reset()
    assert len(reg) == 0


def test_histogram_quantiles_and_snapshot():
    h = metrics.Histogram()
    assert h.quantile(0.5) == 0.0         # empty
    vals = list(range(1, 1001))           # 1..1000 µs
    for v in vals:
        h.observe(v)
    assert h.count == 1000 and h.vmin == 1 and h.vmax == 1000
    assert abs(h.mean - np.mean(vals)) < 1e-9
    # bucket-interpolated quantiles: right order of magnitude, ordered
    q50, q95, q99 = h.quantile(0.5), h.quantile(0.95), h.quantile(0.99)
    assert 300 <= q50 <= 700
    assert 800 <= q95 <= 1000
    assert q50 <= q95 <= q99 <= 1000
    d = h.as_dict()
    assert d["type"] == "histogram" and d["count"] == 1000
    assert {"p50", "p95", "p99", "min", "max"} <= set(d)
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_overflow_bucket_clamps_to_max():
    h = metrics.Histogram(bounds=[10.0, 100.0])
    for v in (5, 50, 5000):
        h.observe(v)
    assert h.quantile(1.0) == 5000        # overflow interpolates to vmax


# ---------------------------------------------------------------------------
# instrumentation: compile / engine / autotune / serve
# ---------------------------------------------------------------------------


def _small_plan():
    plan = BinaryMatvecPlan(8, 16, rows=64, cols=256, parts=8)
    rng = np.random.default_rng(0)
    A = rng.choice([-1, 1], size=(8, 16))
    x = rng.choice([-1, 1], size=16)
    cp = plan.compile()
    mem = np.zeros((2, plan.rows, plan.cols), dtype=np.uint8)
    for b in range(2):
        plan.load_into(mem[b], A, x)
    return cp, mem


def test_engine_execute_publishes_metrics_and_span(tracer):
    cp, mem = _small_plan()
    res = execute(cp, mem, backend="numpy")
    assert metrics.counter("engine.execute.calls").value == 1
    assert metrics.counter("engine.execute.calls.numpy").value == 1
    h = metrics.registry().get("engine.execute.wall_us.numpy")
    assert h is not None and h.count == 1 and h.sum > 0
    names = [e["name"] for e in tracer.events()]
    assert "engine.execute" in names
    ev = next(e for e in tracer.events() if e["name"] == "engine.execute")
    assert ev["args"]["backend"] == "numpy"
    assert ev["args"]["resolved"] == res.backend
    assert ev["args"]["cycles"] == res.cycles


def _batch_of_64():
    plan = BinaryMatvecPlan(8, 16, rows=64, cols=256, parts=8)
    rng = np.random.default_rng(5)
    mems = np.zeros((64, plan.rows, plan.cols), dtype=np.uint8)
    for b in range(64):
        plan.load_into(mems[b], rng.choice([-1, 1], size=(8, 16)),
                       rng.choice([-1, 1], size=16))
    return plan, mems


@pytest.mark.parametrize("runner", ["fused", "realization", "unfused"])
def test_engine_host_path_spans_under_the_profiler(tmp_path, runner):
    """Two packed words through each jax word loop: one engine.word per
    word with its h2d/replay/d2h children, all inside engine.execute under
    one call id, no host engine.pack or engine.unpack (the word program
    packs and unpacks on the device), and the same bits as without the
    profiler."""
    from repro.device.faults import FaultModel, FaultRealization
    plan, mems = _batch_of_64()
    cp = plan.compile()
    backend = "jax-unfused" if runner == "unfused" else "jax-fused"
    faults = None
    if runner == "realization":
        faults = FaultRealization.sample(
            FaultModel(p_sa0=0.01, p_switch=0.01), 64, plan.rows,
            plan.cols, cp.n_cycles, cp.W, cp.I, rng=3)
    want = plan.execute_batch(mems, backend=backend, faults=faults)  # warm
    got, evs = _profiled(
        tmp_path,
        lambda: plan.execute_batch(mems, backend=backend, faults=faults),
        "engine.")
    np.testing.assert_array_equal(got.mem, want.mem)
    assert got.backend == backend

    names = [name for name, *_ in evs]
    assert "engine.pack" not in names and "engine.unpack" not in names
    assert names == (["engine.execute"]
                     + ["engine.word", "engine.h2d", "engine.replay",
                        "engine.d2h"] * 2)
    (_, x0, x1, ex), *inner = evs
    assert len({st["call"] for *_, st in evs}) == 1
    assert all(x0 <= s and e <= x1 for _, s, e, _ in inner)
    words = [i for i, ev in enumerate(inner) if ev[0] == "engine.word"]
    for w, i in enumerate(words):
        _, w0, w1, st = inner[i]
        assert st == {"call": ex["call"], "word": w,
                      "bytes": 32 * plan.rows * plan.cols}
        for _, s, e, child in inner[i + 1:i + 4]:
            assert w0 <= s and e <= w1 and child["word"] == w


def test_fault_model_runner_keeps_the_host_pack_spans(tmp_path):
    """The single-word ``FaultModel`` runner still packs and unpacks on the
    host: engine.pack (words, crossbars) then engine.unpack, no word
    spans, same call id as engine.execute."""
    from repro.device.faults import FaultModel
    plan, mems = _batch_of_64()
    cp = plan.compile()
    fm = FaultModel(p_switch=1e-3)
    want = execute(cp, mems[:32], backend="jax", faults=fm, rng=0)  # warm
    got, evs = _profiled(
        tmp_path,
        lambda: execute(cp, mems[:32], backend="jax", faults=fm, rng=0),
        "engine.")
    np.testing.assert_array_equal(got.mem, want.mem)
    assert [name for name, *_ in evs] == ["engine.execute", "engine.pack",
                                          "engine.unpack"]
    (_, _, _, ex), (_, _, _, pk), (_, _, _, up) = evs
    assert pk == {"call": ex["call"], "words": 1, "crossbars": 32}
    assert up == {"call": ex["call"], "words": 1}


def test_engine_fault_run_sets_fault_gauges():
    from repro.device.faults import FaultModel
    cp, mem = _small_plan()
    execute(cp, mem, backend="numpy", faults=FaultModel(p_switch=1e-3),
            rng=0)
    assert metrics.counter("engine.execute.fault_runs").value == 1
    assert metrics.gauge("engine.fault.p_switch").value == 1e-3
    assert metrics.gauge("engine.fault.p_sa0").value == 0.0


def test_compile_and_autotune_resolve_metrics():
    from repro.core.autotune import TuningTable, program_key, resolve_auto
    cp, mem = _small_plan()               # compiles once inside plan.compile
    assert metrics.counter("compile.programs").value >= 1
    assert metrics.counter("compile.seconds").value > 0
    table = TuningTable()
    be, mb, src = resolve_auto(cp, 2, table=table)
    assert src == "heuristic"
    assert metrics.counter("autotune.resolve.heuristic").value == 1
    from repro.core.autotune import batch_bucket
    table.record(program_key(cp), batch_bucket(2), be, 100.0)
    _, _, src = resolve_auto(cp, 2, table=table)
    assert src == "measured"
    assert metrics.counter("autotune.resolve.measured").value == 1


def test_autotune_execute_probe_counters():
    from repro.core.autotune import TuningTable, autotune_execute, candidates
    cp, mem = _small_plan()
    table = TuningTable()
    res, entry = autotune_execute(cp, mem, table, reps=1, cheap=True,
                                  save=False)
    n_cand = len(candidates(cp, mem.shape[0], cheap=True))
    assert metrics.counter("autotune.probes").value == n_cand
    win = metrics.counter(
        f"autotune.wins.{entry.backend}"
        + (f"@{entry.max_batch}" if entry.max_batch else ""))
    assert win.value == 1


def test_serve_cache_and_latency_metrics():
    rng = np.random.default_rng(0)
    svc = PlanService(**GEOM)
    A = rng.choice([-1, 1], size=(4, 8))
    x = rng.choice([-1, 1], size=8)
    svc.submit_binary_matvec(A, x)
    svc.submit_binary_matvec(-A, x)
    svc.flush()
    assert metrics.counter("serve.cache.misses").value == svc.stats.misses
    assert metrics.counter("serve.cache.hits").value == svc.stats.hits
    assert metrics.counter("serve.requests").value == 2
    h = metrics.registry().get("serve.request_latency_us")
    assert h is not None and h.count == 2 and h.vmin > 0
    assert metrics.counter("serve.warmup_s").value == svc.stats.warmup_s > 0
    assert metrics.gauge("serve.queue_depth_units").value == 0


# ---------------------------------------------------------------------------
# mixed-stream trace: structural Perfetto validation (acceptance criterion)
# ---------------------------------------------------------------------------


def _mixed_stream(rng, n):
    reqs = []
    for i in range(n):
        m, k = int(rng.integers(2, 10)), int(rng.integers(4, 20))
        if i % 2:
            reqs.append(ServeRequest("matvec", (
                rng.integers(0, 16, size=(m, k)),
                rng.integers(0, 16, size=k), 4)))
        else:
            reqs.append(ServeRequest("binary_matvec", (
                rng.choice([-1, 1], size=(m, k)),
                rng.choice([-1, 1], size=k))))
    return reqs


def test_mixed_stream_trace_loads_in_perfetto(tracer, tmp_path):
    rng = np.random.default_rng(3)
    svc = PlanService(**GEOM)
    svc.run_stream(iter(_mixed_stream(rng, 10)), slots=8)
    trace.disable()
    p = tmp_path / "mixed.json"
    tracer.save(p)
    trace.enable(tracer)                 # hand back to the fixture

    # -- structural validation of the Chrome-trace object form -------------
    d = json.loads(p.read_text())
    assert set(d) == {"traceEvents", "displayTimeUnit"}
    evs = d["traceEvents"]
    assert len(evs) > 10
    for e in evs:
        assert set(e) == {"name", "ph", "ts", "dur", "pid", "tid", "args"}
        assert e["ph"] == "X"            # complete events only
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["args"]["depth"], int)

    names = {e["name"] for e in evs}
    assert {"serve.stream", "serve.admit", "serve.step", "serve.bucket",
            "serve.load", "serve.decode", "serve.plan_build",
            "compile.lower", "engine.execute"} <= names

    # -- hierarchy by time containment (what Perfetto reconstructs) --------
    def contains(parent, child):
        return (parent["ts"] <= child["ts"] and child["ts"] + child["dur"]
                <= parent["ts"] + parent["dur"])

    by = lambda n: [e for e in evs if e["name"] == n]  # noqa: E731
    for child_name, parent_name in [("engine.execute", "serve.bucket"),
                                    ("serve.bucket", "serve.step"),
                                    ("serve.load", "serve.bucket"),
                                    ("serve.decode", "serve.bucket"),
                                    ("serve.step", "serve.stream")]:
        for c in by(child_name):
            assert any(contains(p, c) for p in by(parent_name)), \
                (child_name, parent_name)
    # depths recorded match the lexical nesting the containment implies
    for c in by("serve.bucket"):
        assert c["args"]["depth"] > 0


# ---------------------------------------------------------------------------
# disabled-path overhead: the <2% acceptance criterion
# ---------------------------------------------------------------------------


def _per_call_us(fn, n):
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


def test_tracing_disabled_overhead_under_2pct(single_retry):
    """The instrumentation ``engine.execute`` gained must cost <2% of a
    representative execute wall while tracing is disabled.

    Measured directly: a loop running exactly the added operations (the
    disabled ``span()`` enter/exit, the clock reads, the counter/histogram
    updates) vs the best-of-N wall of the small-plan execute itself.
    """
    from repro.device.faults import FaultModel, FaultRealization
    assert not trace.enabled()
    cp, mem = _small_plan()
    faults = None

    def added_ops():                      # mirror of the execute() wrapper
        t0 = time.perf_counter()
        with trace.span("engine.execute", backend="numpy") as sp:
            sp.set(resolved="numpy-fused", cycles=123)
        wall_us = (time.perf_counter() - t0) * 1e6
        label = "numpy-fused".split("@", 1)[0]
        metrics.counter("engine.execute.calls").inc()
        metrics.counter(f"engine.execute.calls.{label}").inc()
        metrics.histogram(f"engine.execute.wall_us.{label}").observe(wall_us)
        if isinstance(faults, FaultModel):
            pass                          # not taken in the common case
        elif isinstance(faults, FaultRealization):
            pass

    added_ops()                           # warm metric creation
    execute(cp, mem, backend="numpy")     # warm

    def timing_check():
        over_us = min(_per_call_us(added_ops, 2000) for _ in range(5))
        wall_us = min(
            _per_call_us(lambda: execute(cp, mem, backend="numpy"), 5)
            for _ in range(5))
        assert over_us < 0.02 * wall_us, (
            f"disabled-path instrumentation {over_us:.2f}us vs execute "
            f"{wall_us:.1f}us = {100 * over_us / wall_us:.2f}%")

    single_retry(timing_check)   # wall-clock only: one bounded re-measure


# ---------------------------------------------------------------------------
# SLO harness: tiny sweep end-to-end + schema contract
# ---------------------------------------------------------------------------


def test_slo_sweep_rows_pass_schema_validation(tmp_path):
    from benchmarks.report import validate_slo
    from benchmarks.slo import run_sweep, write_json

    payload = run_sweep(quick=True, slots=16, n_requests=6,
                        log=lambda *a, **k: None)
    assert validate_slo(payload) == []
    assert len(payload["rows"]) >= 3
    modes = [r["mode"] for r in payload["rows"]]
    assert modes.count("closed") == 1 and modes.count("open") >= 2
    assert payload["capacity_rps"] > 0
    wr = payload["warm_restart"]          # store replay ran compile-free
    assert wr["compile_programs"] == 0
    assert wr["store_hits"] == wr["misses"] > 0
    # batch-polymorphic runners: the replay builds some, the second replay
    # of identical traffic on the warm service builds none
    assert wr["runner_builds"] >= 1
    assert wr["runner_rebuilds"] == 0
    for r in payload["rows"]:
        assert r["requests"] == 6
        assert 0 <= r["hit_rate"] <= 1
        assert r["p50_ms"] <= r["p95_ms"] <= r["p99_ms"]
    p = tmp_path / "BENCH_slo.json"
    write_json(payload, p)
    assert json.loads(p.read_text())["bench"] == "slo"


def test_slo_schema_validator_catches_breakage():
    from benchmarks.report import validate_slo
    ok = {"schema": 2, "bench": "slo",
          "cold_start": {"warm_wall_s": 1.0, "compile_s": 0.5,
                         "warmup_s": 0.2, "store_hits": 0},
          "warm_restart": {"requests": 3, "replay_wall_s": 0.5,
                           "first_batch_ms": 2.0, "steady_p95_ms": 2.0,
                           "compile_s": 0.01, "warmup_s": 0.0,
                           "store_hits": 2, "misses": 2,
                           "compile_programs": 0, "runner_builds": 2,
                           "runner_rebuilds": 0, "p50_ms": 1.0,
                           "p95_ms": 2.0, "p99_ms": 3.0},
          "rows": [
              {"mode": m, "load_factor": lf, "offered_rps": off,
               "achieved_rps": 1.0, "requests": 1, "p50_ms": 1.0,
               "p95_ms": 2.0, "p99_ms": 3.0, "mean_queue_units": 1.0,
               "max_queue_units": 1, "hit_rate": 0.5, "batches": 1}
              for m, lf, off in [("closed", None, None), ("open", 0.5, 10.0),
                                 ("open", 1.5, 30.0)]]}
    assert validate_slo(ok) == []
    bad = json.loads(json.dumps(ok))
    bad["rows"][1]["p95_ms"] = 0.1        # below p50
    assert any("percentiles" in e for e in validate_slo(bad))
    bad = json.loads(json.dumps(ok))
    del bad["rows"][0]["hit_rate"]
    assert any("missing keys" in e for e in validate_slo(bad))
    bad = json.loads(json.dumps(ok))
    del bad["warm_restart"]               # restart proof is not optional
    assert any("warm_restart" in e for e in validate_slo(bad))
    bad = json.loads(json.dumps(ok))
    bad["warm_restart"]["compile_programs"] = 3
    assert any("compile-free" in e for e in validate_slo(bad))
    bad = json.loads(json.dumps(ok))
    del bad["cold_start"]["compile_s"]
    assert any("cold_start" in e for e in validate_slo(bad))
    bad = json.loads(json.dumps(ok))
    del bad["warm_restart"]["runner_rebuilds"]   # v2 keys are mandatory
    assert any("missing keys" in e for e in validate_slo(bad))
    assert validate_slo({"schema": 1, "bench": "slo", "rows": []})


def test_trace_report_self_time(tmp_path):
    sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))
    import trace_report

    tr = trace.enable()
    with trace.span("outer"):
        with trace.span("inner"):
            time.sleep(0.002)
    trace.disable()
    p = tmp_path / "t.json"
    tr.save(p)
    rows = trace_report.summarize(trace_report.load_events(str(p)))
    byname = {r.name: r for r in rows}
    assert byname["inner"].count == 1
    assert byname["inner"].self_us >= 2000 * 0.5   # sleep dominates
    assert byname["outer"].self_us < byname["outer"].total_us
    assert abs(byname["outer"].total_us
               - (byname["outer"].self_us + byname["inner"].total_us)) < 1.0
