"""Cross-backend conformance suite: the fused-executor contract, enforced.

Randomized small Programs (all modes, partition counts, fan-ins, masks) must
execute IDENTICALLY — final memory, cycle count, op-category stats — on
every backend: the per-op interpreter, per-cycle numpy, span-batched fused
numpy, and the fused/unfused jax runners. Fault injection is covered too:

* ``FaultModel`` sampling is backend-RNG-specific, but fused and unfused
  numpy replay draw in the same (cycle, gate-group) order, so they must be
  bit-exact under the same seed (the guarantee that kept BENCH_device
  results stable when the fused path became the default).
* A ``FaultRealization`` pins the masks themselves (sampled per original
  cycle), so numpy, numpy-fused and jax-fused must agree bit-for-bit — the
  strongest cross-backend statement the stochastic models allow. The
  interpreter takes no faults by design (``CrossbarPlan`` rejects them).

Example counts scale with the ``CONFORMANCE_EXAMPLES`` env var (the nightly
CI job raises it; the deterministic hypothesis fallback caps at 5).
"""
import os

import numpy as np
import pytest
from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.core import (Crossbar, compile_program, execute, fuse_program,
                        have_jax, parse_backend)
from repro.core.fused import JAX_FUSE_MAX_SEGMENTS
from repro.core.compile import MODE_INIT
from repro.core.isa import GATES, ColOp, InitOp, RowOp
from repro.device.faults import FaultModel, FaultRealization

EXAMPLES = int(os.environ.get("CONFORMANCE_EXAMPLES", "4"))

if HAVE_HYPOTHESIS:
    # fixed profile for scheduled CI: no deadline flakes, reproducible order
    from hypothesis import settings as _hs
    _hs.register_profile("nightly", deadline=None, derandomize=True)
    if os.environ.get("HYPOTHESIS_PROFILE") == "nightly":
        _hs.load_profile("nightly")

GEOMETRIES = [(16, 32, 2), (32, 64, 4), (24, 48, 2)]
BACKENDS = ["numpy-unfused", "numpy-fused"] + (
    ["jax-unfused", "jax-fused"] if have_jax() else [])
FAULTY_BACKENDS = ["numpy-unfused", "numpy-fused"] + (
    ["jax-fused"] if have_jax() else [])


def random_program(seed: int):
    """A random well-formed Program + geometry.

    Cycles mix column / row / init modes; gate ops are confined to one
    partition each (trivially co-schedulable) with random fan-ins, masks and
    the occasional run of same-mode cycles over disjoint lines — the shapes
    that become multi-cycle fused spans.
    """
    rng = np.random.default_rng(seed)
    rows, cols, parts = GEOMETRIES[seed % len(GEOMETRIES)]
    rp, cp = rows // parts, cols // parts
    gates = list(GATES)
    prog = []

    def col_cycle():
        cyc = []
        for p in range(parts):
            if rng.random() < 0.3:
                continue
            g = gates[rng.integers(len(gates))]
            ar = GATES[g].arity
            offs = rng.choice(cp, size=ar + 1, replace=False)
            sel = [None, slice(1, rows - 1),
                   sorted(int(v) for v in
                          rng.choice(rows, size=3, replace=False))][
                       rng.integers(3)]
            cyc.append(ColOp(g, tuple(int(p * cp + o) for o in offs[:ar]),
                             int(p * cp + offs[ar]), sel))
        return cyc

    def row_cycle():
        cyc = []
        for q in range(parts):
            if rng.random() < 0.3:
                continue
            g = gates[rng.integers(len(gates))]
            ar = GATES[g].arity
            offs = rng.choice(rp, size=ar + 1, replace=False)
            sel = [None, slice(0, cols // 2),
                   sorted(int(v) for v in
                          rng.choice(cols, size=4, replace=False))][
                       rng.integers(3)]
            cyc.append(RowOp(g, tuple(int(q * rp + o) for o in offs[:ar]),
                             int(q * rp + offs[ar]), sel))
        return cyc

    def init_cycle():
        rsel = [slice(None), sorted(int(v) for v in
                                    rng.choice(rows, 4, replace=False))][
            rng.integers(2)]
        csel = [slice(0, cols, 2), sorted(int(v) for v in
                                          rng.choice(cols, 5, replace=False))][
            rng.integers(2)]
        return [InitOp(rsel, csel, int(rng.integers(2)))]

    for _ in range(int(rng.integers(3, 9))):
        kind = rng.integers(0, 4)
        if kind == 0:
            prog.append(col_cycle())
        elif kind == 1:
            prog.append(row_cycle())
        elif kind == 2:
            prog.append(init_cycle())
        else:
            # a same-mode run: repeats become multi-cycle segments/spans
            mk = col_cycle if rng.random() < 0.5 else row_cycle
            for _ in range(int(rng.integers(2, 4))):
                prog.append(mk())
    prog = [c for c in prog if c]
    if not prog:
        prog = [init_cycle()]
    return prog, rows, cols, parts


def interp_reference(prog, rows, cols, parts, mems):
    ref = np.empty_like(mems)
    xb = Crossbar(rows, cols, parts, parts)
    for b in range(mems.shape[0]):
        xb.mem[:, :] = mems[b]
        xb.cycles = 0
        xb.stats = {k: 0 for k in xb.stats}
        xb.run(prog)
        ref[b] = xb.mem
    return ref, xb.cycles, dict(xb.stats)


@settings(max_examples=EXAMPLES, deadline=None)
@given(st.integers(0, 10_000_000))
def test_all_backends_bit_identical(seed):
    """interp == numpy == numpy-fused == jax(-fused/-unfused): memory,
    cycles and stats, over a multi-crossbar batch."""
    prog, rows, cols, parts = random_program(seed)
    rng = np.random.default_rng(seed + 1)
    B = int(rng.integers(1, 4))
    mems = (rng.random((B, rows, cols)) < 0.5).astype(np.uint8)
    ref, cycles, stats = interp_reference(prog, rows, cols, parts, mems)
    cp = compile_program(prog, rows, cols, parts, parts)
    assert cp.schedule is not None and cp.schedule.n_cycles == cp.n_cycles
    for backend in BACKENDS:
        res = execute(cp, mems, backend=backend)
        np.testing.assert_array_equal(res.mem, ref, err_msg=backend)
        assert res.cycles == cycles, backend
        assert res.stats == stats, backend


@settings(max_examples=EXAMPLES, deadline=None)
@given(st.integers(0, 10_000_000))
def test_fault_model_fused_matches_unfused(seed):
    """FaultModel: fused numpy replays draw-for-draw like unfused numpy, so
    the same seed gives bit-identical faulty memory; the ideal (all-zero)
    model gives fault-free memory on both."""
    prog, rows, cols, parts = random_program(seed)
    rng = np.random.default_rng(seed + 2)
    B = int(rng.integers(1, 4))
    mems = (rng.random((B, rows, cols)) < 0.5).astype(np.uint8)
    cp = compile_program(prog, rows, cols, parts, parts)
    fm = FaultModel(p_sa0=0.02, p_sa1=0.02, p_switch=0.05, p_init=0.05)
    a = execute(cp, mems, backend="numpy-unfused", faults=fm, rng=seed).mem
    b = execute(cp, mems, backend="numpy-fused", faults=fm, rng=seed).mem
    np.testing.assert_array_equal(a, b)
    ideal = execute(cp, mems, backend="numpy").mem
    for backend in ("numpy-unfused", "numpy-fused"):
        res = execute(cp, mems, backend=backend, faults=FaultModel(), rng=0)
        np.testing.assert_array_equal(res.mem, ideal, err_msg=backend)


@settings(max_examples=EXAMPLES, deadline=None)
@given(st.integers(0, 10_000_000))
def test_fault_realization_cross_backend(seed):
    """FaultRealization (stuck-at + switching + init-disturb masks sampled
    per original cycle): every executor backend applies the identical event
    set — numpy, numpy-fused and jax-fused agree bit-exactly."""
    prog, rows, cols, parts = random_program(seed)
    rng = np.random.default_rng(seed + 3)
    B = int(rng.integers(1, 4))
    mems = (rng.random((B, rows, cols)) < 0.5).astype(np.uint8)
    cp = compile_program(prog, rows, cols, parts, parts)
    fm = FaultModel(p_sa0=0.03, p_sa1=0.03, p_switch=0.08, p_init=0.08)
    real = FaultRealization.sample(fm, B, rows, cols, cp.n_cycles, cp.W,
                                   cp.I, rng=seed)
    outs = {be: execute(cp, mems, backend=be, faults=real).mem
            for be in FAULTY_BACKENDS}
    first = FAULTY_BACKENDS[0]
    for be, got in outs.items():
        np.testing.assert_array_equal(got, outs[first],
                                      err_msg=f"{be} vs {first}")
    # the ideal realization is exactly fault-free execution
    real0 = FaultRealization.sample(FaultModel(), B, rows, cols,
                                    cp.n_cycles, cp.W, cp.I, rng=seed)
    assert real0.is_ideal
    ideal = execute(cp, mems, backend="numpy").mem
    for be in FAULTY_BACKENDS:
        np.testing.assert_array_equal(
            execute(cp, mems, backend=be, faults=real0).mem, ideal,
            err_msg=be)


def test_span_batching_handles_war_chains():
    """Regression: a read-after-write-after-read chain across consecutive
    same-mode cycles (cycle k reads the line cycle k+1 rewrites) must fuse
    into a span that gathers ALL inputs before any scatter — the XNOR
    scratch-recycling pattern that caught the first span executor."""
    prog = [
        [ColOp("NAND2", (0, 1), 2, None)],   # writes 2
        [ColOp("OAI3", (0, 1, 2), 3, None)], # reads 2
        [ColOp("NAND2", (4, 5), 2, None)],   # REWRITES 2 (WAR vs prev read)
        [ColOp("OAI3", (4, 5, 2), 6, None)],
    ]
    rows, cols = 8, 8
    rng = np.random.default_rng(0)
    mems = (rng.random((3, rows, cols)) < 0.5).astype(np.uint8)
    ref, cycles, stats = interp_reference(prog, rows, cols, 1, mems)
    cp = compile_program(prog, rows, cols, 1, 1)
    for backend in BACKENDS:
        res = execute(cp, mems, backend=backend)
        np.testing.assert_array_equal(res.mem, ref, err_msg=backend)


def test_fusion_cycle_accounting_invariant():
    """Segments partition the trace exactly: no hardware cycle is created,
    dropped, or double-counted by fusion."""
    prog, rows, cols, parts = random_program(17)
    cp = compile_program(prog, rows, cols, parts, parts, fuse=False)
    assert cp.schedule is None
    sched = fuse_program(cp)
    covered = sorted((s.t0, s.t1) for s in sched.segments)
    assert covered[0][0] == 0 and covered[-1][1] == cp.n_cycles
    assert all(a[1] == b[0] for a, b in zip(covered, covered[1:]))
    assert sched.n_cycles == cp.n_cycles
    for seg in sched.segments:
        spans = sorted(seg.spans)
        assert spans[0][0] == 0 and spans[-1][1] == seg.length
        assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))


def test_backend_name_parsing_and_contracts():
    from repro.core import available_backends

    assert parse_backend("numpy") == ("numpy", None)
    assert parse_backend("numpy-unfused") == ("numpy", "unfused")
    assert parse_backend("jax-fused") == ("jax", "fused")
    assert parse_backend("pallas") == ("pallas", None)
    with pytest.raises(ValueError):
        parse_backend("interp")        # plan-level only
    with pytest.raises(ValueError):
        parse_backend("torch")
    for bad in ("auto", "auto-fused", "pallas-unfused"):
        with pytest.raises(ValueError):
            parse_backend(bad)         # no meta-backend; pallas takes no suffix
    with pytest.raises(ValueError) as ei:
        parse_backend("torch")
    # the error enumerates the real set
    for be in ("numpy-unfused", "jax-fused", "pallas"):
        assert f"'{be}'" in str(ei.value)
    assert "'auto'" not in str(ei.value)
    bs = available_backends()
    assert {"numpy", "numpy-fused", "numpy-unfused"} <= set(bs)
    assert "auto" not in bs
    assert ("jax" in bs) == have_jax() and ("pallas" in bs) == have_jax()

    prog = [[ColOp("NOT", (0,), 1, None)]]
    cp = compile_program(prog, 8, 8, 1, 1)
    mem = np.zeros((8, 8), np.uint8)
    if have_jax():
        with pytest.raises(ValueError):
            # FaultModel sampling lives on the unfused PRNG path
            execute(cp, mem, backend="jax-fused",
                    faults=FaultModel(p_switch=0.1))
        real = FaultRealization.sample(FaultModel(), 1, 8, 8, cp.n_cycles,
                                       cp.W, cp.I)
        with pytest.raises(ValueError):
            execute(cp, mem, backend="jax-unfused", faults=real)


def test_unfused_compile_still_executes():
    """fuse=False traces run on the per-cycle paths; explicitly requesting a
    fused backend attaches the schedule on demand."""
    prog = [[InitOp(slice(None), [0, 1], 0)],
            [ColOp("NOT", (0,), 1, None)]]
    cp = compile_program(prog, 8, 8, 1, 1, fuse=False)
    mem = np.zeros((8, 8), np.uint8)
    a = execute(cp, mem, backend="numpy").mem          # rule -> unfused
    assert cp.schedule is None
    b = execute(cp, mem, backend="numpy-fused").mem    # attaches on demand
    assert cp.schedule is not None
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# The one rule that picks the fused or unfused body (engine._variant)
# ---------------------------------------------------------------------------

needs_jax = pytest.mark.skipif(not have_jax(), reason="needs jax")


def _rule_trace(kind):
    """A fuse-eligible trace, one past JAX_FUSE_MAX_SEGMENTS segments (col
    and row cycles alternate, so every cycle is its own segment), or a
    random program compiled with ``fuse=False``."""
    if kind == "long":
        prog = [[ColOp("NOT", (0,), 1, None)] if t % 2 == 0
                else [RowOp("NOT", (0,), 1, None)]
                for t in range(JAX_FUSE_MAX_SEGMENTS + 2)]
        return compile_program(prog, 8, 8, 1, 1)
    prog, rows, cols, parts = random_program(3)
    return compile_program(prog, rows, cols, parts, parts,
                           fuse=kind != "unfused")


def _rule_faults(kind, cp, B):
    if kind == "model":
        return FaultModel()                       # ideal, on the FaultModel path
    if kind == "real":
        fm = FaultModel(p_sa0=0.03, p_sa1=0.03, p_switch=0.08, p_init=0.08)
        return FaultRealization.sample(fm, B, cp.rows, cp.cols, cp.n_cycles,
                                       cp.W, cp.I, rng=5)
    return None


# (trace, faults, backend) -> the body the rule must pick
RULE = [
    ("eligible", None, "numpy", "fused"),
    ("eligible", None, "jax", "fused"),
    ("eligible", "model", "numpy", "fused"),
    ("eligible", "model", "jax", "unfused"),
    ("eligible", "real", "numpy", "fused"),
    ("eligible", "real", "jax", "fused"),
    ("long", None, "numpy", "fused"),
    ("long", None, "jax", "unfused"),
    ("long", "model", "numpy", "fused"),
    ("long", "model", "jax", "unfused"),
    ("long", "real", "numpy", "fused"),
    ("long", "real", "jax", "fused"),
    ("unfused", None, "numpy", "unfused"),
    ("unfused", None, "jax", "unfused"),
    ("unfused", "model", "numpy", "unfused"),
    ("unfused", "model", "jax", "unfused"),
    ("unfused", "real", "numpy", "fused"),
    ("unfused", "real", "jax", "fused"),
    # an explicit variant is obeyed whatever the trace says
    ("eligible", None, "numpy-unfused", "unfused"),
    ("eligible", None, "jax-unfused", "unfused"),
    ("unfused", None, "numpy-fused", "fused"),
    ("unfused", None, "jax-fused", "fused"),
]


def _runner_key(base, variant, faults):
    """The ``cp._caches`` entry the chosen runner is memoised under."""
    if base == "numpy":
        return "numpy_fused_plan" if variant == "fused" else "numpy_plan"
    if variant == "fused":
        return (("jax_fused_real",) if isinstance(faults, FaultRealization)
                else ("jax_fused",))
    return ("jax_runner_faulty" if isinstance(faults, FaultModel)
            else "jax_runner")


@pytest.mark.parametrize(
    "trace,fault_kind,backend,want",
    [pytest.param(*case, id="-".join(str(c) for c in case),
                  marks=[needs_jax] if case[2].startswith("jax") else [])
     for case in RULE])
def test_executor_rule(trace, fault_kind, backend, want):
    """Which body runs is read off the trace, the faults and an explicit
    suffix; whichever it is, memory, cycles and stats equal a
    ``numpy-unfused`` run under the same faults."""
    cp = _rule_trace(trace)
    if trace == "long":
        assert cp.schedule.n_segments > JAX_FUSE_MAX_SEGMENTS
    rng = np.random.default_rng(11)
    B = 3
    mems = (rng.random((B, cp.rows, cp.cols)) < 0.5).astype(np.uint8)
    faults = _rule_faults(fault_kind, cp, B)
    res = execute(cp, mems, backend=backend, faults=faults, rng=0)
    base = parse_backend(backend)[0]
    other = "unfused" if want == "fused" else "fused"
    assert _runner_key(base, want, faults) in cp._caches
    assert _runner_key(base, other, faults) not in cp._caches
    ref = execute(_rule_trace(trace), mems, backend="numpy-unfused",
                  faults=faults, rng=0)
    np.testing.assert_array_equal(res.mem, ref.mem)
    assert res.cycles == ref.cycles and res.stats == ref.stats


@pytest.mark.parametrize("entry", ["parse_backend", "execute", "PlanService"])
def test_auto_backend_refused(entry):
    """``"auto"`` is not a backend: every entry point refuses it with the
    error that lists the accepted spellings."""
    from repro.serve.matpim import PlanService

    calls = {
        "parse_backend": lambda: parse_backend("auto"),
        "execute": lambda: execute(
            compile_program([[ColOp("NOT", (0,), 1, None)]], 8, 8, 1, 1),
            np.zeros((8, 8), np.uint8), backend="auto"),
        "PlanService": lambda: PlanService(backend="auto", store=False),
    }
    with pytest.raises(ValueError, match="unknown engine backend 'auto'") \
            as ei:
        calls[entry]()
    for be in ("numpy", "numpy-unfused", "jax", "jax-fused", "pallas"):
        assert f"'{be}'" in str(ei.value)
