"""The Table II full-precision convolution as an engine cell, on the CPU at a
small size: its plain reference agrees with the served simulator, the jax
path decoded by ``chipbench/plans/conv.py`` agrees with the reference, the
control fails the comparison, the configuration files state what the
compiled traces give, and the word loop counts the cycles of each mode it
replays."""
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import bench, harness, ops, traffic  # noqa: E402

GEOM = {"rows": 64, "cols": 256, "parts": 8}
SPEC = {"op": "conv", "shape": [16, 8], "k": 3, "N": 8}
CELL = "conv-1024x8-3x3-n32.mc192"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def rng(seed=2**37 + 11):
    return traffic.rng(seed, traffic.STREAM_OPERANDS)


@pytest.fixture(scope="module")
def small():
    """The small plan, 33 loaded crossbars (one full word and one padded
    word) and their operands."""
    plan = ops.make_plan(SPEC, GEOM)
    A, K = ops.operands(SPEC, rng(), batch=(33,))
    mems = np.zeros((33, plan.rows, plan.cols), np.uint8)
    for b in range(33):
        plan.load_into(mems[b], A[b], K[b])
    return plan, mems, A, K


def test_reference_by_hand():
    a = np.arange(12, dtype=np.int64).reshape(3, 4)
    kern = np.array([[1, 0], [0, 2]], np.int64)
    spec = {"op": "conv", "shape": [3, 4], "k": 2, "N": 4}
    want = (a[:2, :3] + 2 * a[1:, 1:]) % 16        # not flipped, mod 2^N
    np.testing.assert_array_equal(ops.reference(spec, a, kern), want)


@pytest.mark.parametrize("spec", [SPEC, dict(SPEC, shape=[20, 7])],
                         ids=["16x8", "20x7-cropped"])
def test_reference_matches_the_served_simulator(spec):
    from repro.serve.matpim import PlanService

    svc = PlanService(backend="numpy", store=False, **GEOM)
    r = rng()
    reqs = [ops.operands(spec, r) for _ in range(3)]
    tickets = [ops.submit(svc, spec, a, b) for a, b in reqs]
    svc.flush()
    for (a, b), t in zip(reqs, tickets):
        assert t.done
        assert ops.wrong(spec, t.result, ops.reference(spec, a, b)) == 0


def test_batched_decode_matches_plan_decode(small):
    plan, mems, A, K = small
    out = plan.execute_batch(mems[:5], backend="numpy").mem
    got = ops.decode_batch(SPEC, plan, out)
    assert got.dtype == np.uint64 and got.shape == (5, 14, 6)
    want = np.stack([plan.decode_out(out[b]) for b in range(5)])
    assert ops.wrong(SPEC, got, want) == 0
    assert ops.wrong(SPEC, got, ops.reference(SPEC, A[:5], K[:5])) == 0


def test_batched_decode_of_column_blocks():
    """Balanced splitting (alpha > 1): block i's outputs sit in band i."""
    spec = {"op": "conv", "shape": [8, 12], "k": 3, "N": 8}
    plan = ops.make_plan(spec, GEOM)
    assert plan.alpha > 1
    A, K = ops.operands(spec, rng(), batch=(3,))
    mems = np.zeros((3, plan.rows, plan.cols), np.uint8)
    for b in range(3):
        plan.load_into(mems[b], A[b], K[b])
    out = plan.execute_batch(mems, backend="numpy").mem
    got = ops.decode_batch(spec, plan, out)
    want = np.stack([plan.decode_out(out[b]) for b in range(3)])
    assert ops.wrong(spec, got, want) == 0
    assert ops.wrong(spec, got, ops.reference(spec, A, K)) == 0


def test_jax_unfused_batch_matches_the_reference(small):
    plan, mems, A, K = small
    res = plan.execute_batch(mems, backend="jax-unfused")
    cp = plan.compile()
    assert (res.cycles, res.stats) == (cp.n_cycles, dict(cp.stats))
    got = ops.decode_batch(SPEC, plan, res.mem)
    assert ops.wrong(SPEC, got, ops.reference(SPEC, A, K)) == 0


@pytest.mark.parametrize("spec", [SPEC, dict(SPEC, N=32)],
                         ids=["N8", "N32"])
def test_control_fails_the_comparison(spec):
    a, b = ops.operands(spec, rng(), batch=(4,))
    want = ops.reference(spec, a, b)
    assert ops.wrong(spec, ops.control(spec, a, b), want) == 4
    assert ops.wrong(spec, want, want) == 0
    # the flip is the only broken guarantee: a symmetric kernel passes
    sym = (b + np.flip(b, axis=(-2, -1))) % (1 << spec["N"])
    assert ops.wrong(spec, ops.control(spec, a, sym),
                     ops.reference(spec, a, sym)) == 0


def test_replay_counters_grow_by_mode_cycles_per_word(small):
    from repro.core.engine import mode_cycles
    from repro.obs import metrics

    plan, mems, _, _ = small
    cp = plan.compile()
    modes = mode_cycles(cp)
    assert sum(modes) == cp.n_cycles and all(modes)
    names = [f"engine.replay.{m}_cycles" for m in ("col", "row", "init")]
    for backend, B, words in (("jax-unfused", 33, 2),
                              ("jax-unfused", 32, 1),
                              ("numpy", 33, 0)):
        before = [metrics.counter(n).value for n in names]
        plan.execute_batch(mems[:B], backend=backend)
        grew = [metrics.counter(n).value - v for n, v in zip(names, before)]
        assert grew == [words * n for n in modes], backend


def test_replay_counters_on_the_fused_runners():
    from repro.core import BinaryMatvecPlan
    from repro.core.engine import mode_cycles
    from repro.device.faults import FaultModel, FaultRealization
    from repro.obs import metrics

    plan = BinaryMatvecPlan(8, 16, **GEOM)
    cp = plan.compile()
    mems = np.zeros((40, plan.rows, plan.cols), np.uint8)
    real = FaultRealization.sample(FaultModel(p_switch=0.01), 40, plan.rows,
                                   plan.cols, cp.n_cycles, cp.W, cp.I, rng=1)
    col = metrics.counter("engine.replay.col_cycles")
    for faults in (None, real):
        before = col.value
        plan.execute_batch(mems, backend="jax-fused", faults=faults)
        assert col.value - before == 2 * mode_cycles(cp)[0]


def test_conv_cell_correct_only_when_sound():
    """The cell's runner, at a small size on the jax path: sound, then with
    the control in the program's place."""
    import jax

    cfg = dict(bench.load_config("conv-1024x8-3x3-n32"), geometry=GEOM,
               plan=SPEC)
    cp = ops.make_plan(SPEC, GEOM).compile()
    cfg["cycles"], cfg["stats"] = cp.n_cycles, dict(cp.stats)
    for control in (False, True):
        result, _, info = harness.run_cell(
            BENCH, CELL, seed=2**41 + 9, seconds=0.2, trace=False,
            t0=time.perf_counter(), devs=jax.devices(), config=cfg,
            traffic={"loop": "batch", "crossbars": 33}, control=control)
        assert result["correct"] is (not control)
        wrong = result["checks"]["wrong_crossbars"]["value"]
        assert wrong == (33 * info["calls"] if control else 0)
        assert result["checks"]["wrong_cycles_calls"]["value"] == 0
        assert set(result["metrics"]) == {"xbar_cycles_per_s", "setup_s"}


@pytest.mark.parametrize("name", ["mc192"])
def test_traffic_files_are_whole_words(name):
    mix = bench.load_traffic(name)
    assert mix["loop"] == "batch" and mix["crossbars"] % 32 == 0


@pytest.mark.parametrize("c", [c for c in BENCH["configs"]
                               if "plan" in bench.load_config(c["name"])],
                         ids=lambda c: c["name"])
def test_config_states_the_compiled_trace(c):
    """Each engine configuration's cycles and stats are those a fresh plan
    at the paper's geometry compiles to (no ``.cycles`` first)."""
    cfg = bench.load_config(c["name"])
    cp = ops.make_plan(cfg["plan"], cfg["geometry"]).compile()
    assert (cp.n_cycles, dict(cp.stats)) == (cfg["cycles"], cfg["stats"])
