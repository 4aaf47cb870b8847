"""The Table II binary convolution as an engine cell, on the CPU at a small
size: its plain reference agrees with a hand-worked map and with the served
simulator, the fused jax path decoded by ``chipbench/plans/binary_conv.py``
agrees with the reference, the control fails the comparison, and the fused
word loop counts the cycles of each mode it replays."""
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
KERNEL = [[1, 1, -1], [-1, 1, 1], [1, -1, -1]]
SPEC = {"op": "binary_conv", "shape": [16, 32], "k": 3, "kernel": KERNEL}
CELL = "bconv-1024x256-3x3.mc192"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def rng(seed=2**37 + 19):
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
    spec = {"op": "binary_conv", "shape": [3, 4], "k": 2,
            "kernel": [[1, -1], [-1, 1]]}
    a = np.array([[1, 0, 1, 1], [0, 0, 1, 0], [1, 1, 1, 0]], np.uint8)
    # ±1 sums, not flipped: [[2, 0, -2], [0, -2, 0]]; a zero goes to +1
    want = np.packbits(np.array([[1, 1, 0], [1, 0, 1]], bool), axis=-1)
    kern = ops.operands(spec, rng())[1]
    np.testing.assert_array_equal(ops.reference(spec, a, kern), want)
    assert want.tolist() == [[0b11000000], [0b10100000]]


def test_operands_carry_the_configured_kernel():
    A, K = ops.operands(SPEC, rng(), batch=(4,))
    assert A.shape == (4, 16, 32) and A.dtype == np.uint8
    assert set(np.unique(A)) == {0, 1}
    np.testing.assert_array_equal(K, np.broadcast_to(
        (np.array(KERNEL) > 0).astype(np.uint8), (4, 3, 3)))
    with pytest.raises(ValueError, match="kernel"):
        ops.operands(dict(SPEC, kernel=[[1, 0, 1]] * 3), rng())


@pytest.mark.parametrize("spec", [SPEC, dict(SPEC, shape=[20, 30])],
                         ids=["16x32", "20x30-cropped"])
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
    assert got.dtype == np.uint8 and got.shape == (5, 14, 4)
    want = np.stack([np.packbits(plan.decode_out(out[b]) > 0, axis=-1)
                     for b in range(5)])
    np.testing.assert_array_equal(got, want)
    assert ops.wrong(SPEC, got, ops.reference(SPEC, A[:5], K[:5])) == 0


def test_jax_fused_batch_matches_the_reference(small):
    from repro.core.engine import _variant

    plan, mems, A, K = small
    cp = plan.compile()
    assert _variant(cp, "jax", None, None) == "fused"
    res = plan.execute_batch(mems, backend="jax")
    assert (res.cycles, res.stats) == (cp.n_cycles, dict(cp.stats))
    got = ops.decode_batch(SPEC, plan, res.mem)
    assert ops.wrong(SPEC, got, ops.reference(SPEC, A, K)) == 0


def test_control_fails_the_comparison():
    a, b = ops.operands(SPEC, rng(), batch=(4,))
    want = ops.reference(SPEC, a, b)
    assert ops.wrong(SPEC, ops.control(SPEC, a, b), want) == 4
    assert ops.wrong(SPEC, want, want) == 0
    # the flip is the only broken guarantee: a symmetric kernel passes
    sym = dict(SPEC, kernel=[[1, -1, 1], [-1, -1, -1], [1, -1, 1]])
    a, b = ops.operands(sym, rng(), batch=(4,))
    assert ops.wrong(sym, ops.control(sym, a, b),
                     ops.reference(sym, a, b)) == 0


def test_replay_counters_grow_by_mode_cycles_per_word(small):
    from repro.core.engine import mode_cycles
    from repro.obs import metrics

    plan, mems, _, _ = small
    cp = plan.compile()
    modes = mode_cycles(cp)
    assert sum(modes) == cp.n_cycles and all(modes)
    names = [f"engine.replay.{m}_cycles" for m in ("col", "row", "init")]
    for backend, B, words in (("jax", 33, 2), ("jax", 32, 1),
                              ("numpy", 33, 0)):
        before = [metrics.counter(n).value for n in names]
        plan.execute_batch(mems[:B], backend=backend)
        grew = [metrics.counter(n).value - v for n, v in zip(names, before)]
        assert grew == [words * n for n in modes], backend


def test_bconv_cell_correct_only_when_sound():
    """The cell's runner, at a small size on the jax path: sound, then with
    the control in the program's place."""
    import jax

    cfg = dict(bench.load_config("bconv-1024x256-3x3"), geometry=GEOM,
               plan=SPEC)
    cp = ops.make_plan(SPEC, GEOM).compile()
    cfg["cycles"], cfg["stats"] = cp.n_cycles, dict(cp.stats)
    for control in (False, True):
        result, _, info = harness.run_cell(
            BENCH, CELL, seed=2**41 + 23, seconds=0.2, trace=False,
            t0=time.perf_counter(), devs=jax.devices(), config=cfg,
            traffic={"loop": "batch", "crossbars": 33}, control=control)
        assert result["correct"] is (not control)
        wrong = result["checks"]["wrong_crossbars"]["value"]
        assert wrong == (33 * info["calls"] if control else 0)
        assert result["checks"]["wrong_cycles_calls"]["value"] == 0
        assert set(result["metrics"]) == {"xbar_cycles_per_s", "setup_s"}
