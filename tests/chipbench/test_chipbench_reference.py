"""The plain references agree with the simulator on small plans, and each
control (the reference with one stated guarantee broken) fails them."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import ops, traffic  # noqa: E402

GEOM = {"rows": 64, "cols": 256, "parts": 8}
SPECS = [
    {"op": "binary_matvec", "shape": [16, 24]},
    {"op": "binary_matvec", "shape": [100, 60]},      # tiled over arrays
    {"op": "matvec", "shape": [16, 4], "N": 8},
    {"op": "matvec", "shape": [8, 2], "N": 32,
     "geometry": {"rows": 32, "cols": 1024, "parts": 32}},
]
IDS = [f"{s['op']}-{s['shape'][0]}x{s['shape'][1]}-N{s.get('N', 1)}"
       for s in SPECS]


def rng(seed=2**35 + 3):
    return traffic.rng(seed, traffic.STREAM_OPERANDS)


@pytest.mark.parametrize("spec", SPECS, ids=IDS)
def test_reference_matches_the_served_simulator(spec):
    from repro.serve.matpim import PlanService

    svc = PlanService(backend="numpy", store=False,
                      **spec.get("geometry", GEOM))
    r = rng()
    reqs = [ops.operands(spec, r) for _ in range(3)]
    tickets = [ops.submit(svc, spec, a, b) for a, b in reqs]
    svc.flush()
    for (a, b), t in zip(reqs, tickets):
        assert t.done
        assert ops.wrong(spec, t.result, ops.reference(spec, a, b)) == 0


@pytest.mark.parametrize("spec", [SPECS[0], SPECS[2]], ids=[IDS[0], IDS[2]])
def test_engine_decode_matches_plan_decode(spec):
    plan = ops.make_plan(spec, GEOM)
    A, x = ops.operands(spec, rng(), batch=(5,))
    mems = np.zeros((5, plan.rows, plan.cols), np.uint8)
    for b in range(5):
        plan.load_into(mems[b], A[b], x[b])
    out = plan.execute_batch(mems, backend="numpy").mem
    got = ops.decode_batch(spec, plan, out)
    want = np.stack([plan.decode_y(out[b]) for b in range(5)])
    assert ops.wrong(spec, got, want) == 0
    assert ops.wrong(spec, got, ops.reference(spec, A, x)) == 0


@pytest.mark.parametrize("spec", [
    {"op": "binary_matvec", "shape": [64, 8]},
    {"op": "matvec", "shape": [64, 8], "N": 32}],
    ids=["binary-ties", "matvec-32bit"])
def test_control_fails_the_comparison(spec):
    a, b = ops.operands(spec, rng(), batch=(4,))
    want = ops.reference(spec, a, b)
    got = ops.control(spec, a, b)
    assert ops.wrong(spec, got, want) >= 1
    assert ops.wrong(spec, want, want) == 0


def test_wrong_counts_results_not_elements():
    spec = {"op": "binary_matvec", "shape": [4, 4]}
    want = np.ones((3, 4), np.int64)
    got = want.copy()
    got[1, :2] = -1
    assert ops.wrong(spec, got, want) == 1
    assert ops.wrong(spec, got[:2], want) == 3   # a missing result is wrong


def test_control_readings_on_small_cells():
    """The control systems that ``control.py`` puts in the program's place
    fail the number each cell compares, at a size a test run holds."""
    from chipbench import control

    spec = {"op": "matvec", "shape": [16, 4], "N": 32}
    cfg = {"plan": spec, "cycles": 1, "stats": {}}
    A, x = ops.operands(spec, rng(), batch=(8,))
    execute, decode = control.engine_control(cfg, A, x)
    out, cycles, stats, label = execute()
    assert (cycles, label) == (1, "control")
    assert ops.wrong(spec, decode(out), ops.reference(spec, A, x)) == 8

    svc = control.ControlService()
    spec = {"op": "binary_matvec", "shape": [64, 8]}
    reqs = [ops.operands(spec, rng(2**40 + i)) for i in range(6)]
    tickets = [ops.submit(svc, spec, a, b) for a, b in reqs]
    assert svc.pending_units == 6
    svc.step(max_units=4)
    assert svc.pending_units == 2 and svc.stats.units == 4
    svc.flush()
    assert all(t.done for t in tickets)
    assert sum(ops.wrong(spec, t.result, ops.reference(spec, a, b))
               for t, (a, b) in zip(tickets, reqs)) >= 5
