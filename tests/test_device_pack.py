"""Pack and unpack on the device: the per-word program of the jax runners.

Every multi-word jax runner ships a word's uint8 crossbars, viewed as
uint32 (``P`` crossbars, the word's count padded to a power of two), and
one jitted program packs them into the canonical word, replays and unpacks
(``engine.device_word_program``, ``engine.replay_words``). These tests pin:

* bit identity with the numpy executor (memory, cycles, stats) for the
  fused ideal, fused ``FaultRealization`` and unfused jax runners at batch
  sizes that fill, split and pad words, and where rows pad to whole
  uint32;
* at most six compiled widths (1, 2, 4, 8, 16, 32) for one program,
  whatever the batch;
* the ``engine.device_pack.*`` counters: words and padding crossbars on
  this path, nothing on the numpy and ``FaultModel`` paths; every width
  compiled by the served prewarm, none by the traffic after it;
* the reused output memory: written again only once no array on it is
  held, from one thread or many.
"""
import time

import numpy as np
import pytest

from repro.core import BinaryMatvecPlan, have_jax
from repro.core.engine import (WORD_BITS, device_word_program, execute,
                               padded_width, replay_words, word_count,
                               word_widths)
from repro.device.faults import FaultModel, FaultRealization
from repro.obs import metrics

pytestmark = pytest.mark.skipif(not have_jax(), reason="needs jax")

BATCHES = (1, 5, 31, 32, 33, 64, 100)
RUNNERS = ("fused", "realization", "unfused")


@pytest.fixture(scope="module")
def plan_cp():
    plan = BinaryMatvecPlan(8, 16, rows=64, cols=256, parts=8)
    return plan, plan.compile()


@pytest.fixture(scope="module")
def odd_plan_cp():
    """254 columns: rows ship padded with two zero bytes to whole uint32."""
    plan = BinaryMatvecPlan(8, 16, rows=64, cols=254, parts=2)
    return plan, plan.compile()


@pytest.fixture(autouse=True)
def _fresh_metrics():
    metrics.reset_metrics()
    yield


def _mems(plan, B, seed):
    rng = np.random.default_rng(seed)
    mems = np.zeros((B, plan.rows, plan.cols), dtype=np.uint8)
    for b in range(B):
        plan.load_into(mems[b], rng.choice([-1, 1], size=(8, 16)),
                       rng.choice([-1, 1], size=16))
    return mems


def _counts():
    return (metrics.counter("engine.device_pack.words").value,
            metrics.counter("engine.device_pack.padded_crossbars").value)


def _check_equals_numpy(plan, cp, runner, B):
    mems = _mems(plan, B, seed=B)
    faults = None
    if runner == "realization":
        faults = FaultRealization.sample(
            FaultModel(p_sa0=0.01, p_sa1=0.01, p_switch=0.01, p_init=0.01),
            B, plan.rows, plan.cols, cp.n_cycles, cp.W, cp.I, rng=B)
    backend = "jax-unfused" if runner == "unfused" else "jax-fused"
    want = execute(cp, mems, backend="numpy", faults=faults)
    got = execute(cp, mems, backend=backend, faults=faults)
    np.testing.assert_array_equal(got.mem, want.mem)
    assert got.mem.dtype == np.uint8 and got.mem.flags.writeable
    assert got.cycles == want.cycles == cp.n_cycles
    assert got.stats == want.stats
    assert got.backend == backend
    tail = B - WORD_BITS * (word_count(B) - 1)
    assert _counts() == (word_count(B), padded_width(tail) - tail)


@pytest.mark.parametrize("B", BATCHES)
@pytest.mark.parametrize("runner", RUNNERS)
def test_device_pack_equals_numpy(plan_cp, runner, B):
    _check_equals_numpy(*plan_cp, runner, B)


@pytest.mark.parametrize("runner", RUNNERS)
def test_device_pack_equals_numpy_with_unaligned_rows(odd_plan_cp, runner):
    _check_equals_numpy(*odd_plan_cp, runner, 37)


def test_padded_widths_compile_at_most_six_shapes(plan_cp):
    from repro.core.fused import jax_fused_body

    plan, cp = plan_cp
    body = jax_fused_body(cp)
    traced = []

    def counted(buf):
        traced.append(buf.shape)            # once per compiled width
        return body(buf)

    run = device_word_program(counted, plan.rows, plan.cols)
    mems = _mems(plan, 100, seed=1)
    for B in list(range(1, WORD_BITS + 2)) + [64, 100]:
        got = replay_words(mems[:B], run)
        if B in (1, 17, 33, 100):
            want = execute(cp, mems[:B], backend="numpy").mem
            np.testing.assert_array_equal(got, want)
    assert len(traced) == 6
    assert all(s == (plan.cols + 1, plan.rows + 1) for s in traced)
    # only the full width compiles for whole words
    traced.clear()
    run = device_word_program(counted, plan.rows, plan.cols)
    replay_words(mems[:96], run)
    assert len(traced) == 1


@pytest.mark.parametrize("backend,faults", [
    ("numpy", None), ("numpy-unfused", None),
    ("numpy", FaultModel(p_switch=1e-3)),
    ("jax", FaultModel(p_switch=1e-3)),
], ids=["numpy-fused", "numpy-unfused", "numpy-faultmodel",
        "jax-faultmodel"])
def test_device_pack_counters_stay_zero_off_the_path(plan_cp, backend,
                                                     faults):
    plan, cp = plan_cp
    mems = _mems(plan, 40, seed=2)
    execute(cp, mems, backend=backend, faults=faults, rng=0)
    assert _counts() == (0, 0)


def test_served_prewarm_compiles_every_width_before_traffic(tmp_path):
    """A store-hit plan's off-path warm-up compiles every width a word can
    ship, so served batches of any size then compile nothing inline."""
    from repro.serve.matpim import PlanService
    from repro.serve.plan_store import PlanStore

    geom = dict(rows=64, cols=256, parts=8)
    store = PlanStore(tmp_path / "store")
    rng = np.random.default_rng(5)
    traces = metrics.counter("engine.device_pack.traces")

    def serve(svc, n):
        tickets = []
        for _ in range(n):
            A = rng.choice([-1, 1], size=(8, 16))
            x = rng.choice([-1, 1], size=16)
            tickets.append((svc.submit_binary_matvec(A, x),
                            np.where(A @ x >= 0, 1, -1)))
        svc.flush()
        for t, want in tickets:
            assert t.done
            np.testing.assert_array_equal(np.asarray(t.result), want)

    serve(PlanService(backend="jax", store=store, **geom), 1)
    svc = PlanService(backend="jax", store=store, **geom)
    before = traces.value
    serve(svc, 1)                       # store hit: the warm-up lands first
    assert svc.stats.prewarms == 1
    assert traces.value - before == len(word_widths()) == 6
    warm = traces.value
    units = svc.stats.units
    for n in (3, 11, 40, 2, 17):
        serve(svc, n)
    svc.close()
    assert svc.stats.units - units == 73
    assert traces.value == warm         # no batch compiled inline


def test_output_memory_is_reused_only_once_released(plan_cp):
    """A call writes into the previous call's output only after the caller
    has dropped every array on it; a result still held is never touched."""
    plan, cp = plan_cp
    a, b = _mems(plan, 40, seed=3), _mems(plan, 40, seed=4)
    want_a = execute(cp, a, backend="numpy").mem
    want_b = execute(cp, b, backend="numpy").mem

    def run(mems):
        return execute(cp, mems, backend="jax-fused").mem

    first = run(a)
    addr = first.ctypes.data
    del first
    held = run(b)                       # the released memory, written again
    assert held.ctypes.data == addr
    np.testing.assert_array_equal(held, want_b)
    other = run(a)                      # ``held`` is in use: fresh memory
    assert other.ctypes.data != addr
    np.testing.assert_array_equal(held, want_b)
    np.testing.assert_array_equal(other, want_a)
    view = other[3:5]                   # a view keeps its memory in use
    del other
    again = run(b)
    np.testing.assert_array_equal(view, want_a[3:5])
    np.testing.assert_array_equal(again, want_b)
    np.testing.assert_array_equal(held, want_b)


def test_output_reuse_is_safe_across_threads(plan_cp):
    """Calls from more threads than cores, each holding its result while
    the others run: no call writes into a result another still holds."""
    import sys
    import threading

    plan, cp = plan_cp
    batches = [_mems(plan, 40, seed=10 + i) for i in range(4)]
    wants = [execute(cp, m, backend="numpy").mem for m in batches]
    execute(cp, batches[0], backend="jax-fused")      # compile first
    wrong = []

    def worker(k):
        for i in range(6):
            j = (k + i) % len(batches)
            got = execute(cp, batches[j], backend="jax-fused").mem
            time.sleep(0.001)               # hold it while others run
            if not np.array_equal(got, wants[j]):
                wrong.append((k, i))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert wrong == []
