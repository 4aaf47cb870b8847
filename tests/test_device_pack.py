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
* the word loop's two-deep pipeline: word w+1 dispatched before word w is
  fetched, never more than two words on the device, and
  ``engine.word_loop.overlapped_words`` (words - 1 a call);
* the ``engine.device_pack.*`` counters: words and padding crossbars on
  this path, nothing on the numpy and ``FaultModel`` paths; every width
  compiled by the served prewarm, none by the traffic after it;
* the reused output memory: written again only once no array on it is
  held, from one thread or many;
* the unfused body's loop over same-mode runs, on run shapes that stress
  its bounds (one-cycle runs, modes alternating every cycle, row runs
  first or last, init-only, empty) and under ``mesh_exec``'s vmap, and
  its ``engine.replay.mode_runs`` counter (runs x words, nothing off it).
"""
import time

import numpy as np
import pytest

from repro.core import (BinaryMatvecPlan, Crossbar, MatvecPlan,
                        compile_program, have_jax)
from repro.core.conv import ConvPlan
from repro.core.engine import (WORD_BITS, _pack, _unpack, device_word_program,
                               execute, mode_runs, padded_width,
                               replay_words, word_count, word_widths)
from repro.core.isa import GATES, ColOp, InitOp, RowOp
from repro.device.faults import FaultModel, FaultRealization
from repro.obs import metrics

pytestmark = pytest.mark.skipif(not have_jax(), reason="needs jax")

BATCHES = (1, 5, 31, 32, 33, 64, 96, 100)
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


class _Recorded:
    """A word program's result that logs when the word loop starts its copy
    back and when it fetches it."""

    def __init__(self, res, word, log):
        self.res, self.word, self.log = res, word, log

    def copy_to_host_async(self):
        self.log.append(("copy", self.word))
        self.res.copy_to_host_async()

    def __array__(self, dtype=None, copy=None):
        self.log.append(("fetch", self.word))
        return np.asarray(self.res)


@pytest.mark.parametrize("W", [1, 2, 3, 32])
def test_word_loop_dispatches_the_next_word_before_fetching(plan_cp, W):
    """A W-word call is two words deep: each word's copy back starts as it
    is dispatched, word w+1 is dispatched before word w is fetched, never
    more than two words are unfetched or held on the device, and
    ``engine.word_loop.overlapped_words`` grows by W - 1; the bits are
    numpy's."""
    import weakref

    from repro.core.fused import jax_fused_body

    plan, cp = plan_cp
    run = device_word_program(jax_fused_body(cp), plan.rows, plan.cols)
    log, held, unfetched = [], set(), []

    def recorded(*args):
        w = sum(ev[0] == "dispatch" for ev in log)
        fetched = sum(ev[0] == "fetch" for ev in log)
        unfetched.append(w + 1 - fetched)
        log.append(("dispatch", w, len(held)))
        res = _Recorded(run(*args), w, log)
        held.add(w)
        weakref.finalize(res, held.discard, w)
        return res

    base = _mems(plan, WORD_BITS, seed=W)
    want = np.concatenate([execute(cp, base, backend="numpy").mem] * W)
    overlapped = metrics.counter("engine.word_loop.overlapped_words")
    got = replay_words(np.concatenate([base] * W), recorded)
    np.testing.assert_array_equal(got, want)
    assert overlapped.value == W - 1

    at = {ev[:2]: i for i, ev in enumerate(log)}
    assert [ev[1] for ev in log if ev[0] == "fetch"] == list(range(W))
    for w in range(W):
        assert at["dispatch", w] < at["copy", w] < at["fetch", w]
        if w + 1 < W:
            assert at["copy", w] < at["dispatch", w + 1] < at["fetch", w]
    assert max(unfetched) == min(W, 2)
    # at each dispatch the words before the last are gone from the device
    assert all(ev[2] <= 1 for ev in log if ev[0] == "dispatch")
    assert held == set()


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


# -- the unfused body's loop over same-mode runs -----------------------------

RUN_GEOM = (32, 64, 4)                 # rows, cols, partitions


def _pattern_program(pattern: str, seed: int):
    """One cycle per letter of ``pattern``: ``c`` column gates, ``r`` row
    gates (random gates and lines, one op in most partitions), ``i`` an
    init rectangle."""
    rng = np.random.default_rng(seed)
    rows, cols, parts = RUN_GEOM
    gates = list(GATES)

    def ops(op, span, lines):
        cyc = []
        for p in range(parts):
            if cyc and rng.random() < 0.25:
                continue
            g = gates[rng.integers(len(gates))]
            ar = GATES[g].arity
            at = rng.choice(span, size=ar + 1, replace=False) + p * span
            sel = [None, sorted(int(v) for v in
                                rng.choice(lines, 3, replace=False))][
                rng.integers(2)]
            cyc.append(op(g, tuple(int(v) for v in at[:ar]), int(at[ar]),
                          sel))
        return cyc

    prog = []
    for kind in pattern:
        if kind == "c":
            prog.append(ops(ColOp, cols // parts, rows))
        elif kind == "r":
            prog.append(ops(RowOp, rows // parts, cols))
        else:
            prog.append([InitOp(sorted(int(v) for v in
                                       rng.choice(rows, 5, replace=False)),
                                slice(0, cols, 3), int(rng.integers(2)))])
    return prog


def _small_plan_program(kind: str):
    g = dict(rows=32, cols=128, parts=4)
    plan = {"bmv": lambda: BinaryMatvecPlan(4, 16, **g),
            "mv": lambda: MatvecPlan(8, 2, 4, **g),
            "conv": lambda: ConvPlan(16, 4, 3, 4, **g)}[kind]()
    plan.compile()
    return plan.program, plan.rows, plan.cols, plan.parts


RUN_CASES = {
    "one-cycle-runs": "circrcic",
    "alternating": "crcrcrcrcrcr",
    "starts-with-row": "rrrcccc",
    "ends-with-row": "iccccrrr",
    "init-only": "iii",
    "init-runs": "iicccirrrii",
    "empty": "",
    "bmv": None, "mv": None, "conv": None,
}


@pytest.mark.parametrize("case", list(RUN_CASES))
def test_unfused_run_loop_equals_numpy_and_interpreter(case):
    """The unfused jax body, one loop over the trace's same-mode runs, gives
    the interpreter's and ``numpy-unfused``'s memory, cycles and stats, on
    run shapes that stress its bounds and on small real programs, through
    the word program with partial words (1, 3 and 17 crossbars)."""
    pattern = RUN_CASES[case]
    if pattern is None:
        prog, rows, cols, parts = _small_plan_program(case)
    else:
        prog = _pattern_program(pattern, seed=len(pattern))
        rows, cols, parts = RUN_GEOM
    cp = compile_program(prog, rows, cols, parts, parts)
    start, end, mode = mode_runs(cp).T
    assert np.array_equal(np.r_[0, end], np.r_[start, cp.n_cycles])
    assert (mode[1:] != mode[:-1]).all()
    if pattern is not None:
        assert len(start) == sum(1 for i, m in enumerate(pattern)
                                 if i == 0 or m != pattern[i - 1])
    rng = np.random.default_rng(len(case))
    mems = (rng.random((17, rows, cols)) < 0.5).astype(np.uint8)
    xb = Crossbar(rows, cols, parts, parts)
    for B in (1, 3, 17):
        want = execute(cp, mems[:B], backend="numpy-unfused")
        got = execute(cp, mems[:B], backend="jax-unfused")
        np.testing.assert_array_equal(got.mem, want.mem, err_msg=str(B))
        assert got.cycles == want.cycles == cp.n_cycles
        assert got.stats == want.stats
    for b in (0, 16):
        xb.mem[:, :] = mems[b]
        xb.cycles = 0
        xb.stats = {k: 0 for k in xb.stats}
        xb.run(prog)
        np.testing.assert_array_equal(got.mem[b], xb.mem)
    assert (got.cycles, got.stats) == (xb.cycles, dict(xb.stats))


def test_unfused_run_loop_under_vmapped_shard_map():
    """``mesh_exec`` vmaps the unfused body inside ``shard_map``: the run
    table's bounds stay unbatched, and every stacked word replays as the
    word program does (a one-device mesh on the CPU)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    from repro.distributed import mesh_exec

    rows, cols, parts = RUN_GEOM
    cp = compile_program(_pattern_program("iccrrcrcccri", seed=5), rows,
                         cols, parts, parts)
    rng = np.random.default_rng(9)
    mems = (rng.random((70, rows, cols)) < 0.5).astype(np.uint8)
    spec = PartitionSpec(mesh_exec.TILE_AXIS, None, None)
    fn = mesh_exec._sharded_runner(cp, mesh_exec.tile_mesh(1), "unfused",
                                   spec)
    got = _unpack(np.asarray(fn(jnp.asarray(_pack(mems)))), 70, rows, cols)
    want = execute(cp, mems, backend="numpy-unfused").mem
    np.testing.assert_array_equal(got, want)


MODE_RUN_CASES = {"unfused-2-words": ("conv", "jax-unfused", 33, 2),
                  "unfused-1-word": ("conv", "jax-unfused", 32, 1),
                  "numpy": ("conv", "numpy", 33, 0),
                  "fused": ("bmv", "jax-fused", 40, 0),
                  "realization": ("bmv", "realization", 40, 0)}


@pytest.mark.parametrize("case", list(MODE_RUN_CASES))
def test_mode_runs_counter_grows_by_runs_per_word(case):
    """``engine.replay.mode_runs`` grows by the program's same-mode runs for
    every word the unfused jax body replays, and stays put on numpy and on
    the fused runners, which have no run loop."""
    kind, backend, B, words = MODE_RUN_CASES[case]
    prog, rows, cols, parts = _small_plan_program(kind)
    cp = compile_program(prog, rows, cols, parts, parts)
    runs = len(mode_runs(cp))
    assert 3 <= runs < cp.n_cycles
    mems = np.zeros((B, rows, cols), np.uint8)
    faults = None
    if backend == "realization":
        backend = "jax-fused"
        faults = FaultRealization.sample(FaultModel(p_switch=0.01), B, rows,
                                         cols, cp.n_cycles, cp.W, cp.I, rng=1)
    counter = metrics.counter("engine.replay.mode_runs")
    col = metrics.counter("engine.replay.col_cycles")
    execute(cp, mems, backend=backend, faults=faults)
    assert counter.value == words * runs
    assert col.value > 0 or backend == "numpy"   # the word loop did replay
