"""Async compilation pool (`repro.serve.compile_pool`) + the async admit
path of :class:`PlanService`.

What must hold: results under threaded async admission are bit-identical
to a sequential synchronous oracle; a key compiles at most once no matter
how many submitters race (single-flight); the queue is bounded and
rejects instead of blocking (callers fall back to inline compiles); and a
process SIGKILLed mid-store-write leaves the shared store loadable — the
survivor sees either the previous complete entry or a clean miss, never a
torn read.
"""
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro.serve.compile_pool import CompilePool
from repro.serve.matpim import PlanService
from repro.serve.plan_store import PlanStore, store_key

GEOM = dict(rows=64, cols=256, parts=8)
SRC = str(Path(__file__).resolve().parent.parent / "src")


def _mixed_requests(rng, n):
    reqs = []
    for i in range(n):
        m, k = int(rng.integers(2, 10)), int(rng.integers(4, 20))
        if i % 2:
            reqs.append(("matvec", (rng.integers(0, 16, size=(m, k)),
                                    rng.integers(0, 16, size=k), 4)))
        else:
            reqs.append(("binary_matvec", (rng.choice([-1, 1], size=(m, k)),
                                           rng.choice([-1, 1], size=k))))
    return reqs


# ---------------------------------------------------------------------------
# Pool mechanics: single-flight, bounded queue, drain/shutdown
# ---------------------------------------------------------------------------


def _spin_until(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            raise TimeoutError("condition not reached")
        time.sleep(0.005)


def test_pool_runs_jobs_and_reports_timing():
    pool = CompilePool(workers=2, max_queue=8)
    try:
        jobs = [pool.submit(f"k{i}", lambda i=i: i * i) for i in range(6)]
        assert all(j is not None for j in jobs)
        for i, j in enumerate(jobs):
            assert j.wait(5.0), "worker never finished the job"
            assert j.error is None and j.result == i * i
            assert j.wall_s is not None and j.wall_s >= 0
    finally:
        pool.shutdown()


def test_pool_single_flight_same_key_returns_same_job():
    pool = CompilePool(workers=1, max_queue=4)
    gate = threading.Event()
    ran = []
    try:
        j1 = pool.submit("key", lambda: (gate.wait(10), ran.append(1), 42)[-1])
        # while in flight, every resubmission of the key joins the same job
        dupes = [pool.submit("key", lambda: 99) for _ in range(8)]
        assert all(d is j1 for d in dupes)
        assert pool.inflight == 1
        gate.set()
        assert j1.wait(5.0) and j1.result == 42
        assert ran == [1], "duplicate submission ran the compile twice"
        # after landing, the key is free again: a new submit is a NEW job
        _spin_until(lambda: pool.inflight == 0)
        j2 = pool.submit("key", lambda: 7)
        assert j2 is not j1 and j2.wait(5.0) and j2.result == 7
    finally:
        gate.set()
        pool.shutdown()


def test_pool_bounded_queue_rejects_when_full():
    pool = CompilePool(workers=1, max_queue=2)
    gate = threading.Event()
    try:
        blocker = pool.submit("blocker", lambda: gate.wait(30))
        assert blocker is not None
        _spin_until(lambda: pool.queue_depth == 0)   # worker holds it
        fill = [pool.submit(f"fill{i}", lambda i=i: i) for i in range(2)]
        assert all(j is not None for j in fill)
        assert pool.queue_depth == 2
        # queue full -> non-blocking reject, never a deadlock
        assert pool.submit("overflow", lambda: None) is None
        assert "overflow" not in [j.key for j in fill]
        gate.set()
        assert pool.drain(10.0)
        # capacity freed: submissions flow again
        late = pool.submit("late", lambda: "ok")
        assert late is not None and late.wait(5.0) and late.result == "ok"
    finally:
        gate.set()
        pool.shutdown()


def test_pool_job_error_is_captured_not_raised_in_worker():
    pool = CompilePool(workers=1, max_queue=4)
    try:
        job = pool.submit("boom", lambda: (_ for _ in ()).throw(
            RuntimeError("compile exploded")))
        assert job.wait(5.0)
        assert isinstance(job.error, RuntimeError)
        # pool survives the failure and keeps serving
        ok = pool.submit("next", lambda: 1)
        assert ok.wait(5.0) and ok.result == 1
    finally:
        pool.shutdown()


# ---------------------------------------------------------------------------
# Service-level: threaded async admission vs sequential oracle
# ---------------------------------------------------------------------------


def test_threaded_async_bit_identical_to_sequential_oracle():
    rng = np.random.default_rng(0)
    reqs = _mixed_requests(rng, 16)

    oracle = PlanService(**GEOM)
    expected = []
    for kind, args in reqs:
        t = oracle.submit(kind, *args)
        oracle.flush()
        expected.append(np.asarray(t.result))

    svc = PlanService(**GEOM, async_compile=True, compile_workers=2)
    try:
        tickets = [None] * len(reqs)
        errors = []

        def submitter(lane):
            try:
                for i in range(lane, len(reqs), 4):
                    kind, args = reqs[i]
                    tickets[i] = svc.submit(kind, *args)
            except Exception as e:  # pragma: no cover - diagnostic
                errors.append(e)

        threads = [threading.Thread(target=submitter, args=(lane,))
                   for lane in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(30.0)
        assert not errors and all(t is not None for t in tickets)
        svc.flush()

        for i, t in enumerate(tickets):
            assert t.done, f"ticket {i} never executed"
            np.testing.assert_array_equal(np.asarray(t.result), expected[i])

        s = svc.stats
        assert s.requests == len(reqs)
        assert s.hits + s.misses == s.requests
        assert s.async_compiles <= s.misses
        assert svc.stats.store_hits == 0      # no store wired in
    finally:
        svc.close()


def test_service_single_flight_one_compile_per_key(monkeypatch):
    import repro.core.plan as plan_mod
    calls = []
    real = plan_mod.compile_program

    def counting(*args, **kwargs):
        calls.append(threading.get_ident())
        return real(*args, **kwargs)

    monkeypatch.setattr(plan_mod, "compile_program", counting)
    svc = PlanService(**GEOM, async_compile=True)
    try:
        rng = np.random.default_rng(2)
        A = rng.choice([-1, 1], size=(5, 9))
        tickets = []

        def submitter():
            x = rng.choice([-1, 1], size=9)
            tickets.append(svc.submit("binary_matvec", A, x))

        threads = [threading.Thread(target=submitter) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(30.0)
        svc.flush()
        assert len(tickets) == 8 and all(t.done for t in tickets)
        # one plan key -> exactly one compile despite 8 racing submitters
        assert svc.stats.misses == 1 and svc.stats.hits == 7
        assert len(calls) == 1
    finally:
        svc.close()


def test_async_queue_overflow_falls_back_to_inline_compile():
    # queue of 1 with a heterogeneous burst: some compiles must be rejected
    # by the bounded queue and run inline — but every request still lands
    rng = np.random.default_rng(4)
    reqs = _mixed_requests(rng, 12)
    svc = PlanService(**GEOM, async_compile=True, compile_workers=1,
                      compile_queue=1)
    try:
        tickets = [svc.submit(kind, *args) for kind, args in reqs]
        svc.flush()
        assert all(t.done for t in tickets)
        s = svc.stats
        assert s.hits + s.misses == s.requests == len(reqs)
        # the bounded queue means async_compiles is a *subset* of misses
        assert 0 <= s.async_compiles <= s.misses
    finally:
        svc.close()


def test_async_failed_compile_surfaces_and_service_recovers(monkeypatch):
    import repro.core.plan as plan_mod
    real = plan_mod.compile_program
    calls = {"n": 0}

    def explode_second(*args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("injected compile failure")
        return real(*args, **kwargs)

    monkeypatch.setattr(plan_mod, "compile_program", explode_second)
    svc = PlanService(**GEOM, async_compile=True)
    try:
        rng = np.random.default_rng(6)
        # first submit compiles sync (idle service); with its ticket pending
        # the second DISTINCT key takes the async path — and explodes there
        svc.submit("binary_matvec", rng.choice([-1, 1], size=(3, 9)),
                   rng.choice([-1, 1], size=9))
        t2 = svc.submit("binary_matvec", rng.choice([-1, 1], size=(5, 17)),
                        rng.choice([-1, 1], size=17))
        with pytest.raises(RuntimeError, match="injected compile failure"):
            svc.flush()
        # the failed key was un-parked; the service self-heals by
        # compiling synchronously on the next flush
        svc.flush()
        assert t2.done and t2.result is not None
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# Crash safety: SIGKILL a writer mid-store-write; survivor sees no torn read
# ---------------------------------------------------------------------------

_CRASH_WRITER = textwrap.dedent("""
    import sys
    sys.path.insert(0, sys.argv[2])
    from repro.core import BinaryMatvecPlan
    from repro.serve.plan_store import PlanStore
    store = PlanStore(sys.argv[1])
    cp = BinaryMatvecPlan(8, 32, rows=64, cols=256, parts=8).compile()
    print("ready", flush=True)          # parent kills us after this line
    while True:
        store.put(("victim",), cp)
""")


def test_sigkill_mid_store_write_leaves_store_loadable(tmp_path):
    store_path = tmp_path / "store"
    proc = subprocess.Popen(
        [sys.executable, "-c", _CRASH_WRITER, str(store_path), SRC],
        stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "ready"
        time.sleep(0.2)                  # let it race through some puts
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(10.0)
    finally:
        if proc.poll() is None:  # pragma: no cover - cleanup
            proc.kill()
        proc.stdout.close()

    survivor = PlanStore(store_path)
    cp = survivor.load(("victim",))
    # atomic tmp+rename: either the last COMPLETE entry, or a clean miss —
    # never a half-written file surfacing as corruption
    assert survivor.corrupt == 0
    assert (cp is not None) == (store_key(("victim",)) in survivor.keys())
    # orphaned tmp files from the killed write are invisible to keys()
    assert all(not k.startswith(".tmp") for k in survivor.keys())
    # and the slot is immediately writable by the survivor
    from repro.core import BinaryMatvecPlan
    assert survivor.put(("victim",), BinaryMatvecPlan(8, 32, **GEOM).compile())
    assert survivor.load(("victim",)) is not None


def test_torn_write_without_rename_is_a_clean_miss(tmp_path):
    """Deterministic stand-in for the kill race: a writer that dies between
    tmp-write and rename leaves only a tmp file — the entry itself must
    read as a miss and the litter must not crash directory scans."""
    store = PlanStore(tmp_path / "store")
    (store.path / ".tmp-dead1234.npz").write_bytes(b"PK\x03\x04 torn")
    assert store.load(("never-renamed",)) is None
    assert store.misses == 1 and store.corrupt == 0
    assert store.keys() == [] and len(store) == 0
