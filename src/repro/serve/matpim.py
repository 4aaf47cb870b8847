"""Plan-cache serving layer: compiled-plan reuse + heterogeneous batching.

Every MatPIM caller so far hand-builds one plan per operand shape and can
only batch shape-homogeneous work. This module makes the repo behave like a
*service* (the PPAC/HIPE-MAGIC view: one accelerator multiplexing many
matvec-like workloads over a synthesis layer that reuses lowered programs):

* :class:`PlanService` caches compiled+fused plans in a bounded LRU keyed by
  ``(algorithm, bucket shape, geometry, fuse, backend)`` with hit / miss /
  eviction stats. Evicted plans also drop their executor memoizations
  (``CompiledProgram.clear_caches()``), so jitted runners are released
  instead of leaking under long-lived use.
* A stream of heterogeneous matvec / conv / binary requests is **bucketed**
  by plan key: request shapes round up to power-of-two buckets, operands are
  padded with each algorithm's identity element (zeros for full-precision,
  +1 for binary — the tiling-layer conventions), and every bucket coalesces
  onto the bit-plane batch axis of one ``execute_batch`` call. Results
  scatter back per request (popcounts re-thresholded at the true operand
  length, conv outputs cropped to the true valid region).
* Two driving modes: the synchronous ``submit_* / flush`` API runs
  everything pending, and :meth:`PlanService.run_stream` is a host-side
  continuous-batching loop mirroring ``serve/engine.py``'s slot model —
  admit requests until the in-flight unit budget is full, execute the
  fullest bucket, repeat — with per-request latency-in-cycles and wall-time
  metrics on every :class:`Ticket`.

Fault models thread through per bucket: requests carrying the same
:class:`~repro.device.faults.FaultModel` batch together (each crossbar in
the batch draws an independent realization), and per-request
:class:`~repro.device.faults.FaultRealization` masks are concatenated along
the batch axis — explicit per-instance masks make coalesced execution
bit-identical to sequential per-request execution, in any order.

>>> import numpy as np
>>> svc = PlanService(rows=64, cols=256, parts=8)
>>> A = np.ones((3, 10), dtype=int); x = np.ones(10, dtype=int)
>>> t1 = svc.submit_binary_matvec(A, x)
>>> t2 = svc.submit_binary_matvec(-A[:2, :9], np.ones(9, dtype=int))
>>> _ = svc.flush()
>>> [int(v) for v in t1.result], [int(v) for v in t2.result]
([1, 1, 1], [-1, -1])
>>> svc.stats.misses, t1.key == t2.key   # mixed shapes, one bucket plan
(1, True)
"""
from __future__ import annotations

import dataclasses
import threading
import time
import traceback
from collections import OrderedDict
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from ..core.compile import RunnerCache
from ..core.engine import parse_backend
from ..core.fused import prewarm_replay
from ..core.tiling import (TiledBinaryMatvec, TiledConv2d, TiledMatvec,
                           majority_sign)
from ..device.faults import FaultModel, FaultRealization
from ..obs import metrics as _metrics
from ..obs.trace import span as _span
from .compile_pool import CompilePool
from .plan_store import PlanStore, get_default_store


def bucket_up(v: int, floor: int = 8) -> int:
    """Round ``v`` up to the service's power-of-two shape buckets.

    Both the value and the floor must be positive — a non-positive size is
    always a caller bug (an empty operand or a misconfigured service), and
    silently bucketing it would compile a plan for a shape that can never
    be executed.

    >>> bucket_up(3), bucket_up(8), bucket_up(9), bucket_up(100)
    (8, 8, 16, 128)
    >>> bucket_up(5, floor=1)
    8
    >>> bucket_up(0)
    Traceback (most recent call last):
        ...
    ValueError: bucket_up: size must be positive, got 0
    >>> bucket_up(4, floor=-2)
    Traceback (most recent call last):
        ...
    ValueError: bucket_up: floor must be positive, got -2
    """
    v, floor = int(v), int(floor)
    if v < 1:
        raise ValueError(f"bucket_up: size must be positive, got {v}")
    if floor < 1:
        raise ValueError(f"bucket_up: floor must be positive, got {floor}")
    return max(floor, 1 << (v - 1).bit_length())


@dataclasses.dataclass
class CacheStats:
    """Plan-cache and batching counters for one :class:`PlanService`.

    The reconciliation identities the accounting tests pin down:
    ``hits + misses == requests`` (every submit resolves a plan exactly
    once), and ``compile_s + warmup_s`` is the total cold-plan cost —
    under the async admit path compile wall accrues when the job *lands*
    rather than inside the submit call, but the identity is unchanged.
    ``async_compiles`` counts misses whose compile ran on the worker pool;
    ``store_hits`` counts misses satisfied by deserializing the persistent
    plan store instead of ``compile_program`` (store_hits <= misses).
    """

    hits: int = 0
    misses: int = 0
    evictions: int = 0
    requests: int = 0
    batches: int = 0       # execute_batch calls issued
    units: int = 0         # crossbar images executed (batch sizes summed)
    compile_s: float = 0.0  # wall time spent building/compiling plans (misses)
    # wall of each plan's FIRST engine batch: backend tracing/compilation
    # (jax jit etc.) that would otherwise be mis-attributed to steady-state
    # execute. compile_s + warmup_s is the true cost of a cold plan —
    # prewarmed plans pay it on the worker pool instead of the first request,
    # but it still lands here, so the identity is unchanged.
    warmup_s: float = 0.0
    async_compiles: int = 0   # misses compiled off-path by the worker pool
    store_hits: int = 0       # misses served from the persistent plan store
    prewarms: int = 0         # plans whose executor warm-up ran off-path
    # off-path warm-ups that raised (the first batch then warms inline);
    # the traceback of the latest is PlanService.last_prewarm_error
    prewarm_errors: int = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def as_dict(self) -> Dict[str, float]:
        d = dataclasses.asdict(self)
        d["hit_rate"] = self.hit_rate
        return d


@dataclasses.dataclass
class Ticket:
    """Handle for one submitted request; filled in when its bucket runs."""

    uid: int
    kind: str
    key: tuple                      # plan-cache key the request bucketed to
    n_units: int                    # crossbar images this request contributes
    result: object = None
    cycles: Optional[int] = None    # in-array program cycles (tiles lockstep)
    reduce_depth: int = 0           # host tree-reduction levels on top
    # true per-request end-to-end latency: submit -> decode+finalize done.
    # Includes queueing, so SLO percentiles over wall_s are honest; the
    # shared engine-batch wall lives in batch_wall_s.
    wall_s: Optional[float] = None
    batch_wall_s: Optional[float] = None  # wall of the engine batch serving it
    batch_units: Optional[int] = None  # crossbars coalesced in that batch
    queue_steps: int = 0            # serve-loop steps spent waiting
    submitted_s: Optional[float] = None  # perf_counter stamp at submit
    device: int = 0                 # device slot the serving bucket ran on
    done: bool = False


@dataclasses.dataclass
class ServeRequest:
    """One element of a request stream for :meth:`PlanService.run_stream`:
    ``kind`` picks the ``submit_<kind>`` method, ``args``/``kwargs`` are its
    operands (e.g. ``ServeRequest("binary_matvec", (A, x))``)."""

    kind: str
    args: tuple
    kwargs: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class _Pending:
    ticket: Ticket
    wrapper: object                 # tiled wrapper (kept alive past eviction)
    load: Callable                  # load_tile(b, mem) from bind()
    decode: Callable                # decode_tile(b, mem) from bind()
    finalize: Callable              # partials -> request result
    faults: object = None
    submitted_step: int = 0
    running: bool = False           # claimed by an in-flight bucket execute


def _run_warm(warm: Callable[[], float], info: dict) -> None:
    """Run an off-path executor warm-up into a compile job's ``info``.

    A failed warm-up is not fatal (the plan's first batch warms inline), so
    the job keeps going, but the failure is recorded: ``info["warm_error"]``
    carries the traceback, which :meth:`PlanService._collect_landed` counts
    in ``CacheStats.prewarm_errors``.
    """
    try:
        info["warm_s"] = warm()
        info["prewarmed"] = True
    except Exception:
        info["warm_error"] = traceback.format_exc()


def _concat_realizations(reals: List[FaultRealization]) -> FaultRealization:
    """Stack per-request realizations along the batch axis (same trace)."""
    if len(reals) == 1:
        return reals[0]
    return FaultRealization(
        sa0=np.concatenate([r.sa0 for r in reals]),
        sa1=np.concatenate([r.sa1 for r in reals]),
        switch=np.concatenate([r.switch for r in reals]),
        init_flip=np.concatenate([r.init_flip for r in reals]))


class PlanService:
    """LRU-bounded plan cache + heterogeneous request batcher.

    One service owns one crossbar geometry ``(rows, cols, parts)``, one
    engine ``backend`` and one ``fuse`` policy; those live in every plan key
    so distinct configurations never share compiled state. ``max_plans``
    bounds the cache: the least-recently-used plan is dropped (and its
    executor caches cleared) past the bound. ``bucket=False`` disables
    shape bucketing (each exact shape gets its own plan).

    ``tiled()`` is the pipeline-facing fetch: an exact-shape, exact-kwargs
    cached constructor for the tiled wrappers, shared across stages and
    pipelines (see ``apps/pipeline.py``).
    """

    def __init__(self, max_plans: int = 32, backend: str = "numpy",
                 fuse: bool = True, rows: int = 1024, cols: int = 1024,
                 parts: int = 32, bucket: bool = True, bucket_floor: int = 8,
                 max_batch: Optional[int] = None, seed: Optional[int] = 0,
                 max_starve_steps: int = 4,
                 async_compile: bool = False, compile_workers: int = 2,
                 compile_queue: int = 8, store=None,
                 devices: Optional[int] = None,
                 prewarm: Optional[bool] = None):
        self.max_plans = int(max_plans)
        self.fuse = bool(fuse)
        # the engine's spellings plus the plan-level interpreter; an unknown
        # one fails here, not at the first bucket. Fused or unfused is the
        # engine's choice, made from each trace.
        self._base = (backend if backend == "interp"
                      else parse_backend(backend)[0])
        self.backend = backend
        self.geometry = (int(rows), int(cols), int(parts))
        self.bucket = bool(bucket)
        self.bucket_floor = int(bucket_floor)
        self.max_batch = max_batch
        self.max_starve_steps = int(max_starve_steps)
        self.stats = CacheStats()
        # the same bounded LRU the executors use for their memoization; the
        # eviction hook releases the evicted plan's jitted runners (any
        # in-flight request still holds its wrapper and rebuilds lazily)
        self._plans = RunnerCache(max_entries=self.max_plans,
                                  on_evict=self._on_plan_evict)
        self._queue: List[_Pending] = []
        self._uid = 0
        self._step = 0
        self._rng = np.random.default_rng(seed)  # FaultModel sampling stream
        # ``store``: None -> $MATPIM_PLAN_STORE default (or no store),
        # False -> explicitly store-less, a PlanStore instance is used as
        # given, anything else is a path.
        if store is None:
            self.store: Optional[PlanStore] = get_default_store()
        elif store is False:
            self.store = None
        elif isinstance(store, PlanStore):
            self.store = store
        else:
            self.store = PlanStore(store)
        # async admit path: misses enqueue compile jobs on a bounded worker
        # pool while the stream loop keeps draining warm buckets; the pool
        # is lazy (first async miss) so sync services never spawn threads
        self.async_compile = bool(async_compile)
        self._compile_workers = int(compile_workers)
        self._compile_queue = int(compile_queue)
        self._pool: Optional[CompilePool] = None
        # plan key -> (CompileJob, wrapper) for in-flight async compiles;
        # buckets whose key is here are parked until the job lands
        self._compiling: Dict[tuple, tuple] = {}
        # off-path executor warm-up (ROADMAP: the ~1.1 s jitted-runner build
        # dominates restart cost). Default: on whenever plans can arrive
        # already-compiled (async pool or persistent store) — exactly the
        # paths where the first request would otherwise pay the warm-up.
        self.prewarm = ((self.store is not None or async_compile)
                        if prewarm is None else bool(prewarm))
        # multi-device bucket dispatch: up to ``devices`` independent ready
        # buckets execute concurrently, each pinned to a local jax device
        # slot (numpy buckets still overlap through GIL-released kernels).
        # devices=1 (default) keeps the serial loop.
        self.devices = max(1, int(devices)) if devices else 1
        self._exec_pool = None          # lazy ThreadPoolExecutor (devices>1)
        # coarse re-entrant lock over cache/queue/stats state: submit_* and
        # the execute loops are safe to call from multiple threads. Workers
        # never take it (job closures touch only wrapper + store), so
        # holding it while waiting on a job cannot deadlock.
        self._lock = threading.RLock()
        self.last_prewarm_error: Optional[str] = None

    def close(self) -> None:
        """Shut down the compile pool; in-flight jobs finish first."""
        if self._pool is not None:
            self._pool.shutdown()
            self._pool = None
        if self._exec_pool is not None:
            self._exec_pool.shutdown(wait=True)
            self._exec_pool = None

    # -- plan cache ----------------------------------------------------------

    def _on_plan_evict(self, wrapper) -> None:
        wrapper.plan.clear_caches()
        self.stats.evictions += 1
        _metrics.counter("serve.cache.evictions").inc()

    def _get_plan(self, key: tuple, factory: Callable):
        with self._lock:
            w = self._plans.get(key)       # LRU touch on hit
            if w is not None:
                self.stats.hits += 1
                _metrics.counter("serve.cache.hits").inc()
                return w
            self.stats.misses += 1
            _metrics.counter("serve.cache.misses").inc()
            t0 = time.perf_counter()
            with _span("serve.plan_build", key=repr(key)):
                w = factory()
                # compile here (store load else lowering) unless the async
                # path accepted the job — then the cost accrues at land time
                if w.plan.program is not None \
                        and not self._compile_async(key, w):
                    self._compile_sync(key, w)
            dt = time.perf_counter() - t0
            self.stats.compile_s += dt
            _metrics.counter("serve.compile_s").inc(dt)
            self._plans[key] = w           # may evict -> _on_plan_evict
            return w

    # -- persistent store + async compilation --------------------------------

    def _load_from_store(self, key: tuple, plan) -> bool:
        """Adopt a deserialized trace for ``key`` if the store has one."""
        if self.store is None:
            return False
        cp = self.store.load(key)
        if cp is None:
            return False
        try:
            plan.adopt_compiled(cp)
        except Exception:
            return False        # geometry drift etc. -> recompile below
        return True

    def _compile_sync(self, key: tuple, w) -> None:
        """Miss path on the caller's thread: store load, else lower+put."""
        if self._load_from_store(key, w.plan):
            self.stats.store_hits += 1
            # the trace arrived pre-compiled, but the executor artifacts
            # (replay plan / jitted runners) did not: warm them on the pool
            # so the first request doesn't pay the ~1.1 s restart tax
            self._prewarm_async(key, w)
            return
        cp = w.plan.compile(fuse=self.fuse)
        if self.store is not None and not self.store.entry_path(key).exists():
            self.store.put(key, cp)

    def _warm_executors(self, cp) -> float:
        """Build ``cp``'s heavy executor artifacts (numpy replay plan, jax
        jitted runners) ahead of the first request; returns the wall spent.

        Runs on a compile-pool worker: touches only ``cp._caches`` (and the
        jax compilation cache), never service state.
        """
        t0 = time.perf_counter()
        if self._base == "numpy":
            prewarm_replay(cp)
        elif self._base == "jax":
            from ..core.engine import execute, have_jax, word_widths
            if have_jax():
                # one dummy per width a word can ship (a partial word pads
                # to a power of two) jits THE canonical runner's every
                # executable, so no bucket compiles inline; each run is a
                # few ms on top
                for width in word_widths(self.max_batch):
                    dummy = np.zeros((width, cp.rows, cp.cols), np.uint8)
                    execute(cp, dummy, backend=self.backend,
                            max_batch=self.max_batch)
        return time.perf_counter() - t0

    def _prewarm_async(self, key: tuple, w) -> bool:
        """Queue an off-path executor warm-up for an already-compiled plan.

        Parks ``key`` exactly like an async compile, so the plan's buckets
        wait for the (cheap) warm instead of re-paying it inline; the
        standard :meth:`_collect_landed` machinery accounts the warm wall in
        ``CacheStats.warmup_s`` and marks the plan served-once. Backpressure
        (full pool queue) just skips the warm-up — the first batch then pays
        it, which is today's behavior.
        """
        if not self.prewarm or key in self._compiling:
            return False
        if self._pool is None:
            self._pool = CompilePool(workers=self._compile_workers,
                                     max_queue=self._compile_queue)
        plan, fuse, warm = w.plan, self.fuse, self._warm_executors

        def job():
            info = {"store_hit": False, "warm_s": 0.0, "prewarmed": False}
            _run_warm(lambda: warm(plan.compile(fuse=fuse)), info)
            return info

        job_h = self._pool.submit(key, job, block=False)
        if job_h is None:
            return False
        self._compiling[key] = (job_h, w)
        return True

    def _compile_async(self, key: tuple, w) -> bool:
        """Try to move the miss's compile onto the worker pool.

        Falls back to sync (returns False) when async is off, when there is
        nothing pending to overlap with (an idle service gains nothing from
        the handoff — single-request latency must not regress), or when the
        bounded queue is full (backpressure degrades to inline compiles).
        """
        if not self.async_compile \
                or not (self._queue or self._compiling):
            return False
        if self._pool is None:
            self._pool = CompilePool(workers=self._compile_workers,
                                     max_queue=self._compile_queue)
        store, fuse, plan = self.store, self.fuse, w.plan
        warm = self._warm_executors if self.prewarm else None

        def job():
            info = {"store_hit": False, "warm_s": 0.0, "prewarmed": False}
            if store is not None:
                cp = store.load(key)
                if cp is not None:
                    try:
                        plan.adopt_compiled(cp)
                        info["store_hit"] = True
                    except Exception:
                        cp = None
            if not info["store_hit"]:
                cp = plan.compile(fuse=fuse)
                if store is not None \
                        and not store.entry_path(key).exists():
                    store.put(key, cp)
            if warm is not None:
                # build the executor artifacts (replay plan / jitted
                # runners) off-path too, so the plan's first real batch runs
                # at steady-state speed; warm failure is non-fatal — the
                # first batch self-heals — unlike a compile failure above
                _run_warm(lambda: warm(cp), info)
            return info

        job_h = self._pool.submit(key, job, block=False)
        if job_h is None:
            return False            # queue full -> compile inline
        self._compiling[key] = (job_h, w)
        self.stats.async_compiles += 1
        _metrics.counter("serve.async_compiles").inc()
        return True

    def _collect_landed(self, wait: bool = False,
                        timeout: Optional[float] = None) -> int:
        """Integrate finished compile jobs; their buckets become ready.

        ``wait=True`` blocks (outside the service lock) until at least one
        in-flight job signals, bounding the stream loop's idle spin when
        every pending bucket is parked behind a compile.
        """
        with self._lock:
            jobs = sorted(self._compiling.items(),
                          key=lambda kv: kv[1][0].submitted_s)
        if not jobs:
            return 0
        if wait and not any(j.done.is_set() for _, (j, _) in jobs):
            jobs[0][1][0].wait(timeout)
        landed = 0
        for key, (job, w) in jobs:
            if not job.done.is_set():
                continue
            with self._lock:
                if self._compiling.pop(key, None) is None:
                    continue        # another thread integrated it
                if job.error is not None:
                    # the bucket un-parks; execute_batch will compile
                    # synchronously as a self-healing fallback
                    raise job.error
                info = job.result or {}
                if info.get("warm_error"):
                    self.stats.prewarm_errors += 1
                    self.last_prewarm_error = info["warm_error"]
                dt = job.wall_s - info.get("warm_s", 0.0)
                self.stats.compile_s += dt
                _metrics.counter("serve.compile_s").inc(dt)
                if info.get("store_hit"):
                    self.stats.store_hits += 1
                if info.get("prewarmed"):
                    # executor warm-up already paid on the worker: account
                    # it as warm-up and let the first batch count as steady
                    w._served_once = True
                    self.stats.warmup_s += info["warm_s"]
                    self.stats.prewarms += 1
                    _metrics.counter("serve.warmup_s").inc(info["warm_s"])
            _metrics.histogram("serve.compile_wait_us").observe(
                (job.finished_s - job.submitted_s) * 1e6)
            landed += 1
        return landed

    def tiled(self, kind: str, *args, key_extra=None, **kw):
        """Cached tiled-wrapper fetch (exact shapes, no bucketing).

        ``kind`` is ``"matvec"`` / ``"binary_matvec"`` / ``"conv"``; ``args``
        and ``kw`` go to the wrapper constructor and form the cache key
        together with ``key_extra`` (pipeline conv stages pass their kernel
        bytes: a stage binds one kernel for its lifetime, and keying on it
        is always safe — kernel-*dependent* programs, binary or
        stream-kernel, must never share a wrapper across kernels). The
        service's own geometry supplies the ``rows`` / ``cols`` / ``parts``
        defaults (callers may override per fetch), so the resolved geometry
        is always part of the key.
        """
        factories = {"matvec": TiledMatvec, "binary_matvec": TiledBinaryMatvec,
                     "conv": TiledConv2d}
        for name, v in zip(("rows", "cols", "parts"), self.geometry):
            kw.setdefault(name, v)
        key = ("tiled", kind, args, key_extra, tuple(sorted(kw.items())),
               self.fuse, self.backend)
        return self._get_plan(key, lambda: factories[kind](*args, **kw))

    def cached_keys(self) -> List[tuple]:
        """Current cache keys, least-recently-used first."""
        return list(self._plans.keys())

    # -- request submission --------------------------------------------------

    def _bucket2(self, m: int, k: int) -> Tuple[int, int]:
        if not self.bucket:
            return int(m), int(k)
        return (bucket_up(m, self.bucket_floor),
                bucket_up(k, self.bucket_floor))

    def _ticket(self, kind: str, key: tuple, n_units: int) -> Ticket:
        with self._lock:
            self._uid += 1
            self.stats.requests += 1
            uid = self._uid
        _metrics.counter("serve.requests").inc()
        return Ticket(uid=uid, kind=kind, key=key, n_units=n_units,
                      submitted_s=time.perf_counter())

    def _enqueue(self, ticket, wrapper, load, decode, finalize, faults):
        if isinstance(faults, FaultRealization) \
                and faults.batch != ticket.n_units:
            raise ValueError(
                f"FaultRealization batch {faults.batch} != the request's "
                f"{ticket.n_units} crossbar units; sample it per request "
                f"(n_cycles/W/I of wrapper.plan.compile())")
        with self._lock:
            self._queue.append(_Pending(
                ticket=ticket, wrapper=wrapper, load=load, decode=decode,
                finalize=finalize, faults=faults,
                submitted_step=self._step))
        return ticket

    def submit(self, kind: str, *args, **kw) -> Ticket:
        """Dispatch to ``submit_<kind>`` (the :class:`ServeRequest` path)."""
        return getattr(self, f"submit_{kind}")(*args, **kw)

    def submit_binary_matvec(self, A: np.ndarray, x: np.ndarray,
                             faults=None) -> Ticket:
        """±1 matvec ``y = sign(A @ x)``; result is the (m,) sign vector."""
        A = np.asarray(A)
        x = np.asarray(x)
        m, k = A.shape
        assert x.shape == (k,)
        Mb, Kb = self._bucket2(m, k)
        rows, cols, parts = self.geometry
        key = ("binary_matvec", (Mb, Kb), self.geometry, self.fuse,
               self.backend)
        w = self._get_plan(key, lambda: TiledBinaryMatvec(
            Mb, Kb, rows=rows, cols=cols, parts=parts))
        # bucket padding with the binary identity: +1 rows/cols each add one
        # XNOR match per row, subtracted before the host-side sign below
        Ap = np.ones((Mb, Kb), dtype=np.int64)
        Ap[:m, :k] = A
        xp = np.ones(Kb, dtype=np.int64)
        xp[:k] = x
        load, decode, fin = w.bind(Ap, xp)
        pad_k = Kb - k

        def finalize(partials):
            pop, depth = fin(partials)      # bucket-length popcounts
            return majority_sign(pop[:m] - pad_k, k), depth

        return self._enqueue(self._ticket("binary_matvec", key, w.n_tiles),
                             w, load, decode, finalize, faults)

    def submit_matvec(self, A: np.ndarray, x: np.ndarray, N: int,
                      faults=None) -> Ticket:
        """Full-precision ``y = A @ x mod 2^(2N)`` (N-bit operands)."""
        A = np.asarray(A)
        x = np.asarray(x)
        m, k = A.shape
        assert x.shape == (k,)
        Mb, Kb = self._bucket2(m, k)
        rows, cols, parts = self.geometry
        key = ("matvec", (Mb, Kb), int(N), self.geometry, self.fuse,
               self.backend)
        w = self._get_plan(key, lambda: TiledMatvec(
            Mb, Kb, N, rows=rows, cols=cols, parts=parts))
        Ap = np.zeros((Mb, Kb), dtype=np.int64)   # zero-pad: adds 0 mod 2^2N
        Ap[:m, :k] = A
        xp = np.zeros(Kb, dtype=np.int64)
        xp[:k] = x
        load, decode, fin = w.bind(Ap, xp)

        def finalize(partials):
            y, depth = fin(partials)
            return y[:m], depth

        return self._enqueue(self._ticket("matvec", key, w.n_tiles),
                             w, load, decode, finalize, faults)

    def _submit_conv(self, kind: str, img: np.ndarray, K: np.ndarray,
                     N: int, binary: bool, faults) -> Ticket:
        img = np.asarray(img)
        K = np.asarray(K, dtype=np.int64)
        H, Wd = img.shape
        k = K.shape[0]
        assert K.shape == (k, k)
        assert H >= k and Wd >= k, "image smaller than the kernel"
        Hb, Wb = self._bucket2(H, Wd)
        Hb, Wb = max(Hb, k), max(Wb, k)
        rows, cols, parts = self.geometry
        tile_kw = {"tile_n": 64} if binary else {}  # cf. tiled_binary_conv2d
        # the kernel joins the cache key only when the lowered program
        # actually depends on it (binary taps are baked into gates; the
        # full-precision plan specializes only in the stream-kernel
        # fallback). Kernel-independent plans serve EVERY kernel of the
        # shape: requests with distinct kernels share one compiled plan and
        # coalesce into one batch (each tile loads its own kernel as data).
        # The probe constructor is cheap — programs build lazily below.
        probe = TiledConv2d(Hb, Wb, k, N, binary=binary, rows=rows,
                            cols=cols, parts=parts, **tile_kw)
        kernel_dep = (binary or probe.plan.specialize
                      or probe.plan.stream_kernel)
        key = (kind, (Hb, Wb), k, int(N),
               K.tobytes() if kernel_dep else None, self.geometry,
               self.fuse, self.backend)

        def factory():
            probe.plan.ensure_program(K)   # program build lands in compile_s
            return probe

        w = self._get_plan(key, factory)
        # pad bottom/right with the operand identity (+1 binary, 0 full-
        # precision); the true valid region [0:H-k+1, 0:W-k+1] only reads
        # real pixels, so cropping it back is exact
        pad_val = 1 if binary else 0
        imgp = np.full((Hb, Wb), pad_val, dtype=np.int64)
        imgp[:H, :Wd] = img
        load, decode, fin = w.bind(imgp, K)
        oh, ow = H - k + 1, Wd - k + 1

        def finalize(tiles):
            out, depth = fin(tiles)
            return out[:oh, :ow], depth

        return self._enqueue(self._ticket(kind, key, w.n_tiles),
                             w, load, decode, finalize, faults)

    def submit_conv(self, img: np.ndarray, K: np.ndarray, N: int,
                    faults=None) -> Ticket:
        """Full-precision valid 2D correlation mod 2^N (negative taps ride
        two's-complement encoding; decode with ``apps.pipeline
        .decode_signed``). Result is the (H-k+1, W-k+1) raw map."""
        return self._submit_conv("conv", img, K, N, binary=False,
                                 faults=faults)

    def submit_binary_conv(self, img: np.ndarray, K: np.ndarray,
                           faults=None) -> Ticket:
        """±1-kernel binary conv (§III-C); result is the ±1 sign map."""
        assert set(np.unique(np.asarray(K))) <= {-1, 1}
        return self._submit_conv("binary_conv", img, K, N=1, binary=True,
                                 faults=faults)

    # -- execution -----------------------------------------------------------

    @property
    def pending_units(self) -> int:
        return sum(p.ticket.n_units for p in self._queue)

    @property
    def ready_units(self) -> int:
        """Pending units whose plan is compiled (not parked behind an
        in-flight async compile) — what the admission budget counts."""
        comp = self._compiling
        if not comp:
            return self.pending_units
        return sum(p.ticket.n_units for p in self._queue
                   if p.ticket.key not in comp)

    @staticmethod
    def _exec_key(p: _Pending) -> tuple:
        # requests coalesce only when they share the plan AND a compatible
        # fault specification: same FaultModel instances batch together
        # (independent per-crossbar draws), explicit realizations batch
        # with each other (masks concatenate), ideal runs with ideal
        if p.faults is None:
            f = ("ideal",)
        elif isinstance(p.faults, FaultRealization):
            f = ("realization",)
        else:
            f = ("model", p.faults)
        return (p.ticket.key, f)

    def _buckets(self, ready_only: bool = True) \
            -> "OrderedDict[tuple, List[_Pending]]":
        """Pending requests grouped by exec key; ``ready_only`` skips
        buckets parked behind an in-flight async compile. Requests already
        claimed by an in-flight bucket execute are never regrouped."""
        comp = self._compiling
        out: "OrderedDict[tuple, List[_Pending]]" = OrderedDict()
        for p in self._queue:
            if p.running:
                continue
            if ready_only and comp and p.ticket.key in comp:
                continue
            out.setdefault(self._exec_key(p), []).append(p)
        return out

    def _device_ctx(self, slot: int):
        """Pin a bucket's engine work to local jax device ``slot``.

        A no-op for single-device services, numpy-family backends (nothing
        to place — threads overlap through GIL-released kernels), or hosts
        without jax; jax buckets on different slots then compile + execute
        on distinct devices, so concurrent buckets don't serialize behind
        one device queue.
        """
        import contextlib
        if self.devices <= 1 or self._base != "jax":
            return contextlib.nullcontext()
        from ..core.engine import have_jax
        if not have_jax():
            return contextlib.nullcontext()
        import jax
        devs = jax.devices()
        return jax.default_device(devs[slot % len(devs)])

    def _run_bucket(self, pends: List[_Pending], slot: int = 0
                    ) -> List[Ticket]:
        """Coalesce one bucket onto the engine batch axis and scatter back.

        Thread-safe: load/execute run without the service lock (this is the
        part :meth:`_run_buckets` overlaps across device slots); the
        warm-up claim and the decode/scatter bookkeeping take it.
        """
        w = pends[0].wrapper
        plan = w.plan
        units = sum(p.ticket.n_units for p in pends)
        try:
            with _span("serve.bucket", kind=pends[0].ticket.kind,
                       units=units, requests=len(pends), device=slot):
                with _span("serve.load", units=units):
                    mems = np.zeros((units, plan.rows, plan.cols),
                                    dtype=np.uint8)
                    off = 0
                    for p in pends:
                        for b in range(p.ticket.n_units):
                            p.load(b, mems[off + b])
                        off += p.ticket.n_units
                faults = rng = None
                if pends[0].faults is not None:
                    if isinstance(pends[0].faults, FaultRealization):
                        faults = _concat_realizations(
                            [p.faults for p in pends])
                    else:
                        faults, rng = pends[0].faults, self._rng
                with self._lock:
                    # claim the warm-up before executing so two concurrent
                    # buckets on one plan can't both book it
                    warm_up = not getattr(w, "_served_once", False)
                    w._served_once = True
                t0 = time.perf_counter()
                with self._device_ctx(slot):
                    res = plan.execute_batch(
                        mems, backend=self.backend, max_batch=self.max_batch,
                        faults=faults, rng=rng)
                wall = time.perf_counter() - t0
                _metrics.counter(f"serve.device.{slot}.batches").inc()
                _metrics.histogram(f"serve.device.{slot}.busy_us") \
                    .observe(wall * 1e6)
                done = []
                with _span("serve.decode", units=units), self._lock:
                    if warm_up:
                        # first engine batch through this plan pays backend
                        # tracing / jit compilation: account it as warm-up,
                        # not steady state
                        self.stats.warmup_s += wall
                        _metrics.counter("serve.warmup_s").inc(wall)
                    off = 0
                    for p in pends:
                        partials = [p.decode(b, res.mem[off + b])
                                    for b in range(p.ticket.n_units)]
                        off += p.ticket.n_units
                        t = p.ticket
                        t.result, t.reduce_depth = p.finalize(partials)
                        t.cycles = res.cycles
                        t.batch_wall_s = wall
                        t.wall_s = (time.perf_counter() - t.submitted_s
                                    if t.submitted_s is not None else wall)
                        t.batch_units = units
                        t.device = slot
                        # steps the request sat queued before the serving one
                        t.queue_steps = max(
                            0, self._step - p.submitted_step - 1)
                        t.done = True
                        _metrics.histogram("serve.request_latency_us") \
                            .observe(t.wall_s * 1e6)
                        _metrics.histogram("serve.queue_steps") \
                            .observe(t.queue_steps)
                        done.append(t)
                        self._queue.remove(p)
        finally:
            for p in pends:     # release claims (no-op for scattered ones)
                p.running = False
        with self._lock:
            self.stats.batches += 1
            self.stats.units += units
        _metrics.counter("serve.batches").inc()
        _metrics.counter("serve.units").inc(units)
        _metrics.histogram("serve.batch_units").observe(units)
        return done

    def _run_buckets(self, ready: List[List[_Pending]]) -> List[Ticket]:
        """Execute independent ready buckets, overlapping across device
        slots when ``devices > 1``.

        ``FaultModel`` buckets stay serial — they draw from the service's
        single RNG stream, and overlapping them would make sampling depend
        on scheduling. Everything else dispatches onto a bounded thread
        pool, one bucket per device slot.
        """
        if self.devices <= 1 or len(ready) <= 1:
            done = []
            for ps in ready:
                done.extend(self._run_bucket(ps))
            return done
        par, ser = [], []
        for ps in ready:
            (ser if isinstance(ps[0].faults, FaultModel)
             else par).append(ps)
        done: List[Ticket] = []
        if len(par) > 1:
            if self._exec_pool is None:
                from concurrent.futures import ThreadPoolExecutor
                self._exec_pool = ThreadPoolExecutor(
                    max_workers=self.devices,
                    thread_name_prefix="serve-device")
            futs = [self._exec_pool.submit(self._run_bucket, ps,
                                           i % self.devices)
                    for i, ps in enumerate(par)]
            for f in futs:
                done.extend(f.result())
        else:
            for ps in par:
                done.extend(self._run_bucket(ps))
        for ps in ser:
            done.extend(self._run_bucket(ps))
        return done

    def _claim(self, ready: List[List[_Pending]]) -> None:
        """Mark the selected buckets in-flight (caller holds the lock), so
        a concurrent flush/step never double-executes them."""
        for ps in ready:
            for p in ps:
                p.running = True

    def flush(self) -> List[Ticket]:
        """Run every pending request, coalesced per bucket; with
        ``devices > 1`` up to that many independent ready buckets execute
        concurrently per iteration (async per-device dispatch).

        Buckets parked behind an in-flight async compile are skipped until
        their plan lands; when nothing is ready the loop blocks on the
        earliest compile job instead of spinning.
        """
        done = []
        with _span("serve.flush", pending_units=self.pending_units,
                   devices=self.devices):
            while self._queue:
                self._collect_landed()
                with self._lock:
                    buckets = self._buckets()
                    if not buckets and not self._compiling:
                        # defensive: a failed job already un-parked its
                        # bucket; execute compiles synchronously if needed
                        buckets = self._buckets(ready_only=False)
                    ready = list(buckets.values())[:self.devices]
                    if ready:
                        self._step += 1
                        self._claim(ready)
                if ready:
                    done.extend(self._run_buckets(ready))
                    continue
                if self._compiling:
                    self._collect_landed(wait=True, timeout=1.0)
                else:
                    # every pending request is claimed by another thread's
                    # in-flight bucket; yield until it scatters
                    time.sleep(0.001)
        _metrics.gauge("serve.queue_depth_units").set(0)
        return done

    def step(self, max_units: Optional[int] = None) -> List[Ticket]:
        """One serve-loop step: execute the fullest *ready* bucket (up to
        ``max_units`` crossbar images), leave the rest queued.

        Anti-starvation aging: fullest-first alone lets a sustained popular
        stream starve minority buckets forever, so a bucket whose oldest
        request has waited ``max_starve_steps`` steps is served first
        (oldest such bucket wins), bounding every request's queue delay.
        When every pending bucket is parked behind an async compile, the
        step blocks until one lands rather than returning empty-handed.
        """
        if not self._queue:
            return []
        _metrics.gauge("serve.queue_depth_units").set(self.pending_units)
        self._collect_landed()
        with self._lock:
            buckets = list(self._buckets().values())
        if not buckets:
            if self._compiling:
                self._collect_landed(wait=True, timeout=1.0)
            with self._lock:
                buckets = list(self._buckets().values())
                if not buckets and not self._compiling:
                    buckets = list(
                        self._buckets(ready_only=False).values())
            if not buckets:
                return []
        with self._lock:
            self._step += 1

            def age(ps):
                return self._step - min(p.submitted_step for p in ps)

            def units_of(ps):
                return sum(p.ticket.n_units for p in ps)

            starved = [ps for ps in buckets
                       if age(ps) > self.max_starve_steps]
            if starved:
                primary = max(starved, key=age)
            else:
                primary = max(buckets, key=units_of)
            pends = primary
            if max_units is not None:
                take, acc = [], 0
                for p in pends:
                    if take and acc + p.ticket.n_units > max_units:
                        break
                    take.append(p)
                    acc += p.ticket.n_units
                pends = take
            ready = [pends]
            if self.devices > 1:
                # fill the remaining device slots with the next-fullest
                # ready buckets so heterogeneous streams overlap
                rest = sorted((ps for ps in buckets if ps is not primary),
                              key=units_of, reverse=True)
                ready += rest[:self.devices - 1]
            self._claim(ready)
        with _span("serve.step", step=self._step,
                   pending_units=self.pending_units,
                   starved=bool(starved), buckets=len(ready)):
            done = self._run_buckets(ready)
        _metrics.gauge("serve.queue_depth_units").set(self.pending_units)
        return done

    def run_stream(self, requests: Iterable[ServeRequest], slots: int = 64,
                   max_units: Optional[int] = None) -> List[Ticket]:
        """Continuous-batching loop over a request stream.

        Mirrors the slot model of ``serve/engine.py``: admit requests until
        ``slots`` crossbar units are in flight, execute the fullest bucket
        (:meth:`step`), repeat until the stream and the queue drain. Every
        returned ticket carries its latency in cycles, its true end-to-end
        wall latency (``wall_s``: submit → decode done), the wall and size
        of the engine batch that served it (``batch_wall_s`` /
        ``batch_units``), and how many steps it queued.

        With the async admit path on, a miss parks its bucket behind a
        background compile job while the loop keeps admitting and draining
        warm buckets — the admission budget counts only *ready* units, so
        compiling buckets don't block warm traffic, with total in-flight
        work still bounded at ``2 * slots`` units.
        """
        if slots < 1:
            raise ValueError(f"slots={slots}: need at least one in-flight "
                             f"crossbar unit to admit work")
        it = iter(requests)
        exhausted = False
        tickets: List[Ticket] = []
        with _span("serve.stream", slots=slots) as sp:
            while True:
                self._collect_landed()
                with _span("serve.admit", slots=slots):
                    while (not exhausted and self.ready_units < slots
                           and self.pending_units < 2 * slots):
                        try:
                            r = next(it)
                        except StopIteration:
                            exhausted = True
                            break
                        tickets.append(
                            self.submit(r.kind, *r.args, **r.kwargs))
                if not self._queue:
                    if exhausted:
                        break
                    continue
                self.step(max_units=max_units or slots)
            sp.set(requests=len(tickets))
        return tickets


# ---------------------------------------------------------------------------
# Shared default service (the pipeline layer's plan source)
# ---------------------------------------------------------------------------

_DEFAULT: Optional[PlanService] = None


def get_default_service() -> PlanService:
    """Process-wide shared :class:`PlanService` that application pipelines
    compile through by default — stages with the same shape/geometry reuse
    one compiled plan instead of private recompiles."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = PlanService(max_plans=64)
    return _DEFAULT


def reset_default_service() -> None:
    """Drop the shared service (tests; releases all cached plans)."""
    global _DEFAULT
    if _DEFAULT is not None:
        for w in list(_DEFAULT._plans.values()):
            w.plan.clear_caches()
    _DEFAULT = None


__all__ = [
    "CacheStats", "PlanService", "ServeRequest", "Ticket", "bucket_up",
    "get_default_service", "reset_default_service",
]
