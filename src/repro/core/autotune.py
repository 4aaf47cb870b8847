"""Batch-aware backend autotuner: measured lowering decisions, reused.

The fused executors win at narrow batch widths and lose to plain per-cycle
numpy replay in the wide-batch regime (BENCH_engine batch=64: fused 0.8-0.9x
vs unfused numpy) — which concrete variant is fastest is a property of the
*(program, batch width)* pair, not of the program alone. Re-deriving that
choice per request is exactly what HIPE-MAGIC's ahead-of-time synthesis view
argues against, so this module makes it a measurement that is taken once and
reused:

* :func:`program_key` — content-derived key for a compiled trace (geometry,
  cycle count, op stats, segment shape). Recompiling the same plan yields
  the same key, so tunings survive plan-cache eviction and process restarts.
* :func:`batch_bucket` — packed-word buckets ``ceil(B/32)``: under the
  canonical uint32 layout every batch with the same word count replays
  through identical executor shapes, so the word count IS the performance
  class (the old pow2 buckets keyed one entry per batch size family even
  when the execution was identical).
* :class:`TuningTable` — a small on-disk JSON table mapping
  ``(program key, batch bucket, device topology) -> (backend, max_batch,
  us)``.  The topology axis (the ``tiles``-mesh device count, 1 when
  unsharded) keeps 1-device measurements from deciding 8-device sharded
  executes; schema-1/-2 tables (pre-word-bucket) load with their buckets
  re-derived as word counts and demoted to *heuristic* entries — usable
  hints, never authoritative measurements.  Corrupt or unknown-schema
  files never fail an execute: they load as empty and the conservative
  :func:`heuristic` takes over.
* :func:`resolve_auto` — what ``engine.execute(backend="auto")`` calls:
  measured entry if present and runnable, heuristic otherwise.
* :func:`autotune_execute` — time the real candidate variants on a real
  replay (the workload itself is the probe), record the winner, and return
  its result so the probe run is not wasted. ``tools/autotune.py`` drives
  this offline; :class:`repro.serve.matpim.PlanService` drives it on the
  first occurrence of a ``(program, bucket)`` pair in a stream.

Span-chunking rides in as a candidate dimension: ``max_batch=32`` splits a
wide batch into single-canonical-word chunks (W=1 per executor call), which
trades per-call W-axis breadth for cache locality and is occasionally the
fastest shape — the tuner measures it instead of guessing.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from ..obs import metrics as _metrics
from ..obs.trace import span as _span

# v2 added the device-topology key component ("key|bucket|topo"); v3 keys
# buckets by canonical word count (ceil(B/32)) instead of pow2 batch width
SCHEMA = 3

# env var naming the on-disk tunings table; unset -> in-process table only
TUNINGS_ENV = "MATPIM_TUNINGS"

# one canonical packed word (engine.WORD_BITS crossbars): the span-chunking
# candidate splits wide batches into chunks of this many crossbars
CHUNK_BATCH = 32


def batch_bucket(B: int) -> int:
    """Packed-word bucket ``ceil(B/32)`` for a batch width (min 1).

    Batches with the same canonical word count execute through identical
    shapes on every backend, so they share one tuning entry.

    >>> batch_bucket(1), batch_bucket(32), batch_bucket(33), batch_bucket(128)
    (1, 1, 2, 4)
    """
    return max(1, -(-int(B) // 32))


def program_key(cp) -> str:
    """Content-derived tuning key for a compiled trace.

    Built only from trace invariants (geometry, cycle count, padded widths,
    op-category stats, fused segment count), so recompiling the same plan —
    after plan-cache eviction, or in another process — maps back to the same
    tunings row. Distinct programs that collide here would at worst share a
    measured preference, never produce wrong results.
    """
    seg = cp.schedule.n_segments if cp.schedule is not None else -1
    stats = ";".join(f"{k}={v}" for k, v in sorted(cp.stats.items()))
    return (f"r{cp.rows}c{cp.cols}t{cp.n_cycles}w{cp.W}i{cp.I}"
            f"s{seg}[{stats}]")


@dataclasses.dataclass
class TuningEntry:
    backend: str                    # concrete backend, e.g. "numpy-unfused"
    us: float                       # measured wall per execute (microseconds)
    max_batch: Optional[int] = None  # span-chunking width (None = word width)
    source: str = "measured"        # "measured" | "heuristic"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class TuningTable:
    """On-disk ``(program key, batch bucket, topology) -> TuningEntry`` map.

    ``topo`` is the device count the execute sharded over (1 = single
    device / no mesh), so measurements taken at one topology never resolve
    the backend for another. ``path=None`` keeps the table in-process only.
    Loading is lazy and forgiving: an unreadable / corrupt / unknown-schema
    file records a ``load_error`` and yields an empty table —
    ``backend="auto"`` then falls back to the heuristic instead of failing
    the execute. Legacy files load demoted to ``source="heuristic"``:
    schema-1 (pre-topology) entries as topo-1, and both schema-1 and -2
    with their pow2 batch buckets re-derived as canonical word buckets
    (``batch_bucket``; the fastest entry wins when several legacy buckets
    collapse onto one word count) — they may seed choices, not assert
    them. ``save()`` writes atomically (tmp + rename) and creates parent
    directories.
    """

    def __init__(self, path: Optional[os.PathLike] = None):
        self.path = Path(path) if path is not None else None
        self.load_error: Optional[str] = None
        self._entries: Optional[Dict[Tuple[str, int, int], TuningEntry]] = None

    # -- persistence ---------------------------------------------------------

    def _load(self) -> Dict[Tuple[str, int, int], TuningEntry]:
        if self._entries is not None:
            return self._entries
        self._entries = {}
        if self.path is None or not self.path.exists():
            return self._entries
        try:
            d = json.loads(self.path.read_text())
            schema = d.get("schema")
            if schema not in (1, 2, SCHEMA):
                raise ValueError(f"schema {schema} not in (1, 2, {SCHEMA})")
            for k, e in d["entries"].items():
                if schema == 1:
                    key, bucket = k.rsplit("|", 1)
                    topo, source = 1, "heuristic"  # pre-topology: demote
                else:
                    key, bucket, topo = k.rsplit("|", 2)
                    source = str(e.get("source", "measured"))
                bucket, topo = int(bucket), int(topo)
                if schema < SCHEMA:
                    # legacy pow2 batch bucket -> canonical word bucket;
                    # measured walls predate the layout, so demote
                    bucket, source = batch_bucket(bucket), "heuristic"
                entry = TuningEntry(
                    backend=str(e["backend"]), us=float(e["us"]),
                    max_batch=e.get("max_batch"), source=source)
                if entry.max_batch is not None:
                    entry.max_batch = int(entry.max_batch)
                cur = self._entries.get((key, bucket, topo))
                if cur is None or entry.us < cur.us:  # fastest survivor
                    self._entries[(key, bucket, topo)] = entry
        except Exception as exc:  # corrupt/stale table is never fatal
            self.load_error = f"{type(exc).__name__}: {exc}"
            self._entries = {}
        return self._entries

    def save(self) -> None:
        if self.path is None:
            return
        entries = {f"{k}|{b}|{t}": e.as_dict()
                   for (k, b, t), e in sorted(self._load().items())}
        payload = {"schema": SCHEMA, "generated_by": "repro.core.autotune",
                   "entries": entries}
        self.path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=self.path.parent,
                                   prefix=self.path.name, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
                f.write("\n")
            os.replace(tmp, self.path)
        finally:
            if os.path.exists(tmp):  # pragma: no cover - rename failed
                os.unlink(tmp)

    # -- queries -------------------------------------------------------------

    def lookup(self, key: str, bucket: int,
               topo: int = 1) -> Optional[TuningEntry]:
        return self._load().get((key, int(bucket), int(topo)))

    def record(self, key: str, bucket: int, backend: str, us: float,
               max_batch: Optional[int] = None,
               source: str = "measured", topo: int = 1) -> TuningEntry:
        e = TuningEntry(backend=backend, us=float(us), max_batch=max_batch,
                        source=source)
        self._load()[(key, int(bucket), int(topo))] = e
        return e

    def observe(self, key: str, bucket: int, backend: str, us: float,
                max_batch: Optional[int] = None, topo: int = 1) -> None:
        """Fold one measured wall time into the table: keep the fastest
        variant seen per (key, bucket, topo); refresh the incumbent's time."""
        cur = self.lookup(key, bucket, topo)
        same = (cur is not None and cur.backend == backend
                and cur.max_batch == max_batch)
        if cur is None or same or cur.source == "heuristic" or us < cur.us:
            self.record(key, bucket, backend, us, max_batch=max_batch,
                        topo=topo)

    def entries(self) -> Dict[Tuple[str, int, int], TuningEntry]:
        return dict(self._load())

    def __len__(self) -> int:
        return len(self._load())


_DEFAULT: Optional[TuningTable] = None
_DEFAULT_PATH: Optional[str] = None


def get_default_table() -> TuningTable:
    """Process-default table; backed by ``$MATPIM_TUNINGS`` when set (the
    path is re-checked per call so tests and the bench can redirect it),
    in-memory otherwise."""
    global _DEFAULT, _DEFAULT_PATH
    path = os.environ.get(TUNINGS_ENV) or None
    if _DEFAULT is None or path != _DEFAULT_PATH:
        _DEFAULT = TuningTable(path)
        _DEFAULT_PATH = path
    return _DEFAULT


def reset_default_table() -> None:
    """Drop the process-default table (tests)."""
    global _DEFAULT, _DEFAULT_PATH
    _DEFAULT = None
    _DEFAULT_PATH = None


# ---------------------------------------------------------------------------
# Resolution: measured entry if usable, conservative heuristic otherwise
# ---------------------------------------------------------------------------


def _runnable(backend: str) -> bool:
    from .engine import have_jax, parse_backend
    try:
        base, _ = parse_backend(backend)
    except ValueError:
        return False
    return base in ("numpy",) or (base == "jax" and have_jax())


def heuristic(cp, B: int, topo: int = 1) -> Tuple[str, Optional[int]]:
    """Cold-path choice with nothing measured: jax-fused for narrow batches
    when the trace is fuse-friendly (the PR-4 regime: 8-40x vs interp),
    per-cycle numpy once the batch exceeds one jax word (the regime where
    BENCH_engine shows fusion losing), fused numpy in between.

    ``topo > 1`` (a usable ``tiles`` mesh under the batch) prefers a jax
    variant regardless of width — only jax executes sharded, so numpy would
    silently serialize the topology it was asked to exploit."""
    from .engine import JAX_WORD_BITS, have_jax
    from .fused import jax_fuse_eligible
    if topo > 1 and have_jax():
        if cp.schedule is not None and jax_fuse_eligible(cp):
            return "jax-fused", None
        return "jax-unfused", None
    if B > JAX_WORD_BITS:
        return "numpy-unfused", None
    if have_jax() and cp.schedule is not None and jax_fuse_eligible(cp):
        return "jax-fused", None
    return ("numpy-fused" if cp.schedule is not None
            else "numpy-unfused"), None


def resolve_auto(cp, B: int, faults=None,
                 table: Optional[TuningTable] = None, topo: int = 1
                 ) -> Tuple[str, Optional[int], str]:
    """``backend="auto"`` resolution: ``(backend, max_batch, source)``.

    Fault runs skip the table entirely — the numpy paths accept every fault
    specification, and fault-injected walls should never train the table.
    ``topo`` keys the lookup by device topology, so a 1-device measurement
    never decides an 8-device sharded execute (and vice versa).
    """
    if faults is not None:
        _metrics.counter("autotune.resolve.faults").inc()
        return "numpy", None, "faults"
    table = table if table is not None else get_default_table()
    e = table.lookup(program_key(cp), batch_bucket(B), topo=topo)
    if e is not None and e.source == "measured" and _runnable(e.backend):
        _metrics.counter("autotune.resolve.measured").inc()
        return e.backend, e.max_batch, "measured"
    if e is not None and _runnable(e.backend) and topo == 1:
        # demoted schema-1 entry: a usable hint at the topology it was
        # (implicitly) measured at, still reported as heuristic
        _metrics.counter("autotune.resolve.heuristic").inc()
        return e.backend, e.max_batch, "heuristic"
    be, mb = heuristic(cp, B, topo=topo)
    _metrics.counter("autotune.resolve.heuristic").inc()
    return be, mb, "heuristic"


# ---------------------------------------------------------------------------
# Measurement: time real replays, record the winner
# ---------------------------------------------------------------------------


def candidates(cp, B: int, cheap: bool = False
               ) -> List[Tuple[str, Optional[int]]]:
    """Candidate ``(backend, max_batch)`` pairs for a batch width.

    ``cheap=True`` (the serving layer's inline tune) drops jax-unfused —
    it is never competitive on fuse-friendly traces, and its jit is one
    more artifact to build.
    """
    from .engine import have_jax
    from .fused import jax_fuse_eligible
    cand: List[Tuple[str, Optional[int]]] = [
        ("numpy-fused", None), ("numpy-unfused", None)]
    if B > CHUNK_BATCH:  # span-chunking: word-width chunks of a wide batch
        cand += [("numpy-fused", CHUNK_BATCH),
                 ("numpy-unfused", CHUNK_BATCH)]
    if have_jax():
        if cp.schedule is not None and jax_fuse_eligible(cp):
            cand.append(("jax-fused", None))
        if not cheap:
            cand.append(("jax-unfused", None))
    return cand


def autotune_execute(cp, mems, table: Optional[TuningTable] = None,
                     reps: int = 2, cheap: bool = True, save: bool = True):
    """Time every candidate on the given batch, record the fastest, return
    ``(EngineResult of the winner, TuningEntry)``.

    The probe runs ARE real executions (all candidates are bit-identical by
    the conformance contract), so the caller keeps the winner's result and
    the measurement costs ``len(candidates)-1`` extra replays, paid once per
    ``(program key, batch bucket)``.
    """
    import numpy as np

    from .engine import execute

    mems = np.asarray(mems)
    B = mems.shape[0] if mems.ndim == 3 else 1
    table = table if table is not None else get_default_table()
    best = None
    with _span("autotune.tune", key=program_key(cp),
               bucket=batch_bucket(B)) as tune_sp:
        for be, mb in candidates(cp, B, cheap=cheap):
            with _span("autotune.probe", backend=be, max_batch=mb) as sp:
                res = execute(cp, mems, backend=be, max_batch=mb)  # warm
                us = None
                for _ in range(max(1, reps)):
                    t0 = time.perf_counter()
                    res = execute(cp, mems, backend=be, max_batch=mb)
                    dt = (time.perf_counter() - t0) * 1e6
                    us = dt if us is None else min(us, dt)
                sp.set(us=us)
            _metrics.counter("autotune.probes").inc()
            if best is None or us < best[0]:
                best = (us, be, mb, res)
        us, be, mb, res = best
        tune_sp.set(winner=be, us=us)
    _metrics.counter(f"autotune.wins.{be}" + (f"@{mb}" if mb else "")).inc()
    entry = table.record(program_key(cp), batch_bucket(B), be, us,
                         max_batch=mb)
    if save:
        table.save()
    return res, entry


__all__ = [
    "CHUNK_BATCH", "TuningEntry", "TuningTable", "autotune_execute",
    "batch_bucket", "candidates", "get_default_table", "heuristic",
    "program_key", "reset_default_table", "resolve_auto",
]
