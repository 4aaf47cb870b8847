"""What every runner shares: its outcome, host annotations and the profiler
around the measured window."""
from __future__ import annotations

import contextlib
import dataclasses
import shutil
from pathlib import Path
from typing import Dict, Optional, Tuple


@dataclasses.dataclass
class Outcome:
    e2e: Dict[str, float]                   # end-to-end metric values
    checks: Dict[str, Tuple[float, float]]  # compared number -> (value, limit)
    attempted: int
    failed: int
    peak_bytes: int
    trace: Optional[dict]                   # tracekit.reduce(...) or None
    ctx: dict                               # what per-layer readers read
    info: dict                              # printed on stderr only

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.checks.values())


def annotate(name: str):
    """A host span on the profiler's clock (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class _Profile:
    reduction: Optional[dict] = None


@contextlib.contextmanager
def profiled(trace_dir: Optional[Path]):
    """Profile the block into ``trace_dir`` and reduce the trace on exit
    (``.reduction``); yields ``None`` and records nothing without a dir."""
    if trace_dir is None:
        yield None
        return
    import jax

    from . import tracekit

    trace_dir = Path(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    prof = _Profile()
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield prof
    finally:
        jax.profiler.stop_trace()
    prof.reduction = tracekit.reduce(
        tracekit.load_xplane(tracekit.find_xplane(trace_dir)))
    shutil.rmtree(trace_dir, ignore_errors=True)   # a full trace is ~0.3 GB
