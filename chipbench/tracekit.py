"""Reduce a profiler trace to device busy time, idle share and named gaps.

A trace is read from the ``.xplane.pb`` that ``jax.profiler.trace`` writes
into the neutral form :class:`Trace`: per device, the intervals in which an
operation ran, the programs (jitted executables) it ran, and where the
profiler dropped events; and the host annotations the benchmark put around
its own calls (``jax.profiler.TraceAnnotation``). All are on the
profiler's clock. Everything after loading is plain interval arithmetic,
so it is tested on synthetic traces as well as on a small recorded one.

The TPU profiler keeps about 6.3 million op events; past that it drops
buffers and records a ``Trace Buffers Dropped`` event. The traced window
then ends where the first drop begins: busy time, idle share and the
programs counted all refer to the part of the window the trace holds.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]      # (start_ns, end_ns, name)

# the benchmark's own host annotations; "window" spans the measured window
# and "execute_batch" each engine call
WINDOW, CALL = "window", "execute_batch"
ANNOTATIONS = (WINDOW, CALL, "submit", "step", "decode-check")
DEVICE_PREFIX = "/device:TPU:"
# a TPU device plane's lines: one event per operation, one per program run
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
DROPPED = "Trace Buffers Dropped"


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Interval]]      # op intervals per device
    host: List[Interval]
    modules: Dict[str, List[Interval]] = dataclasses.field(
        default_factory=dict)                # program runs per device
    drops: List[float] = dataclasses.field(default_factory=list)

    def window(self) -> Tuple[float, float]:
        """The measured window: the benchmark's ``window`` annotation."""
        spans = [(s, e) for s, e, n in self.host if n == WINDOW]
        if not spans:
            raise ValueError("trace holds no 'window' annotation")
        return min(s for s, _ in spans), max(e for _, e in spans)


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path: Path, device_prefix: str = DEVICE_PREFIX,
                annotations: Sequence[str] = ANNOTATIONS) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = Trace(devices={}, host=[])
    wanted = set(annotations)

    def spans(line):
        return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in line.events]

    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            lines = {line.name: line for line in plane.lines}
            out.devices[plane.name] = (spans(lines[OP_LINE])
                                       if OP_LINE in lines else [])
            if MODULE_LINE in lines:
                out.modules[plane.name] = spans(lines[MODULE_LINE])
            out.drops.extend(e.start_ns for line in lines.values()
                             for e in line.events if e.name == DROPPED)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out.host.extend(s for s in spans(line) if s[2] in wanted)
    return out


def merge(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of intervals as sorted, disjoint (start, end) pairs."""
    out: List[List[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(spans: Iterable[Tuple[float, float]], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in spans
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float):
    """Idle intervals of ``[lo, hi)`` between merged busy spans."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_name(gap: Tuple[float, float], host: Sequence[Interval]) -> str:
    """The innermost benchmark annotation that covers most of ``gap``."""
    best, best_key = "idle", (0.0, 0.0)
    for s, e, name in host:
        if name == WINDOW:
            continue
        cover = min(e, gap[1]) - max(s, gap[0])
        # most overlap first; among equals the shortest (innermost) span
        key = (cover, -(e - s))
        if cover > 0 and key > best_key:
            best, best_key = name, key
    return best


def reduce(trace: Trace, top: int = 10,
           window: Optional[Tuple[float, float]] = None) -> dict:
    """Busy time, idle share, programs run, time by op name and longest
    named idle gaps over the traced window, averaged over the devices."""
    lo, hi = window or trace.window()
    truncated = bool(trace.drops) and min(trace.drops) < hi
    if truncated:
        hi = max(lo, min(trace.drops))
    span = hi - lo
    if span <= 0 or not trace.devices:
        raise ValueError("empty window or no device in the trace")
    busy_ns, by_op, all_gaps = [], {}, []
    for name, evs in sorted(trace.devices.items()):
        busy = merge(clip(((s, e) for s, e, _ in evs), lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        for s, e, op in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op = op.split(" = ", 1)[0]      # "%fusion.3 = u32[..] ..."
                by_op[op] = by_op.get(op, 0.0) + d
        all_gaps.extend(gaps(busy, lo, hi))
    n = len(busy_ns)
    mean_busy = sum(busy_ns) / n
    # programs that ran whole inside the window, and their device time, per
    # device on average; and those inside the engine calls the window holds
    # whole
    whole = [(s, e) for evs in trace.modules.values() for s, e, _ in evs
             if lo <= s and e <= hi]
    calls = [(s, e) for s, e, name in trace.host
             if name == CALL and lo <= s and e <= hi]
    in_calls = [(s, e) for s, e in whole
                if any(cs <= s and e <= ce for cs, ce in calls)]
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": span * 1e-9,
        "busy_s": mean_busy * 1e-9,
        "idle_pct": 100.0 * (1.0 - mean_busy / span),
        "devices": n,
        "truncated": truncated,
        "programs": len(whole) / n,
        "program_s": sum(e - s for s, e in whole) * 1e-9 / n,
        "calls": len(calls),
        "call_programs": len(in_calls) / n,
        "call_program_s": sum(e - s for s, e in in_calls) * 1e-9 / n,
        "device_ops": [[op, d * 1e-9 / n] for op, d in ops],
        "idle_gaps": [[host_name(g, trace.host), (g[1] - g[0]) * 1e-9]
                      for g in longest],
    }
