"""Service cells: ``PlanService`` serving a mix of requests as they arrive.

Set-up builds the service, draws every request of the window from the seed
(operands included) and serves one request of each kind once, so that its
plan is compiled and its replay warm. The window is an open loop: requests
are submitted when due, whether or not the service keeps up, and the loop
runs ``step(max_units)`` while work is queued. After the last request is
due the loop drains the queue, for at most ``DRAIN_S`` seconds. Latency is
from the scheduled arrival to the decoded result; a request that never
completes counts as infinitely late. Every completed result is compared,
once the loop has ended, with the plain reference. With ``control`` the
control stands in the service's place.
"""
from __future__ import annotations

import math
import time

from chipbench import ops
from chipbench.bench import peak_bytes
from chipbench.timing import Outcome, annotate, profiled
from chipbench.traffic import STREAM_OPERANDS, STREAM_WARM, open_schedule, rng

DRAIN_S = 60.0
SPANS = ("serve.load", "serve.decode")


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank (an order statistic: no
    interpolation, so a missing request's infinite latency stays above it)."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def make_service(cfg: dict):
    from repro.serve.matpim import PlanService
    return PlanService(backend=cfg["backend"], store=False, **cfg["geometry"])


def warm(svc, kinds: dict, names, seed: int) -> None:
    """Serve one request of each kind once (compiles and warms its plan)."""
    wr = rng(seed, STREAM_WARM)
    for name in names:
        ops.submit(svc, kinds[name], *ops.operands(kinds[name], wr))
        svc.flush()


def draw(kinds: dict, traffic: dict, seconds: float, seed: int):
    """Every request due in ``seconds``: [(due_s, kind, operands)]."""
    orng = rng(seed, STREAM_OPERANDS)
    return [(due, name, ops.operands(kinds[name], orng))
            for due, name in open_schedule(traffic, seconds, seed)]


def open_loop(svc, reqs, kinds, max_units: int, clock, deadline: float):
    """Serve ``reqs`` = [(due_s, kind, operands)]; returns (start, tickets)."""
    tickets = [None] * len(reqs)
    i, n = 0, len(reqs)
    start = clock()
    while True:
        now = clock() - start
        while i < n and reqs[i][0] <= now:
            _, name, (a, b) = reqs[i]
            with annotate("submit"):
                tickets[i] = ops.submit(svc, kinds[name], a, b)
            i += 1
        if svc.pending_units:
            with annotate("step"):
                svc.step(max_units=max_units)
        elif i < n:
            time.sleep(max(0.0, min(reqs[i][0] - now, 0.05)))
        else:
            break
        if now > deadline:
            break
    return start, tickets


def judge(reqs, tickets, kinds: dict, start: float):
    """Each request's latency from its scheduled arrival (infinite where it
    never completed), how late each completed one was submitted, and the
    number of completed requests whose result differs from the plain
    reference."""
    lat, lags, wrong = [], [], 0
    for (due, name, (a, b)), t in zip(reqs, tickets):
        if t is None or not t.done:
            lat.append(math.inf)
            continue
        lags.append(t.submitted_s - (start + due))
        lat.append(t.submitted_s + t.wall_s - (start + due))
        wrong += ops.wrong(kinds[name], t.result,
                           ops.reference(kinds[name], a, b)) > 0
    return lat, lags, wrong


def run(cfg: dict, traffic: dict, *, seed: int, seconds: float,
        trace_dir, t0: float, clock, devs, compiles=None,
        control: bool = False) -> Outcome:
    kinds = {k["name"]: k for k in cfg["requests"]}
    if control:
        from chipbench.control import ControlService
        svc = ControlService()
    else:
        svc = make_service(cfg)
    reqs = draw(kinds, traffic, seconds, seed)
    warm(svc, kinds, traffic["mix"], seed)
    setup_s = clock() - t0
    compiled = compiles.count if compiles else 0

    units0, batches0 = svc.stats.units, svc.stats.batches
    tracer = None
    if trace_dir is not None:
        from repro.obs import trace as obs
        tracer = obs.enable()
    try:
        with profiled(trace_dir) as prof:
            with annotate("window"):
                start, tickets = open_loop(
                    svc, reqs, kinds, int(traffic["max_units"]), clock,
                    seconds + DRAIN_S)
                loop_s = clock() - start
    finally:
        if tracer is not None:
            obs.disable()
    peak = peak_bytes(devs)
    window_compiles = compiles.count - compiled if compiles else None
    svc.close()

    lat, lags, wrong = judge(reqs, tickets, kinds, start)
    lost = sum(math.isinf(v) for v in lat)
    p90 = nearest_rank(lat, 0.9)
    spans_s = 0.0
    if tracer is not None:
        spans_s = sum(e["dur"] for e in tracer.events()
                      if e["name"] in SPANS) * 1e-6
    done = len(reqs) - lost
    return Outcome(
        e2e={"req_p90_ms": p90 * 1e3 if math.isfinite(p90) else 1e9,
             "setup_s": setup_s},
        checks={"wrong_results": (wrong, 0), "lost_requests": (lost, 0)},
        attempted=len(reqs), failed=wrong + lost, peak_bytes=peak,
        trace=prof.reduction if prof else None,
        ctx={"requests": done, "host_span_s": spans_s,
             "units": svc.stats.units - units0,
             "batches": svc.stats.batches - batches0,
             "device_kind": devs[0].device_kind},
        info={"window_compiles": window_compiles,
              "compile_s": compiles.seconds if compiles else None,
              "requests": len(reqs), "loop_s": loop_s,
              "p50_ms": nearest_rank(lat, 0.5) * 1e3,
              "gen_lag_p90_ms": (nearest_rank(lags, 0.9) * 1e3
                                 if lags else None),
              "batches": svc.stats.batches - batches0})
