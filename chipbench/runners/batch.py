"""Engine cells: ``CrossbarPlan.execute_batch`` over a batch of crossbars.

Set-up compiles the configuration's plan, loads ``crossbars`` independent
images from the seed and warms the replay on one packed word. The window
then runs whole calls over the same images until ``seconds`` have passed;
no call is cut. ``xbar_cycles_per_s`` is crossbars times the configuration's
cycles, summed over the calls, over the time those calls took. Every call's
results are read back between calls (``decode-check``) and compared, once
the window has closed, with the plain reference: every crossbar of every
call, and each call's reported cycles and stats with the configuration's.
With ``control`` the control stands in the program's place.
"""
from __future__ import annotations

import numpy as np

from chipbench import ops, roofline
from chipbench.bench import peak_bytes
from chipbench.timing import Outcome, annotate, profiled
from chipbench.traffic import STREAM_OPERANDS, rng

WORD = 32   # crossbars per packed word


def run(cfg: dict, traffic: dict, *, seed: int, seconds: float,
        trace_dir, t0: float, clock, devs, compiles=None,
        control: bool = False) -> Outcome:
    spec, backend = cfg["plan"], cfg["backend"]
    plan = ops.make_plan(spec, cfg["geometry"])
    cp = plan.compile()
    B = int(traffic["crossbars"])
    A, x = ops.operands(spec, rng(seed, STREAM_OPERANDS), batch=(B,))
    mems = np.zeros((B, plan.rows, plan.cols), np.uint8)
    for b in range(B):
        plan.load_into(mems[b], A[b], x[b])

    def execute():      # the timed path: one call over every crossbar
        res = plan.execute_batch(mems, backend=backend)
        return res.mem, res.cycles, res.stats, res.backend

    def decode(out):
        return ops.decode_batch(spec, plan, out)

    if control:
        from chipbench.control import engine_control
        execute, decode = engine_control(cfg, A, x)
        execute()
    else:
        plan.execute_batch(mems[:WORD], backend=backend)   # one word warms
    setup_s = clock() - t0
    compiled = compiles.count if compiles else 0

    calls, decoded = [], []
    with profiled(trace_dir) as prof:
        with annotate("window"):
            start = clock()
            while not calls or clock() - start < seconds:
                with annotate("execute_batch"):
                    c0 = clock()
                    out, cycles, stats, label = execute()
                    c1 = clock()
                with annotate("decode-check"):
                    decoded.append(decode(out))
                    calls.append((c1 - c0, cycles, stats, label))
                del out
            window_s = clock() - start
    peak = peak_bytes(devs)
    window_compiles = compiles.count - compiled if compiles else None
    del mems

    want = ops.reference(spec, A, x)
    wrong_xbars = sum(ops.wrong(spec, d, want) for d in decoded)
    wrong_cycles = sum(c != cfg["cycles"] for _, c, _, _ in calls)
    wrong_stats = sum(s != cfg["stats"] for _, _, s, _ in calls)
    call_s = sum(t for t, _, _, _ in calls)
    return Outcome(
        e2e={"xbar_cycles_per_s": B * cfg["cycles"] * len(calls) / call_s,
             "setup_s": setup_s},
        checks={"wrong_crossbars": (wrong_xbars, 0),
                "wrong_cycles_calls": (wrong_cycles, 0),
                "wrong_stats_calls": (wrong_stats, 0)},
        attempted=B * len(calls),
        failed=min(B * len(calls),
                   wrong_xbars + B * (wrong_cycles + wrong_stats)),
        peak_bytes=peak, trace=prof.reduction if prof else None,
        ctx={"cycles": cp.n_cycles, "words_per_call": -(-B // WORD),
             "word_bytes": roofline.word_bytes(cp),
             "device_kind": devs[0].device_kind},
        info={"window_compiles": window_compiles,
              "compile_s": compiles.seconds if compiles else None,
              "calls": len(calls),
              "call_s": [round(t, 4) for t, _, _, _ in calls],
              "window_s": window_s, "backend": calls[0][3],
              "crossbars": B})
