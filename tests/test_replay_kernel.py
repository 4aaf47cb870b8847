"""The unfused replay as one Pallas kernel per same-mode run.

On a TPU the unfused jax runner replays each column and row run of the
trace in one kernel call (``core/replay_kernel.py``). Here the kernel runs
in Pallas interpret mode, through the runner itself (the tests stand in for
the TPU by patching the platform rule), and must give the ``numpy-unfused``
executor's memory byte for byte, and the engine's cycles and stats, on:

* every gate of ``BIT_GATES`` in column mode and in row mode;
* init cycles between gate runs;
* a trace of more instructions than one SMEM chunk of the table, at the
  default chunk and at a small one (runs that start inside a chunk and
  cross several);
* cycles in which one live gate reads a line that another live gate of the
  same cycle writes (every gate reads the state from before the cycle);
* small Table I matvec and Table II conv programs, whose gates read their
  own output line.

The ``engine.replay.live_gates`` counter grows by the kernel's live gates
for every word replayed.
"""
import dataclasses
import doctest
import functools

import numpy as np
import pytest

from repro.core import (ConvPlan, MatvecPlan, compile_program, execute,
                        have_jax)
from repro.core.compile import GATE_IDS, MODE_COL, MODE_INIT, MODE_ROW
from repro.core.engine import BIT_GATES, word_count
from repro.core.isa import GATES, ColOp, InitOp, RowOp
from repro.obs import metrics

pytestmark = pytest.mark.skipif(not have_jax(), reason="needs jax")

GEOM = (32, 64, 4)                 # rows, cols, partitions


def _program(pattern: str, seed: int):
    """One cycle per letter of ``pattern``: ``c`` column gates, ``r`` row
    gates (one op in every partition, the gates of each mode in turn so
    that a few cycles use all eight, random lines and masks), ``i`` an init
    rectangle."""
    rng = np.random.default_rng(seed)
    rows, cols, parts = GEOM
    names = sorted(GATES, key=GATE_IDS.get)
    turn = {"c": 0, "r": 0}

    def ops(kind):
        op, span, lines = ((ColOp, cols // parts, rows) if kind == "c"
                           else (RowOp, rows // parts, cols))
        cyc = []
        for p in range(parts):
            g = names[turn[kind] % len(names)]
            turn[kind] += 1
            ar = GATES[g].arity
            at = rng.choice(span, size=ar + 1, replace=False) + p * span
            sel = (None if rng.random() < 0.5 else
                   sorted(int(v) for v in rng.choice(lines, 3, replace=False)))
            cyc.append(op(g, tuple(int(v) for v in at[:ar]), int(at[ar]),
                          sel))
        return cyc

    prog = []
    for kind in pattern:
        if kind == "i":
            prog.append([InitOp(sorted(int(v) for v in
                                       rng.choice(rows, 5, replace=False)),
                                slice(0, cols, 3), int(rng.integers(2)))])
        else:
            prog.append(ops(kind))
    return compile_program(prog, rows, cols, parts, parts)


def _with_hazards(cp):
    """``cp`` with, in its first column and first row cycle of two or more
    gates, the first two gates made one gate that each reads the line the
    other writes: a cycle no valid program has (its ops would share a
    partition), but whose gates must still read the pre-cycle state."""
    gate, ins = cp.gate.copy(), cp.ins.copy()
    for mode in (MODE_COL, MODE_ROW):
        t = int(np.flatnonzero((cp.mode == mode) & (cp.nops >= 2))[0])
        gate[t, 1] = gate[t, 0]
        ins[t, 0, 0], ins[t, 1, 0] = cp.dst[t, 1], cp.dst[t, 0]
    return dataclasses.replace(cp, gate=gate, ins=ins)


def _plan(kind):
    g = dict(rows=32, cols=128, parts=4)
    plan = {"mv": lambda: MatvecPlan(8, 2, 4, **g),
            "conv": lambda: ConvPlan(16, 4, 3, 4, **g)}[kind]()
    return plan.compile()


# case -> (program, batch, table chunk or None for the default)
CASES = {
    "gates-both-modes": (lambda: _program("cc" + "rr" + "cr" * 3, 1), 5,
                         None),
    "init-between-runs": (lambda: _program("icciriccr", 2), 3, None),
    "over-one-chunk": (lambda: _program("c" * 700 + "r" * 400 + "ic", 3), 2,
                       None),
    "small-chunks": (lambda: _program("icrccrrrcccir", 4), 3, 5),
    "hazard": (lambda: _with_hazards(_program("crrcc", 5)), 4, None),
    "hazard-small-chunks": (lambda: _with_hazards(_program("ccrrcc", 6)), 4,
                            3),
    "matvec-self-read": (lambda: _plan("mv"), 33, None),
    "conv-self-read": (lambda: _plan("conv"), 5, 64),
}


@pytest.mark.parametrize("case", list(CASES))
def test_replay_kernel_equals_numpy(case, monkeypatch):
    import repro.kernels.ops as kernel_ops
    from repro.core import replay_kernel

    make, B, chunk = CASES[case]
    cp = make()
    if chunk is not None:
        monkeypatch.setattr(replay_kernel, "CHUNK", chunk)
    # the runner as on a TPU, with the kernel interpreted
    monkeypatch.setattr(kernel_ops, "interpret_mode", lambda: False)
    monkeypatch.setattr(
        replay_kernel, "pallas_unfused_body",
        functools.partial(replay_kernel.pallas_unfused_body, interpret=True))
    metrics.reset_metrics()

    table = replay_kernel.replay_table(cp)
    gates = cp.mode != MODE_INIT
    live = np.arange(cp.W)[None, :] < cp.nops[:, None]
    assert table.live == int(cp.nops[gates].sum()) == \
        len(table.ops) - table.staged
    assert (table.staged > 0) == case.startswith("hazard")
    if case == "gates-both-modes":
        for mode in (MODE_COL, MODE_ROW):
            on = live & (cp.mode == mode)[:, None]
            assert set(cp.gate[on].tolist()) == set(range(len(BIT_GATES)))
    if case == "init-between-runs":
        assert (cp.mode == MODE_INIT).sum() >= 2
    if case in ("over-one-chunk", "small-chunks", "conv-self-read"):
        assert len(table.ops) > replay_kernel.CHUNK
    if case.endswith("self-read"):
        reads = [int(cp.dst[t, k]) in
                 cp.ins[t, k, :BIT_GATES[cp.gate[t, k]][0]]
                 for t, k in zip(*np.nonzero(live & gates[:, None]))]
        assert any(reads)

    rng = np.random.default_rng(B)
    mem = (rng.random((B, cp.rows, cp.cols)) < 0.5).astype(np.uint8)
    got = execute(cp, mem, backend="jax-unfused")
    want = execute(cp, mem, backend="numpy-unfused")
    np.testing.assert_array_equal(got.mem, want.mem)
    assert (got.cycles, got.stats, got.backend) == \
        (want.cycles, want.stats, "jax-unfused")
    assert metrics.counter("engine.replay.live_gates").value == \
        table.live * word_count(B)


def test_replay_table_doctest():
    """The table builder's docstring: live gates per cycle of a small
    binary matvec."""
    from repro.core import replay_kernel

    res = doctest.testmod(replay_kernel, verbose=False)
    assert res.attempted >= 3 and res.failed == 0


def test_live_gates_counter_stays_off_the_cpu_runner():
    """Off a TPU the unfused runner is the XLA body: nothing counts live
    gates there."""
    cp = _program("crci", 7)
    metrics.reset_metrics()
    execute(cp, np.zeros((3,) + GEOM[:2], np.uint8), backend="jax-unfused")
    assert metrics.counter("engine.replay.live_gates").value == 0
    assert metrics.counter("engine.replay.mode_runs").value == 4
