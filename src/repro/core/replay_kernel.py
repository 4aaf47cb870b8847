"""The unfused replay as one Pallas TPU kernel per same-mode run.

The XLA unfused body (:func:`repro.core.engine.jax_unfused_body`) replays a
cycle as a dense step: it gathers the five fan-in lines of all ``W`` op
slots, evaluates all eight gates on them and scatters ``W`` lines, whatever
number of the slots is live. Here a column or row run of the trace is one
kernel call that keeps the packed word in VMEM and replays only the live
gates, each with its own fan-in, one line at a time under scalar control.

Host side, :func:`replay_table` flattens the live gates of every column and
row cycle into one instruction stream in trace order (built once per
compiled program and memoised on ``cp._caches``). An instruction is
:data:`FIELDS` int32: ``(op, dst, sel, in0, ..., in4)``.

* ``op`` < :data:`OP_STAGE`: gate ``op`` (``GATE_IDS``) of lines ``in*``
  (``in`` past the gate's arity are 0), merged into line ``dst`` under mask
  ``sel`` of the mode's mask pool;
* :data:`OP_STAGE` + g: gate ``g``, kept in staging line ``dst`` unmerged;
* :data:`OP_COMMIT` + s: staging line ``s`` merged into line ``dst`` under
  mask ``sel``.

Every gate of a cycle reads the state from before the cycle. A gate whose
output line a later gate of its cycle reads is therefore staged, and
committed after the cycle's last gate; every other gate writes its line at
once (no cycle writes one line twice, so nothing else can see it early).
The Table I matvec and the Table II conv stage nothing: their gates only
ever read their own output line.

Device side, :func:`pallas_unfused_body` is the per-word body that
:func:`repro.core.engine.device_word_program` jits: an XLA loop over the
trace's same-mode runs (:func:`repro.core.engine.mode_runs`) that calls the
kernel for a column run on the ``(C+1, R+1)`` word with the row-mask pool,
for a row run on the transposed word with the column-mask pool (so every
access is a line of the word's minor axis), and replays init cycles with
the XLA body's :func:`repro.core.engine.init_replay`. In the kernel every
line is one ``(S, 128)`` block of uint32 (one vreg at 1024 rows), its pad
cell left out, and the run's instructions stream from HBM into SMEM in
chunks of :data:`CHUNK`, double-buffered.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from .compile import MAX_FANIN, MODE_INIT, CompiledProgram
from .engine import BIT_GATES, mode_runs

FIELDS = 8         # op, dst, sel, in0..in4
OP_STAGE = 8       # op - OP_STAGE is the gate; the output goes to staging
OP_COMMIT = 16     # op - OP_COMMIT is the staging line merged into the word
# instructions per SMEM chunk (128 KiB); the kernel holds two
CHUNK = 4096
# instructions per trip of the kernel's loop: on a TPU v5e 4 replayed
# 2.6-5.4% faster than 2 and 11-13% faster than 1 (PERF.md)
UNROLL = 4


@dataclasses.dataclass(frozen=True)
class ReplayTable:
    """The live gates of a trace's column and row cycles as instructions.

    ``ops`` is ``(N, FIELDS)`` int32 in trace order; ``cycle_start[t]`` is
    the index of cycle ``t``'s first instruction (``cycle_start[T] == N``;
    init cycles have none); ``runs`` is :func:`mode_runs`, so run ``r``
    replays instructions ``[cycle_start[runs[r, 0]], cycle_start[runs[r,
    1]])``; ``live`` counts the gates replayed (``cp.nops`` over the column
    and row cycles), and ``staged`` the gates kept in staging lines.
    """

    ops: np.ndarray
    cycle_start: np.ndarray
    runs: np.ndarray
    live: int
    staged: int

    def run_bounds(self) -> np.ndarray:
        """``(n_runs, 2)`` int32 instruction range of each run."""
        return self.cycle_start[self.runs[:, :2]].astype(np.int32)


def replay_table(cp: CompiledProgram) -> ReplayTable:
    """The kernel's instruction table of ``cp`` (memoised on
    ``cp._caches``); see :class:`ReplayTable`.

    Live gates per cycle, as ``cp.nops`` counts them (an init cycle, a
    column cycle, a row cycle, then column cycles), and each run's
    instructions:

    >>> from repro.core import BinaryMatvecPlan
    >>> t = replay_table(BinaryMatvecPlan(2, 8, rows=16, cols=64, parts=2)
    ...                  .compile())
    >>> np.diff(t.cycle_start)[:8].tolist(), t.live, t.staged
    ([0, 2, 1, 2, 2, 2, 2, 2], 112, 0)
    >>> t.run_bounds().tolist()
    [[0, 0], [0, 2], [2, 3], [3, 112]]
    """
    key = ("replay_table",)
    table = cp._caches.get(key)
    if table is not None:
        return table
    if cp.row_masks[:, -1].any() or cp.col_masks[:, -1].any():
        # the kernel leaves every line's pad cell out (see _build_body)
        raise ValueError("a mask selects the pad row or column")
    T, W = cp.gate.shape
    live = (cp.mode != MODE_INIT)[:, None] & (np.arange(W) < cp.nops[:, None])
    t, k = np.nonzero(live)                      # trace order, slot order
    arity = np.array([a for a, _ in BIT_GATES])[cp.gate[t, k]]
    reads = np.arange(MAX_FANIN) < arity[:, None]
    ins = np.where(reads, cp.ins[t, k], 0)
    # a gate is staged where a later gate of its cycle reads its line: the
    # last reader of every (cycle, line) against the line's writer
    lines = max(cp.rows, cp.cols) + 2
    read_key = (t[:, None] * lines + ins)[reads]
    by_key = np.argsort(read_key, kind="stable")
    keys, first = np.unique(read_key[by_key], return_index=True)
    st = np.zeros(len(t), bool)
    if len(keys):
        last = np.maximum.reduceat(
            np.broadcast_to(k[:, None], reads.shape)[reads][by_key], first)
        write_key = t * lines + cp.dst[t, k]
        at = np.minimum(np.searchsorted(keys, write_key), len(keys) - 1)
        st = (keys[at] == write_key) & (last[at] > k)
    # staging lines count from 0 in every cycle
    n_st = np.cumsum(st)
    cycle_first = np.searchsorted(t, t)
    stage_slot = n_st - 1 - (n_st - st)[cycle_first]
    gates = np.concatenate([
        np.stack([cp.gate[t, k] + OP_STAGE * st,
                  np.where(st, stage_slot, cp.dst[t, k]),
                  np.where(st, 0, cp.sel[t, k])], axis=1), ins], axis=1)
    commits = np.zeros((int(st.sum()), FIELDS), np.int64)
    commits[:, :3] = np.stack([OP_COMMIT + stage_slot[st], cp.dst[t, k][st],
                               cp.sel[t, k][st]], axis=1)
    # cycle by cycle: the gates in slot order, then the cycle's commits
    order = np.lexsort((np.r_[k, W + stage_slot[st]], np.r_[t, t[st]]))
    counts = np.bincount(np.r_[t, t[st]], minlength=T)
    table = cp._caches[key] = ReplayTable(
        ops=np.concatenate([gates, commits])[order].astype(np.int32),
        cycle_start=np.r_[0, np.cumsum(counts)].astype(np.int32),
        runs=mode_runs(cp), live=len(t), staged=len(commits))
    return table


def _kernel(bounds, tab_hbm, masks, word_in, word, tab, sem, stage):
    """Replay instructions ``[bounds[0], bounds[1])`` on the word in place.

    ``tab_hbm`` is the flat instruction table in HBM, ``tab`` two SMEM
    chunks of it, ``masks`` the mode's mask pool and ``stage`` the staging
    lines. Every line (of the word, the pool and the staging) is one
    ``(S, 128)`` block of uint32.
    """
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    span = CHUNK * FIELDS
    i0, i1 = bounds[0], bounds[1]
    c0, c1 = i0 // CHUNK, (i1 + CHUNK - 1) // CHUNK
    word[...] = word_in[...]    # a no-op where the two are one buffer

    def fetch(c, slot):
        return pltpu.make_async_copy(tab_hbm.at[pl.ds(c * span, span)],
                                     tab.at[pl.ds(slot * span, span)],
                                     sem.at[slot])

    def merge(b, out):
        dst = tab[b + 1]
        m = masks[tab[b + 2]]
        word[dst] = (out & m) | (word[dst] & ~m)

    def staged(b, op, out):
        @pl.when(op < OP_COMMIT)
        def _():
            stage[tab[b + 1]] = out

        @pl.when(op >= OP_COMMIT)
        def _():
            merge(b, stage[op - OP_COMMIT])

    def one(b):
        # every gate on the loaded lines, then the op's pick: no branch on
        # the gate, one (never taken in the paper's programs) on staging
        op = tab[b]
        g = op % OP_STAGE
        args = [word[tab[b + 3 + i]] for i in range(MAX_FANIN)]
        out = None
        for gid, (arity, fn) in enumerate(BIT_GATES):
            v = fn(*args[:arity])
            out = v if out is None else jnp.where(g == gid, v, out)
        lax.cond(op < OP_STAGE, lambda: merge(b, out),
                 lambda: staged(b, op, out))

    def chunk(c, carry):
        slot = (c - c0) % 2

        @pl.when(c + 1 < c1)
        def _():
            fetch(c + 1, 1 - slot).start()

        fetch(c, slot).wait()
        base = (slot - c) * span
        lo = jnp.maximum(i0, c * CHUNK)
        hi = jnp.minimum(i1, (c + 1) * CHUNK)
        n = (hi - lo) // UNROLL

        def steps(j, carry):
            for u in range(UNROLL):
                one(base + (lo + j * UNROLL + u) * FIELDS)
            return carry

        def step(i, carry):
            one(base + i * FIELDS)
            return carry

        carry = lax.fori_loop(0, n, steps, carry)
        return lax.fori_loop(lo + n * UNROLL, hi, step, carry)

    @pl.when(c0 < c1)
    def _():
        fetch(c0, 0).start()

    lax.fori_loop(c0, c1, chunk, 0)


def _sublanes(width: int) -> int:
    """Sublanes of 128 lanes that hold a line of ``width`` words."""
    return -(-width // 128)


def _mode_kernel(lines: int, width: int, n_masks: int, slots: int,
                 interpret: bool):
    """``run(bounds, table, masks, word) -> word`` for a word of ``lines``
    lines of ``width`` words each, each line an ``(S, 128)`` block."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    vmem = pltpu.VMEM
    S = _sublanes(width)
    # the word twice (input and output block), the masks and staging lines,
    # each line padded to (8, 128) tiles, and room for the compiler's own
    tile = 4 * 128 * (-(-S // 8) * 8)
    need = (2 * lines + n_masks + slots) * tile
    call = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((lines, S, 128), jnp.uint32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec(memory_space=vmem),
                  pl.BlockSpec(memory_space=vmem)],
        out_specs=pl.BlockSpec(memory_space=vmem),
        scratch_shapes=[pltpu.SMEM((2 * CHUNK * FIELDS,), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,)),
                        vmem((max(1, slots), S, 128), jnp.uint32)],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=need + (8 << 20)),
        interpret=interpret,
        name="replay_run",
    )
    return call


def pallas_unfused_body(cp: CompiledProgram, interpret: bool = False):
    """Un-jitted per-word body ``body(buf) -> buf`` over one packed
    ``(C+1, R+1)`` uint32 word, equal to the XLA unfused body bit for bit,
    that replays each column and row run in one kernel call (see the
    module). ``interpret`` runs the kernel in Pallas interpret mode (off
    TPU). Memoised on ``cp._caches``."""
    key = ("pallas_unfused_body", interpret)
    body = cp._caches.get(key)
    if body is None:
        body = cp._caches[key] = _build_body(cp, interpret)
    return body


def _build_body(cp: CompiledProgram, interpret: bool):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental.pallas import tpu as pltpu

    from .engine import init_replay

    table = replay_table(cp)
    R, C = cp.rows, cp.cols
    slots = int(table.staged and cp.W)
    # whole chunks, at least one, so that every chunk's copy is in bounds
    flat = np.zeros((max(1, -(-len(table.ops) // CHUNK)) * CHUNK, FIELDS),
                    np.int32)
    flat[:len(table.ops)] = table.ops
    ops = jnp.asarray(flat.reshape(-1))
    bounds = jnp.asarray(table.run_bounds())
    runs = jnp.asarray(table.runs)

    def in_hbm(x):
        # else XLA copies the whole table into VMEM before every run (the
        # interpreter takes no such constraint)
        if interpret:
            return x
        return pltpu.with_memory_space_constraint(x, pltpu.HBM)

    def blocks(x, width):
        # (n, width + 1) -> (n, S, 128): the pad cell of every line dropped
        # (no mask selects it, so no gate or init changes it), the rest
        # padded with zeros to whole sublanes
        S = _sublanes(width)
        x = jnp.pad(x[:, :width], ((0, 0), (0, S * 128 - width)))
        return x.reshape(x.shape[0], S, 128)

    def unblocks(w, y, width):
        return w.at[:, :width].set(y.reshape(w.shape[0], -1)[:, :width])

    ones = np.uint32(0xFFFFFFFF)
    row_pool = blocks(jnp.asarray(np.where(cp.row_masks, ones, 0)
                                  .astype(np.uint32)), R)
    col_pool = blocks(jnp.asarray(np.where(cp.col_masks, ones, 0)
                                  .astype(np.uint32)), C)
    col_kernel = _mode_kernel(C + 1, R, len(cp.row_masks), slots, interpret)
    row_kernel = _mode_kernel(R + 1, C, len(cp.col_masks), slots, interpret)
    init = init_replay(cp)

    def col_run(b, buf):
        return unblocks(buf, col_kernel(b, in_hbm(ops), row_pool,
                                        blocks(buf, R)), R)

    def row_run(b, buf):
        t = buf.T
        return unblocks(t, row_kernel(b, in_hbm(ops), col_pool,
                                      blocks(t, C)), C).T

    def run(r, buf):
        b = bounds[r]
        return lax.switch(             # by mode: column, row, init
            runs[r, 2],
            (functools.partial(col_run, b), functools.partial(row_run, b),
             lambda buf: lax.fori_loop(runs[r, 0], runs[r, 1], init, buf)),
            buf)

    def body(buf0):
        if not len(table.runs):
            return buf0
        return lax.fori_loop(0, len(table.runs), run, buf0)

    return body
