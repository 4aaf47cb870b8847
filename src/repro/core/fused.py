"""Fused (macro-op segment) executors for compiled crossbar traces.

This module lowers a :class:`~repro.core.compile.FusedSchedule` — the static
segment schedule attached at compile time — onto the two vectorized backends:

* **numpy-fused** (:func:`run_numpy_fused`): replays each segment's
  *independent spans* as single batched fancy-indexing calls — one gather /
  gate-eval / masked-scatter per gate group per span instead of a Python
  loop per cycle — and skips the trace-global op padding entirely (segments
  carry their own, usually much narrower, width).
* **jax-fused** (:func:`build_jax_fused`): ONE jitted function per program —
  batch-polymorphic over the canonical packed layout (the host loops the
  leading ``W = ceil(B/32)`` word axis around a per-word uint32 body, so
  every batch size replays through one of at most six XLA executables,
  one per word width shipped) — with **no
  per-cycle ``lax.switch`` and no cycle-granular scan carry**. Init segments
  lower to compile-time-constant
  ``jnp.where`` rectangles; short gate segments unroll to straight-line code
  with static indices; long gate segments become a mode-specialized
  ``lax.scan`` over fixed-size chunks of ``CHUNK`` cycles, so the carry
  (whole packed memory) is copied once per chunk, not once per cycle. Where
  a segment's per-position gate pattern repeats across chunks (the common
  ripple-adder periodicity), the exact gate expression is emitted instead of
  the 8-way branch-free gate stack.

Fault injection follows :mod:`repro.device.faults`: a ``FaultModel`` is
sampled per original cycle with the *same RNG discipline* as the unfused
numpy path (bit-identical under the same seed), while a ``FaultRealization``
carries explicit per-cycle masks that are packed per segment — the only
fault path shared bit-exactly by every backend.

Cycle accounting is untouched by construction: fusion changes how many
*simulator* steps replay the trace, never how many *hardware* cycles the
trace costs (``FusedSchedule.n_cycles == CompiledProgram.n_cycles``).
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..device.faults import FaultRealization, bernoulli_words
from .compile import (MAX_FANIN, MODE_COL, MODE_INIT, MODE_ROW,
                      CompiledProgram, FusedSchedule, Segment, fuse_program)

# jax lowering knobs: cycles per scan chunk, max segment length that is
# fully unrolled instead of scanned, and the segment-count ceiling above
# which the auto backend falls back to the unfused body, one loop over the
# trace's same-mode runs (jit trace/compile time grows with segment count;
# heavily mode-interleaved programs like the wide convs are better served
# by the unfused body).
CHUNK = 8
INLINE_MAX = 16
JAX_FUSE_MAX_SEGMENTS = 64


def schedule_for(cp: CompiledProgram) -> FusedSchedule:
    """``cp.schedule``, computing and attaching it if compiled unfused."""
    if cp.schedule is None:
        cp.schedule = fuse_program(cp)
    return cp.schedule


def prewarm_replay(cp: CompiledProgram) -> None:
    """Build ``cp``'s numpy replay plan ahead of the first batch.

    The first execute through a plan pays for deriving the replay structure
    (span grouping, gather tables) on top of the actual array work; the
    async compile pool calls this from a worker thread so that cost lands in
    the compile/warm-up account instead of the first request's latency.
    Memoized on ``cp._caches`` like every executor artifact — calling it is
    always correct and at worst a no-op.
    """
    if cp.schedule is not None:
        _numpy_fused_plan(cp)
    else:
        from .engine import _numpy_plan
        _numpy_plan(cp)


# ---------------------------------------------------------------------------
# NumPy fused executor
# ---------------------------------------------------------------------------


def _full_mask_ids(masks: np.ndarray, size: int) -> frozenset:
    return frozenset(
        int(i) for i, m in enumerate(masks)
        if m[:size].all() and not m[size:].any())


def _numpy_fused_plan(cp: CompiledProgram) -> list:
    """Span-batched replay plan (memoized on ``cp``).

    Per segment: ``(MODE_INIT, [per-cycle init entries])`` or
    ``(mode, [span replay entries])`` where a span entry carries the span's
    ops concatenated in (cycle-major, gate-sorted) order::

        (groups, blocks)
        groups = [(gid, arity, dst, ins, sel_ids, mask_rows, full, kidx)]
        blocks = [(t, gid, k0, k1, slots)]   # per-(cycle, gate) fault blocks

    ``kidx`` indexes a group's ops inside the span concat (fault masks are
    sampled block-contiguously and gathered per group through it); ``slots``
    are the ops' original compile slots (realization alignment).
    """
    plan = cp._caches.get("numpy_fused_plan")
    if plan is not None:
        return plan
    from .engine import BIT_GATES
    sched = schedule_for(cp)
    full_r = _full_mask_ids(cp.row_masks, cp.rows)
    full_c = _full_mask_ids(cp.col_masks, cp.cols)
    plan = []
    for seg in sched.segments:
        if seg.mode == MODE_INIT:
            cycles = []
            for t in range(seg.t0, seg.t1):
                ents = []
                for i in range(cp.I):
                    rm = cp.row_masks[cp.init_r[t, i]]
                    cm = cp.col_masks[cp.init_c[t, i]]
                    if rm.any() and cm.any():
                        ents.append((np.nonzero(cm)[0], np.nonzero(rm)[0],
                                     int(cp.init_v[t, i]), t, i))
                cycles.append(ents)
            plan.append((MODE_INIT, cycles))
            continue
        full_ids = full_r if seg.mode == MODE_COL else full_c
        masks = cp.row_masks if seg.mode == MODE_COL else cp.col_masks
        spans = []
        for a, b in seg.spans:
            gates, dsts, inss, sels, slots, ts = [], [], [], [], [], []
            blocks = []
            k = 0
            for j in range(a, b):
                n = int(seg.nops[j])
                g = seg.gate[j, :n]
                # per-cycle ops are gate-sorted: emit one block per gate run
                pos = 0
                while pos < n:
                    gid = int(g[pos])
                    end = pos
                    while end < n and int(g[end]) == gid:
                        end += 1
                    blocks.append((seg.t0 + j, gid, k + pos, k + end,
                                   seg.perm[j, pos:end]))
                    pos = end
                gates.append(g)
                dsts.append(seg.dst[j, :n])
                inss.append(seg.ins[j, :n])
                sels.append(seg.sel[j, :n])
                slots.append(seg.perm[j, :n])
                ts.append(np.full(n, seg.t0 + j))
                k += n
            gates = np.concatenate(gates) if gates else np.empty(0, np.int8)
            dsts = np.concatenate(dsts) if dsts else np.empty(0, np.int32)
            inss = (np.concatenate(inss) if inss
                    else np.empty((0, MAX_FANIN), np.int32))
            sels = np.concatenate(sels) if sels else np.empty(0, np.int32)
            groups = []
            for gid in np.unique(gates):
                kidx = np.nonzero(gates == gid)[0]
                arity = BIT_GATES[gid][0]
                sel = sels[kidx]
                groups.append((
                    int(gid), arity, dsts[kidx],
                    np.ascontiguousarray(inss[kidx, :arity]), sel,
                    masks[sel], all(int(s) in full_ids for s in sel), kidx))
            spans.append((groups, blocks))
        plan.append((seg.mode, spans))
    cp._caches["numpy_fused_plan"] = plan
    return plan


def run_numpy_fused(cp: CompiledProgram, mem: np.ndarray,
                    faults=None, rng=None) -> np.ndarray:
    """Fused numpy replay of ``cp`` over batch ``mem`` (B, R, C).

    Runs on the canonical packed buffer — uint32 words with a leading
    ``W = ceil(B/32)`` axis that every array expression broadcasts over.
    Bit-identical to the per-cycle numpy executor (and the interpreter) in
    all cases; under a ``FaultModel`` it also consumes the numpy RNG in the
    exact per-(cycle, gate-group) order of the unfused path, so faulty runs
    match bit-for-bit given the same seed.
    """
    from .engine import BIT_GATES, _pack, _unpack
    from ..device.faults import make_fault_source
    B = mem.shape[0]
    ones = np.uint32(0xFFFFFFFF)
    R, C = cp.rows, cp.cols
    src = make_fault_source(faults, rng, B, R, C)
    buf = _pack(mem)                                 # (W, C1, R1)
    if src is not None:
        sa0, sa1 = src.stuck()
        buf = (buf | sa1) & ~sa0

    for mode, items in _numpy_fused_plan(cp):
        if mode == MODE_INIT:
            for ents in items:
                for c_idx, r_idx, v, t, i in ents:
                    rect = (slice(None),) + np.ix_(c_idx, r_idx)
                    if src is None:
                        buf[rect] = ones if v else np.uint32(0)
                    else:
                        blk = np.full(
                            (buf.shape[0], len(c_idx), len(r_idx)),
                            ones if v else np.uint32(0), dtype=np.uint32)
                        flip = src.init_flip(t, i, c_idx, r_idx)
                        if flip is not None:
                            blk ^= flip
                        buf[rect] = (blk | sa1[rect]) & ~sa0[rect]
            continue
        for groups, blocks in items:
            if src is not None and src.has_switch:
                fail = np.empty(
                    (buf.shape[0], blocks[-1][3] if blocks else 0,
                     (R if mode == MODE_COL else C) + 1), dtype=np.uint32)
                for t, gid, k0, k1, slots in blocks:
                    f = (src.switch_col(t, slots, k1 - k0)
                         if mode == MODE_COL
                         else src.switch_row(t, slots,
                                             k1 - k0).transpose(0, 2, 1))
                    fail[:, k0:k1] = f
            else:
                fail = None
            # snapshot semantics: gather EVERY group's inputs against
            # pre-span memory before any group scatters (span analysis
            # permits write-after-read between span cycles, so a group must
            # never see another span write through its gathers)
            if mode == MODE_COL:
                outs = []
                for gid, arity, d, ik, s, m, full, kidx in groups:
                    g = buf[:, ik]                   # (W, n, arity, R1)
                    outs.append(
                        BIT_GATES[gid][1](*(g[:, :, k] for k in range(arity))))
                for (gid, arity, d, ik, s, m, full, kidx), out in zip(
                        groups, outs):
                    if src is None and full:
                        buf[:, d, :R] = out[..., :R]
                        continue
                    old = buf[:, d]
                    new = np.where(m, out, old)
                    if fail is not None:
                        fw = fail[:, kidx]
                        new = (old & fw) | (new & ~fw)
                    if src is not None:
                        new = (new | sa1[:, d]) & ~sa0[:, d]
                    buf[:, d] = new
            else:
                outs = []
                for gid, arity, d, ik, s, m, full, kidx in groups:
                    g = buf[:, :, ik]                # (W, C1, n, arity)
                    outs.append(
                        BIT_GATES[gid][1](*(g[..., k] for k in range(arity))))
                for (gid, arity, d, ik, s, m, full, kidx), out in zip(
                        groups, outs):
                    if src is None and full:
                        buf[:, :C, d] = out[:, :C]
                        continue
                    old = buf[:, :, d]
                    new = np.where(m.T, out, old)
                    if fail is not None:
                        fw = fail[:, kidx].transpose(0, 2, 1)  # (W, C1, n)
                        new = (old & fw) | (new & ~fw)
                    if src is not None:
                        new = (new | sa1[:, :, d]) & ~sa0[:, :, d]
                    buf[:, :, d] = new
    return _unpack(buf, B, cp.rows, cp.cols)


# ---------------------------------------------------------------------------
# JAX fused executor
# ---------------------------------------------------------------------------


def jax_fuse_eligible(cp: CompiledProgram) -> bool:
    """Whether the auto backend lowers ``cp`` through the fused jax path."""
    return schedule_for(cp).n_segments <= JAX_FUSE_MAX_SEGMENTS


def _build_jax_fused(cp: CompiledProgram,
                     realization: bool = False, body_only: bool = False):
    """Build the canonical jitted fused runner for ``cp``.

    The body is a per-word uint32 transition on one ``(C+1, R+1)`` packed
    buffer, jitted inside the word program that packs and unpacks on the
    device (``engine.device_word_program``); the returned runner loops the
    words host-side, so one executable per shipped width (at most six)
    serves every batch size. Returns
    ``runner(mem)`` (ideal) or ``runner(mem, real)`` where ``real`` is a
    :class:`FaultRealization` packed to runtime arguments, so one jit serves
    every realization of the same shape. ``body_only=True`` instead returns
    the un-jitted ideal packed-buffer transition ``body(buf) -> buf`` — the
    seam the mesh executor vmaps and shard_maps
    (``repro.distributed.mesh_exec``).
    """
    import jax.numpy as jnp
    from jax import lax

    from .engine import (BIT_GATES, WORD_BITS, device_word_program,
                         mode_cycles, replay_words)

    sched = schedule_for(cp)
    dt = jnp.dtype(np.uint32)
    R1, C1 = cp.rows + 1, cp.cols + 1
    ones = dt.type(0xFFFFFFFF)
    row_masks, col_masks = cp.row_masks, cp.col_masks
    jrow_masks, jcol_masks = jnp.asarray(row_masks), jnp.asarray(col_masks)

    def gate_runs(gates) -> List[tuple]:
        """[(gid, lo, hi)] contiguous same-gate runs of a sorted gate row."""
        runs, pos = [], 0
        while pos < len(gates):
            gid, end = int(gates[pos]), pos
            while end < len(gates) and int(gates[end]) == gid:
                end += 1
            runs.append((gid, pos, end))
            pos = end
        return runs

    def apply_cycle(buf, axis, out, dst, mask, fail, sa):
        """Masked scatter of one cycle's outputs, optional fault injection.

        ``out``/``mask`` are (n, L) in col mode and (C1, n) in row mode;
        ``fail`` likewise (or None); ``sa=(sa0, sa1)`` or None. Stuck cells
        are re-imposed on the whole word after the write (the invariant
        holds everywhere else already): on TPU, gathering the stuck-at maps
        at the written lines gave wrong words even with all-zero maps.
        """
        old = buf[dst] if axis == 0 else buf[:, dst]
        new = jnp.where(mask, out, old)
        if fail is not None:
            new = (old & fail) | (new & ~fail)
        buf = buf.at[dst].set(new) if axis == 0 else buf.at[:, dst].set(new)
        if sa is not None:
            buf = (buf | sa[1]) & ~sa[0]
        return buf

    def eval_static(buf, axis, gates, ins):
        """Gate-run-specialized evaluation with static gate structure."""
        outs = []
        for gid, lo, hi in gate_runs(gates):
            ar, fn = BIT_GATES[gid]
            idx = jnp.asarray(ins[lo:hi, :ar]) if isinstance(ins, np.ndarray) \
                else ins[lo:hi, :ar]
            if axis == 0:
                lines = buf[idx]                       # (n, ar, R1)
                outs.append(fn(*(lines[:, k] for k in range(ar))))
            else:
                lines = buf[:, idx]                    # (C1, n, ar)
                outs.append(fn(*(lines[:, :, k] for k in range(ar))))
        if len(outs) == 1:
            return outs[0]
        return jnp.concatenate(outs, axis=0 if axis == 0 else 1)

    def eval_stacked(buf, axis, gate_ids, ins, gates_present, iota_w):
        """Branch-free evaluation over the gates present in the segment."""
        gmap = np.zeros(8, np.int32)
        for i, g in enumerate(gates_present):
            gmap[g] = i
        gi = jnp.asarray(gmap)[gate_ids]
        if axis == 0:
            lines = buf[ins]                           # (W, 5, R1)
            stacked = jnp.stack(
                [BIT_GATES[g][1](*(lines[:, k] for k in range(BIT_GATES[g][0])))
                 for g in gates_present])              # (G, W, R1)
            return stacked[gi, iota_w]
        lines = buf[:, ins]                            # (C1, W, 5)
        stacked = jnp.stack(
            [BIT_GATES[g][1](*(lines[:, :, k] for k in range(BIT_GATES[g][0])))
             for g in gates_present])                  # (G, C1, W)
        return stacked[gi, :, iota_w].T                # (C1, W)

    # -- per-segment lowering -------------------------------------------------
    # Each segment lowers to fn(buf, sa, rx) -> buf where ``sa`` is the packed
    # stuck-at pair (or None) and ``rx`` the segment's realization arrays.

    def lower_init(seg: Segment, si: int):
        cycles = []
        for t in range(seg.t0, seg.t1):
            ents = []
            for i in range(cp.I):
                rm = row_masks[cp.init_r[t, i]]
                cm = col_masks[cp.init_c[t, i]]
                if rm.any() and cm.any():
                    ents.append((cm[:, None] & rm[None, :],
                                 int(cp.init_v[t, i]), i))
            cycles.append(ents)

        def run(buf, sa, rx):
            for j, ents in enumerate(cycles):
                for region, v, i in ents:
                    val = jnp.full((C1, R1), ones if v else dt.type(0), dt)
                    if rx is not None:
                        val = val ^ rx["init"][j, i]
                    if sa is not None:
                        val = (val | sa[1]) & ~sa[0]
                    buf = jnp.where(jnp.asarray(region), val, buf)
            return buf
        return run

    def lower_inline(seg: Segment, si: int):
        axis = 0 if seg.mode == MODE_COL else 1

        def run(buf, sa, rx):
            for j in range(seg.length):
                n = int(seg.nops[j])
                if not n:
                    continue
                out = eval_static(buf, axis, seg.gate[j, :n], seg.ins[j, :n])
                m = (row_masks if axis == 0 else col_masks)[seg.sel[j, :n]]
                mask = jnp.asarray(m if axis == 0 else m.T)
                fail = None if rx is None else (
                    rx["switch"][j, :n] if axis == 0
                    else rx["switch"][j, :n].T)
                buf = apply_cycle(buf, axis, out,
                                  jnp.asarray(seg.dst[j, :n]), mask, fail, sa)
            return buf
        return run

    def lower_scan(seg: Segment, si: int):
        axis = 0 if seg.mode == MODE_COL else 1
        L, W = seg.length, seg.W
        pad = (-L) % CHUNK
        n_ch = (L + pad) // CHUNK
        pad_cell = cp.cols if seg.mode == MODE_COL else cp.rows

        def padded(a, fill):
            if not pad:
                return a
            shape = (pad,) + a.shape[1:]
            return np.concatenate([a, np.full(shape, fill, a.dtype)])

        gate = padded(seg.gate, 0).reshape(n_ch, CHUNK, W)
        dst = padded(seg.dst, pad_cell).reshape(n_ch, CHUNK, W)
        ins = padded(seg.ins, pad_cell).reshape(n_ch, CHUNK, W, MAX_FANIN)
        sel = padded(seg.sel, 0).reshape(n_ch, CHUNK, W)  # id 0 = all-False
        # chunk-periodic gate structure => emit exact gate expressions
        static_sig = [tuple(gate[0, s]) if (gate[:, s] == gate[0, s]).all()
                      else None for s in range(CHUNK)]
        gates_present = sorted({int(g) for g in gate.reshape(-1)})
        iota_w = jnp.arange(W)
        line = R1 if axis == 0 else C1
        xs = {"gate": jnp.asarray(gate, jnp.int32), "dst": jnp.asarray(dst),
              "ins": jnp.asarray(ins), "sel": jnp.asarray(sel)}
        jmasks = jrow_masks if axis == 0 else jcol_masks

        def run(buf, sa, rx):
            scan_xs = dict(xs)
            if rx is not None:
                scan_xs["fail"] = rx["switch"]         # (n_ch, CHUNK, W, line)

            def step(b, x):
                for s in range(CHUNK):
                    sig = static_sig[s]
                    if sig is not None:
                        out = eval_static(b, axis, np.asarray(sig, np.int8),
                                          x["ins"][s])
                    else:
                        out = eval_stacked(b, axis, x["gate"][s], x["ins"][s],
                                           gates_present, iota_w)
                    m = jmasks[x["sel"][s]]            # (W, line)
                    fail = None
                    if rx is not None:
                        fail = x["fail"][s]
                        fail = fail if axis == 0 else fail.T
                    b = apply_cycle(b, axis, out, x["dst"][s],
                                    m if axis == 0 else m.T, fail, sa)
                return b, None

            buf, _ = lax.scan(step, buf, scan_xs)
            return buf
        return run

    seg_fns = []
    for si, seg in enumerate(sched.segments):
        if seg.mode == MODE_INIT:
            seg_fns.append(lower_init(seg, si))
        elif seg.length <= INLINE_MAX:
            seg_fns.append(lower_inline(seg, si))
        else:
            seg_fns.append(lower_scan(seg, si))

    def ideal_body(buf):
        for fn in seg_fns:
            buf = fn(buf, None, None)
        return buf

    if body_only:
        return ideal_body

    modes = mode_cycles(cp)
    if not realization:
        run_ideal = device_word_program(ideal_body, cp.rows, cp.cols)

        def runner(mem_np: np.ndarray,
                   call: Optional[int] = None) -> np.ndarray:
            return replay_words(mem_np, run_ideal, call, modes=modes)
        return runner

    def real_body(buf, sa, rxs):
        buf = (buf | sa[1]) & ~sa[0]             # cells are stuck from t=0
        for fn, rx in zip(seg_fns, rxs):
            buf = fn(buf, sa, rx)
        return buf

    run_real = device_word_program(real_body, cp.rows, cp.cols)

    def pack_realization(real: FaultRealization) -> tuple:
        """Segment-indexed host arrays for ONE canonical word of ``real``
        (batch <= 32; masks sampled per original cycle; sorted-slot
        permutation applied here, host-side)."""
        sa = tuple(a[0] for a in real.stuck_words())
        rxs = []
        for seg in sched.segments:
            if seg.mode == MODE_INIT:
                init = np.zeros((seg.length, cp.I, C1, R1), np.uint32)
                for j, t in enumerate(range(seg.t0, seg.t1)):
                    for i in range(cp.I):
                        init[j, i] = real.init_words(t, i)[0]
                rxs.append({"init": init})
                continue
            line = R1 if seg.mode == MODE_COL else C1
            sw = np.zeros((seg.length, seg.W, line), np.uint32)
            for j, t in enumerate(range(seg.t0, seg.t1)):
                n = int(seg.nops[j])
                if n:
                    sw[j, :n] = real.switch_words(t, seg.perm[j, :n],
                                                  line)[0]
            if seg.length > INLINE_MAX:
                pad = (-seg.length) % CHUNK
                if pad:
                    sw = np.concatenate(
                        [sw, np.zeros((pad, seg.W, line), np.uint32)])
                sw = sw.reshape(-1, CHUNK, seg.W, line)
            rxs.append({"switch": sw})
        return sa, tuple(rxs)

    def runner(mem_np: np.ndarray, real: FaultRealization,
               call: Optional[int] = None) -> np.ndarray:
        B = mem_np.shape[0]

        def word_args(w):
            rw = real.narrow(WORD_BITS * w, min(WORD_BITS * (w + 1), B))
            return pack_realization(rw)

        return replay_words(mem_np, run_real, call, word_args,
                            modes=modes)
    return runner


def build_jax_fused(cp: CompiledProgram):
    """The canonical ideal fused runner, memoized per program."""
    key = ("jax_fused",)
    runner = cp._caches.get(key)
    if runner is None:
        runner = cp._caches[key] = _build_jax_fused(cp)
    return runner


def jax_fused_body(cp: CompiledProgram):
    """Un-jitted ideal fused transition ``body(buf) -> buf`` on one packed
    ``(C+1, R+1)`` uint32 word buffer, memoized per program; the mesh
    executor vmaps this over per-device chunk stacks inside ``shard_map``."""
    key = ("jax_fused_body",)
    body = cp._caches.get(key)
    if body is None:
        body = cp._caches[key] = _build_jax_fused(cp, body_only=True)
    return body


def build_jax_fused_real(cp: CompiledProgram):
    """Realization-taking canonical fused runner, memoized per program."""
    key = ("jax_fused_real",)
    runner = cp._caches.get(key)
    if runner is None:
        runner = cp._caches[key] = _build_jax_fused(cp, realization=True)
    return runner
