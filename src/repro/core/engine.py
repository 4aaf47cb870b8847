"""Vectorized batched executors for compiled crossbar traces.

Two backend families replay a :class:`~repro.core.compile.CompiledProgram`
over a batch of B independent crossbars, each in a fused (macro-op segment)
and an unfused (per-cycle) variant:

* ``numpy`` — fused by default: segments replay as batched fancy-indexing
  over independent cycle spans (``fused.run_numpy_fused``). The unfused
  variant (``numpy-unfused``) is the legacy Python loop over cycles.
* ``jax`` — fused by default for segment-friendly traces: one jitted
  function per (program, word dtype) with mode-specialized per-segment
  ``lax.scan`` chunks and **no** per-cycle ``lax.switch``
  (``fused.build_jax_fused``). The unfused variant folds the whole trace
  through a per-cycle ``lax.scan`` + ``lax.switch`` — kept as the fallback
  for heavily mode-interleaved traces and for ``FaultModel`` injection.
  Gated: raises cleanly when jax is absent.

``backend`` accepts ``"numpy"``/``"jax"`` (auto: fused when the compiled
trace carries a schedule) plus the explicit variants ``"numpy-fused"``,
``"numpy-unfused"``, ``"jax-fused"``, ``"jax-unfused"``.

Canonical packed-word layout
----------------------------
Memory is held transposed and bit-packed over the batch in ONE canonical
layout shared by every executor: a ``(W, cols+1, rows+1)`` uint32 buffer
with ``W = word_count(B) = ceil(B / 32)`` as a leading data axis —
``buf[w, c, r]`` is one 32-bit word whose bit b is cell (r, c) of crossbar
``32*w + b``. Every FELIX gate is a short boolean expression on words
(``BIT_GATES``), so one gather + a couple of bitwise ops simulate the gate
across 32 crossbars at once — this is where the >=10x over the interpreter
comes from, and what makes the tiled multi-crossbar scale-out
(``tiling.py``) cheap.

The word width never tracks the batch: the numpy executors broadcast over
the leading W axis, and the jitted jax bodies stay per-word ``(C+1, R+1)``
with a host-side loop over words — so every batch size shares the SAME
jitted runner (one XLA compile per program, keyed dtype-free on
``cp._caches``), instead of one runner per batch-derived word dtype.
The only transparent chunking left is ``FaultModel`` sampling, which keeps
the historic chunk sizes (64 on numpy, 32 on jax) so same-seed Monte-Carlo
draws stay bit-identical across releases.

All backends are bit-identical to the interpreter (``Crossbar.run``) in
final memory state, cycle count, and op-category stats — property-tested in
``tests/test_compile_engine.py`` and ``tests/test_conformance.py``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import time
from typing import Dict, List, Optional

import numpy as np

# import-light by design (numpy + stdlib-only obs) — safe at module init
from ..device.faults import (FaultModel, FaultRealization, as_rng,
                             make_fault_source, sample_stuck_words)
from ..obs import metrics as _metrics
from ..obs.trace import span as _span
from .compile import (MAX_FANIN, MODE_COL, MODE_INIT, MODE_ROW,
                      CompiledProgram)

# boolean word implementations of the FELIX suite, indexed by GATE_IDS.
# MINk (k-input minority) is NOT(majority); MIN5 goes through two full adders:
# a+b+c = 2*maj(a,b,c) + (a^b^c), then fold in d, e.


def _maj3(a, b, c):
    return (a & b) | ((a ^ b) & c)


def _min5(a, b, c, d, e):
    s1 = a ^ b ^ c
    c1 = _maj3(a, b, c)
    s2 = d ^ e ^ s1
    c2 = _maj3(d, e, s1)
    # a+..+e = 2*(c1+c2) + s2  =>  sum >= 3  <=>  (c1&c2) | ((c1^c2)&s2)
    return ~((c1 & c2) | ((c1 ^ c2) & s2))


# (arity, word function) per GATE_IDS slot; executors gather exactly `arity`
# input lines per op
BIT_GATES = (
    (1, lambda a: ~a),                              # NOT
    (2, lambda a, b: a | b),                        # OR2
    (2, lambda a, b: ~(a | b)),                     # NOR2
    (3, lambda a, b, c: ~(a | b | c)),              # NOR3
    (2, lambda a, b: ~(a & b)),                     # NAND2
    (3, lambda a, b, c: ~_maj3(a, b, c)),           # MIN3
    (5, _min5),                                     # MIN5
    (3, lambda a, b, c: ~((a | b) & c)),            # OAI3
)


def have_jax() -> bool:
    return importlib.util.find_spec("jax") is not None


def available_backends() -> tuple:
    """The real set of backends ``execute`` accepts for compiled traces.

    ``"auto"`` resolves per ``(program key, batch bucket)`` from the tunings
    table (measured) or a conservative heuristic; ``"numpy"``/``"jax"`` pick
    fused-vs-unfused from the trace alone; the ``-fused``/``-unfused`` forms
    force a variant; ``"pallas"`` lowers eligible traces onto the
    ``repro.kernels`` Pallas kernels and falls back otherwise.
    ``CrossbarPlan`` methods additionally accept ``"interp"`` (the uncompiled
    interpreter), which is plan-level only.

    >>> bs = available_backends()
    >>> ("auto" in bs, "numpy-fused" in bs, "numpy-unfused" in bs)
    (True, True, True)
    >>> ("jax" in bs) == ("pallas" in bs)  # both need jax
    True
    """
    base = ("auto", "numpy", "numpy-fused", "numpy-unfused")
    if have_jax():
        base += ("jax", "jax-fused", "jax-unfused", "pallas")
    return base


def parse_backend(backend: str) -> tuple:
    """``backend`` → ``(base, variant)`` with base in
    {auto, numpy, jax, pallas} and variant in {auto, fused, unfused}.

    >>> parse_backend("numpy"), parse_backend("jax-fused")
    (('numpy', 'auto'), ('jax', 'fused'))
    >>> parse_backend("auto"), parse_backend("pallas")
    (('auto', 'auto'), ('pallas', 'auto'))
    >>> parse_backend("interp")
    Traceback (most recent call last):
        ...
    ValueError: unknown engine backend 'interp'; compiled traces support \
'auto', 'numpy', 'numpy-fused', 'numpy-unfused', 'jax', 'jax-fused', \
'jax-unfused', 'pallas' ('interp' is plan-level only: use \
CrossbarPlan.execute)
    """
    base, variant = backend, "auto"
    if backend.endswith("-fused"):
        base, variant = backend[:-len("-fused")], "fused"
    elif backend.endswith("-unfused"):
        base, variant = backend[:-len("-unfused")], "unfused"
    if base not in ("numpy", "jax") and not (
            base in ("auto", "pallas") and variant == "auto"):
        # enumerate the full spelling set, not just what this host can run:
        # a clear error beats hiding 'jax'/'pallas' on a cpu-only box
        known = ("'auto', 'numpy', 'numpy-fused', 'numpy-unfused', 'jax', "
                 "'jax-fused', 'jax-unfused', 'pallas'")
        raise ValueError(
            f"unknown engine backend {backend!r}; compiled traces support "
            f"{known} ('interp' is plan-level only: use "
            f"CrossbarPlan.execute)")
    return base, variant


@dataclasses.dataclass
class EngineResult:
    mem: np.ndarray        # (B, rows, cols) uint8 final memory state
    cycles: int            # == len(program) by construction
    stats: Dict[str, int]  # interpreter-identical op-category counters
    backend: str
    faults: object = None  # FaultModel / FaultRealization the run was under


# ---------------------------------------------------------------------------
# Canonical bit-plane pack / unpack: (W, C+1, R+1) uint32 words
# ---------------------------------------------------------------------------

# bits per packed word — THE word width of the canonical layout. Every
# executor (numpy, jax, mesh, pallas operand packing) shares it; batches
# wider than one word grow the leading W axis instead of the word dtype.
WORD_BITS = 32

# legacy alias (the constant predates the canonical layout; importers treat
# it as "the jax chunk width", which is still the word width)
JAX_WORD_BITS = WORD_BITS


def word_count(B: int) -> int:
    """Packed words covering a batch of ``B`` crossbars: ``ceil(B / 32)``.

    >>> word_count(1), word_count(32), word_count(33), word_count(128)
    (1, 1, 2, 4)
    """
    if B < 1:
        raise ValueError(f"batch must be positive, got {B}")
    return -(-int(B) // WORD_BITS)


_LITTLE = __import__("sys").byteorder == "little"


def _pack_word(mem: np.ndarray) -> np.ndarray:
    """(B <= 32, R, C) uint8 -> (C+1, R+1) uint32, bit b = crossbar b.

    Byte-plane construction: bits are OR-accumulated into uint8 planes (one
    per word byte) and the planes reinterpreted as uint32, so the only wide
    operation is a single word-matrix transpose at the end. At B == 1 the
    word simply *is* the cell value. This keeps host-side packing far below
    trace-replay cost (the generic ``np.packbits(axis=0)`` path it replaces
    dominated whole-engine wall time at large batches).
    """
    B, R, C = mem.shape
    buf = np.zeros((C + 1, R + 1), dtype=np.uint32)
    if B == 1:
        buf[:C, :R] = mem[0].T
        return buf
    if not _LITTLE:                                   # pragma: no cover
        pb = np.packbits(mem, axis=0, bitorder="little")
        word = pb[0].astype(np.uint32)
        for g in range(1, pb.shape[0]):
            word |= pb[g].astype(np.uint32) << np.uint32(8 * g)
        buf[:C, :R] = word.T
        return buf
    planes = np.zeros((R, C, 4), np.uint8)
    for g in range((B + 7) // 8):
        p = planes[:, :, g]
        for k in range(min(8, B - 8 * g)):
            p |= mem[8 * g + k] << np.uint8(k)
    word = planes.reshape(R, C * 4).view(np.uint32)   # (R, C)
    buf[:C, :R] = word.T
    return buf


def _pack(mem: np.ndarray) -> np.ndarray:
    """(B, R, C) uint8 -> canonical (W, C+1, R+1) uint32 packed buffer.

    ``W = word_count(B)``; word ``w`` packs crossbars ``[32w, 32w+32)`` with
    unused high bits of the last word zero. This is the ONE layout every
    executor replays — the numpy paths broadcast over the leading axis, the
    jax runners loop it host-side around a per-word jitted body.
    """
    B = mem.shape[0]
    W = word_count(B)
    if W == 1:
        return _pack_word(mem)[None]
    buf = np.empty((W, mem.shape[2] + 1, mem.shape[1] + 1), np.uint32)
    for w in range(W):
        buf[w] = _pack_word(mem[WORD_BITS * w:WORD_BITS * (w + 1)])
    return buf


def _unpack_word(buf: np.ndarray, B: int, R: int, C: int) -> np.ndarray:
    """Inverse of :func:`_pack_word`: (C+1, R+1) uint32 -> (B, R, C) uint8.

    One word-matrix transpose up front, then contiguous per-bit shifts out
    of uint8 byte planes (no ``np.unpackbits`` round-trip through an
    8x-inflated bit tensor, no strided (B, R, C) transpose copy).
    """
    if B == 1:
        return np.ascontiguousarray(
            (buf[:C, :R] & np.uint32(1)).astype(np.uint8).T)[None]
    wT = np.ascontiguousarray(buf[:C, :R].T)          # (R, C) words
    out = np.empty((B, R, C), dtype=np.uint8)
    if not _LITTLE:                                   # pragma: no cover
        for b in range(B):
            out[b] = (wT >> np.uint32(b)).astype(np.uint8) & 1
        return out
    u8 = wT.view(np.uint8).reshape(R, C, 4)
    for g in range((B + 7) // 8):
        plane = np.ascontiguousarray(u8[:, :, g])
        for k in range(min(8, B - 8 * g)):
            out[8 * g + k] = (plane >> np.uint8(k)) & np.uint8(1)
    return out


def _unpack(buf: np.ndarray, B: int, R: int, C: int) -> np.ndarray:
    """Inverse of :func:`_pack`: (W, C+1, R+1) uint32 -> (B, R, C) uint8."""
    W = buf.shape[0]
    if W == 1:
        return _unpack_word(buf[0], B, R, C)
    out = np.empty((B, R, C), dtype=np.uint8)
    for w in range(W):
        lo = WORD_BITS * w
        bw = min(WORD_BITS, B - lo)
        out[lo:lo + bw] = _unpack_word(buf[w], bw, R, C)
    return out


# ---------------------------------------------------------------------------
# NumPy executor
# ---------------------------------------------------------------------------


def _full_mask_ids(masks: np.ndarray, size: int) -> frozenset:
    return frozenset(
        int(i) for i, m in enumerate(masks)
        if m[:size].all() and not m[size:].any())


def _numpy_plan(cp: CompiledProgram) -> List[tuple]:
    """Ragged, gate-grouped per-cycle schedule (memoized on ``cp``).

    Each cycle becomes ``(mode, groups, inits)`` with gate ops grouped by
    gate id so the executor evaluates one boolean expression per group, the
    gather sliced to the gate's actual fan-in. ``full`` marks groups whose
    write masks select every real row/column — those skip the read-mask-merge
    and write the data region directly.
    """
    plan = cp._caches.get("numpy_plan")
    if plan is not None:
        return plan
    full_r = _full_mask_ids(cp.row_masks, cp.rows)
    full_c = _full_mask_ids(cp.col_masks, cp.cols)
    plan = []
    for t in range(cp.n_cycles):
        n = int(cp.nops[t])
        mode = int(cp.mode[t])
        full_ids = full_r if mode == MODE_COL else full_c
        groups = []
        if n:
            gids = cp.gate[t, :n]
            for gid in np.unique(gids):
                w = np.nonzero(gids == gid)[0]
                arity = BIT_GATES[gid][0]
                sel = cp.sel[t, w]
                full = all(int(s) in full_ids for s in sel)
                groups.append((int(gid), arity, cp.dst[t, w],
                               np.ascontiguousarray(cp.ins[t, w, :arity]),
                               sel, full, t, w))
        inits = []
        if mode == MODE_INIT:
            for i in range(cp.I):
                rm = cp.row_masks[cp.init_r[t, i]]
                cm = cp.col_masks[cp.init_c[t, i]]
                if rm.any() and cm.any():
                    inits.append((np.nonzero(cm)[0], np.nonzero(rm)[0],
                                  int(cp.init_v[t, i]), t, i))
        plan.append((mode, groups, inits))
    cp._caches["numpy_plan"] = plan
    return plan


def _run_numpy(cp: CompiledProgram, mem: np.ndarray,
               faults: Optional[FaultModel] = None,
               rng: Optional[np.random.Generator] = None) -> np.ndarray:
    if faults is not None:
        return _run_numpy_faulty(cp, mem, faults, rng)
    B = mem.shape[0]
    ones = np.uint32(0xFFFFFFFF)
    R, C = cp.rows, cp.cols
    buf = _pack(mem)                             # (W, C1, R1) words
    rmasks, cmasks = cp.row_masks, cp.col_masks
    plan = _numpy_plan(cp)

    for mode, groups, inits in plan:
        if mode == MODE_COL:
            for gid, arity, d, ik, s, full, t, w in groups:
                g = buf[:, ik]                   # (W, n, arity, R1)
                out = BIT_GATES[gid][1](*(g[:, :, k] for k in range(arity)))
                if full:
                    # write the data rows only; the extra (const-0) row at
                    # index R must stay zero
                    buf[:, d, :R] = out[..., :R]
                else:
                    m = rmasks[s]                # (n, R1), broadcasts over W
                    buf[:, d] = np.where(m, out, buf[:, d])
        elif mode == MODE_ROW:
            for gid, arity, d, ik, s, full, t, w in groups:
                g = buf[:, :, ik]                # (W, C1, n, arity)
                out = BIT_GATES[gid][1](*(g[..., k] for k in range(arity)))
                if full:
                    buf[:, :C, d] = out[:, :C]
                else:
                    m = cmasks[s].T              # (C1, n), broadcasts over W
                    buf[:, :, d] = np.where(m, out, buf[:, :, d])
        else:
            for c_idx, r_idx, v, t, i in inits:
                rect = (slice(None),) + np.ix_(c_idx, r_idx)
                buf[rect] = ones if v else np.uint32(0)
    return _unpack(buf, B, cp.rows, cp.cols)


def _run_numpy_faulty(cp: CompiledProgram, mem: np.ndarray,
                      faults,
                      rng: Optional[np.random.Generator]) -> np.ndarray:
    """Trace replay with device faults as packed word masks.

    Identical replay structure to :func:`_run_numpy` (the ``full`` shortcut
    is skipped — masked writes give the same result), with three injection
    points: the stuck-at invariant ``buf = (buf | sa1) & ~sa0`` applied to
    the initial load and to every written line, a per-gate-evaluation
    switching-failure mask that retains the old output value, and per-cell
    init-disturb flips inside bulk-init rectangles. ``faults`` is a
    :class:`FaultModel` (masks drawn here, in cycle-then-gate order) or a
    :class:`FaultRealization` (masks precomputed per cycle). With the ideal
    model all masks are zero words and the result is bit-identical to the
    fault-free path (property-tested).
    """
    B = mem.shape[0]
    ones = np.uint32(0xFFFFFFFF)
    R, C = cp.rows, cp.cols
    src = make_fault_source(faults, rng, B, R, C)
    sa0, sa1 = src.stuck()                       # (W, C1, R1) each
    buf = _pack(mem)
    buf = (buf | sa1) & ~sa0                     # cells are stuck from t=0
    rmasks, cmasks = cp.row_masks, cp.col_masks

    for mode, groups, inits in _numpy_plan(cp):
        if mode == MODE_COL:
            for gid, arity, d, ik, s, full, t, w in groups:
                g = buf[:, ik]                   # (W, n, arity, R1)
                out = BIT_GATES[gid][1](*(g[:, :, k] for k in range(arity)))
                old = buf[:, d]
                new = np.where(rmasks[s], out, old)
                if src.has_switch:
                    fail = src.switch_col(t, w, len(d))   # (W, n, R1)
                    new = (old & fail) | (new & ~fail)
                buf[:, d] = (new | sa1[:, d]) & ~sa0[:, d]
        elif mode == MODE_ROW:
            for gid, arity, d, ik, s, full, t, w in groups:
                g = buf[:, :, ik]                # (W, C1, n, arity)
                out = BIT_GATES[gid][1](*(g[..., k] for k in range(arity)))
                old = buf[:, :, d]
                new = np.where(cmasks[s].T, out, old)
                if src.has_switch:
                    fail = src.switch_row(t, w, len(d))   # (W, C1, n)
                    new = (old & fail) | (new & ~fail)
                buf[:, :, d] = (new | sa1[:, :, d]) & ~sa0[:, :, d]
        else:
            for c_idx, r_idx, v, t, i in inits:
                rect = (slice(None),) + np.ix_(c_idx, r_idx)
                blk = np.full((buf.shape[0], len(c_idx), len(r_idx)),
                              ones if v else np.uint32(0), dtype=np.uint32)
                flip = src.init_flip(t, i, c_idx, r_idx)
                if flip is not None:
                    blk ^= flip
                buf[rect] = (blk | sa1[rect]) & ~sa0[rect]
    return _unpack(buf, B, cp.rows, cp.cols)


# ---------------------------------------------------------------------------
# JAX executor (lax.scan over the packed trace, uint32 bit-planes)
# ---------------------------------------------------------------------------


def _build_jax_body(cp: CompiledProgram):
    """Un-jitted unfused per-cycle scan ``body(buf) -> buf`` over one packed
    ``(C+1, R+1)`` uint32 word of the canonical buffer (see
    :func:`jax_unfused_body`); the runner loops words host-side."""
    import jax.numpy as jnp
    from jax import lax

    R1, C1, W = cp.rows + 1, cp.cols + 1, cp.W
    dt = jnp.dtype(np.uint32)
    ones = dt.type(0xFFFFFFFF)
    row_masks = jnp.asarray(cp.row_masks)
    col_masks = jnp.asarray(cp.col_masks)
    xs = {
        "mode": jnp.asarray(cp.mode, jnp.int32),
        "gate": jnp.asarray(cp.gate, jnp.int32),
        "dst": jnp.asarray(cp.dst),
        "ins": jnp.asarray(cp.ins),
        "sel": jnp.asarray(cp.sel),
        "init_r": jnp.asarray(cp.init_r),
        "init_c": jnp.asarray(cp.init_c),
        "init_v": jnp.asarray(cp.init_v),
    }
    iota_w = jnp.arange(W)

    def gate_select(gate_ids, args):
        # args: 5 operand arrays (W, L); evaluate all 8 boolean gates on the
        # words and pick per-op — branch-free, vectorizes across the cycle
        stacked = jnp.stack([fn(*args[:ar]) for ar, fn in BIT_GATES])  # (8, W, L)
        return stacked[gate_ids, iota_w]                               # (W, L)

    def col_step(buf, x):
        g = jnp.take(buf, x["ins"].reshape(-1), axis=0).reshape(W, MAX_FANIN, R1)
        out = gate_select(x["gate"], tuple(g[:, k] for k in range(MAX_FANIN)))
        mask = row_masks[x["sel"]]                           # (W, R1)
        old = jnp.take(buf, x["dst"], axis=0)
        return buf.at[x["dst"]].set(jnp.where(mask, out, old))

    def row_step(buf, x):
        g = jnp.take(buf, x["ins"].reshape(-1), axis=1) \
            .reshape(C1, W, MAX_FANIN).transpose(1, 2, 0)    # (W, 5, C1)
        out = gate_select(x["gate"], tuple(g[:, k] for k in range(MAX_FANIN)))
        mask = col_masks[x["sel"]]                           # (W, C1)
        old = jnp.take(buf, x["dst"], axis=1).T              # (W, C1)
        new = jnp.where(mask, out, old)
        return buf.at[:, x["dst"]].set(new.T)

    def init_step(buf, x):
        for i in range(cp.I):
            region = col_masks[x["init_c"][i]][:, None] \
                & row_masks[x["init_r"][i]][None, :]
            word = jnp.where(x["init_v"][i] > 0, ones, dt.type(0))
            buf = jnp.where(region, word, buf)
        return buf

    def step(buf, x):
        buf = lax.switch(x["mode"], (col_step, row_step, init_step), buf, x)
        return buf, None

    def body(buf0):
        # modest unroll amortizes the while-loop bookkeeping (~35% on CPU)
        buf, _ = lax.scan(step, buf0, xs, unroll=4)
        return buf

    return body


def jax_unfused_body(cp: CompiledProgram):
    """Un-jitted unfused per-word transition, memoized dtype-free on
    ``cp._caches`` — the seam ``repro.distributed.mesh_exec`` vmaps inside
    ``shard_map``."""
    key = ("jax_unfused_body",)
    body = cp._caches.get(key)
    if body is None:
        body = cp._caches[key] = _build_jax_body(cp)
    return body


def _build_jax_runner(cp: CompiledProgram):
    import jax
    import jax.numpy as jnp

    run = jax.jit(jax_unfused_body(cp))

    def runner(mem_np: np.ndarray) -> np.ndarray:
        B = mem_np.shape[0]
        bufs = _pack(mem_np)                       # (W, C1, R1)
        out = np.stack([np.asarray(run(jnp.asarray(b))) for b in bufs])
        return _unpack(out, B, cp.rows, cp.cols)

    return runner


def _build_jax_runner_faulty(cp: CompiledProgram):
    """Fault-injecting variant of :func:`_build_jax_runner`.

    The scan carry is ``(buf, key)``: one PRNG key threads through the whole
    trace, split once per cycle, so every gate evaluation / init cell draws
    independent Bernoulli fault words. Stuck-at maps and the two soft-fault
    probabilities are jit arguments — one compilation serves every fault
    rate of a sweep.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    R1, C1, W = cp.rows + 1, cp.cols + 1, cp.W
    dt = jnp.uint32
    row_masks = jnp.asarray(cp.row_masks)
    col_masks = jnp.asarray(cp.col_masks)
    xs = {
        "mode": jnp.asarray(cp.mode, jnp.int32),
        "gate": jnp.asarray(cp.gate, jnp.int32),
        "dst": jnp.asarray(cp.dst),
        "ins": jnp.asarray(cp.ins),
        "sel": jnp.asarray(cp.sel),
        "init_r": jnp.asarray(cp.init_r),
        "init_c": jnp.asarray(cp.init_c),
        "init_v": jnp.asarray(cp.init_v),
    }
    iota_w = jnp.arange(W)
    bit_w = jnp.arange(WORD_BITS, dtype=dt)

    def bern(key, p, shape):
        # words of Bernoulli(p) bits, one realization per bit-plane slot
        bits = (jax.random.uniform(key, shape + (WORD_BITS,)) < p)
        return jnp.sum(bits.astype(dt) << bit_w, axis=-1, dtype=dt)

    def gate_select(gate_ids, args):
        stacked = jnp.stack([fn(*args[:ar]) for ar, fn in BIT_GATES])
        return stacked[gate_ids, iota_w]

    @jax.jit
    def run(buf0, key, sa0, sa1, p_switch, p_init):
        def col_step(buf, k, x):
            g = jnp.take(buf, x["ins"].reshape(-1), axis=0) \
                .reshape(W, MAX_FANIN, R1)
            out = gate_select(x["gate"],
                              tuple(g[:, i] for i in range(MAX_FANIN)))
            mask = row_masks[x["sel"]]
            old = jnp.take(buf, x["dst"], axis=0)
            new = jnp.where(mask, out, old)
            fail = bern(k, p_switch, (W, R1))
            new = (old & fail) | (new & ~fail)
            return buf.at[x["dst"]].set(new)

        def row_step(buf, k, x):
            g = jnp.take(buf, x["ins"].reshape(-1), axis=1) \
                .reshape(C1, W, MAX_FANIN).transpose(1, 2, 0)
            out = gate_select(x["gate"],
                              tuple(g[:, i] for i in range(MAX_FANIN)))
            mask = col_masks[x["sel"]]
            old = jnp.take(buf, x["dst"], axis=1).T        # (W, C1)
            new = jnp.where(mask, out, old)
            fail = bern(k, p_switch, (W, C1))
            new = (old & fail) | (new & ~fail)
            return buf.at[:, x["dst"]].set(new.T)

        def init_step(buf, k, x):
            ks = jax.random.split(k, cp.I)
            for i in range(cp.I):
                region = col_masks[x["init_c"][i]][:, None] \
                    & row_masks[x["init_r"][i]][None, :]
                word = jnp.where(x["init_v"][i] > 0, dt(0xFFFFFFFF), dt(0))
                val = word ^ bern(ks[i], p_init, (C1, R1))
                buf = jnp.where(region, val, buf)
            return buf

        def step(carry, x):
            buf, key = carry
            key, sub = jax.random.split(key)
            buf = lax.switch(x["mode"], (col_step, row_step, init_step),
                             buf, sub, x)
            # stuck cells hold their value through every write. Applied to
            # the whole word, not gathered per written line: on TPU the
            # per-line form (take of sa0/sa1 at the written lines, then
            # OR/AND-NOT) gave wrong words even with all-zero maps.
            buf = (buf | sa1) & ~sa0
            return (buf, key), None

        (buf, _), _ = lax.scan(step, (buf0, key), xs, unroll=4)
        return buf

    def runner(mem_np: np.ndarray, faults: FaultModel,
               rng: np.random.Generator) -> np.ndarray:
        # _execute_impl chunks FaultModel batches at WORD_BITS, so the
        # canonical pack is always a single word here
        B = mem_np.shape[0]
        sa0, sa1 = sample_stuck_words(faults, B, cp.rows, cp.cols, rng)
        sa0, sa1 = sa0[0], sa1[0]
        buf = _pack(mem_np)[0]
        buf = (buf | sa1) & ~sa0                 # cells are stuck from t=0
        key = jax.random.PRNGKey(int(rng.integers(0, 2**31 - 1)))
        out = np.asarray(run(jnp.asarray(buf), key, jnp.asarray(sa0),
                             jnp.asarray(sa1), jnp.float32(faults.p_switch),
                             jnp.float32(faults.p_init)))
        return _unpack(out[None], B, cp.rows, cp.cols)

    return runner


def _run_jax(cp: CompiledProgram, mem: np.ndarray,
             faults: Optional[FaultModel] = None,
             rng: Optional[np.random.Generator] = None) -> np.ndarray:
    if faults is not None:
        runner = cp._caches.get("jax_runner_faulty")
        if runner is None:
            runner = cp._caches["jax_runner_faulty"] = \
                _build_jax_runner_faulty(cp)
        return runner(mem, faults, as_rng(rng))
    runner = cp._caches.get("jax_runner")
    if runner is None:
        runner = cp._caches["jax_runner"] = _build_jax_runner(cp)
    return runner(mem)


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------


def _ambient_mesh():
    """The mesh activated by ``distributed.sharding.use_mesh``, if any.

    Checked via ``sys.modules`` so numpy-only processes never pay a jax
    import: an ambient mesh can only exist if something already imported
    the sharding module to activate it.
    """
    import sys
    mod = sys.modules.get("repro.distributed.sharding")
    return mod.current_mesh() if mod is not None else None


def execute(
    cp: CompiledProgram,
    mem: np.ndarray,
    backend: str = "numpy",
    max_batch: Optional[int] = None,
    faults=None,
    rng=None,
    tunings=None,
    mesh=None,
) -> EngineResult:
    """Replay ``cp`` over a batch of crossbars.

    Telemetry: every call runs under a ``span("engine.execute")`` (no-op
    unless tracing is enabled) and publishes into the ``repro.obs`` metrics
    registry — ``engine.execute.calls[.<label>]`` counters, a per-resolved-
    backend ``engine.execute.wall_us.<label>`` histogram, and fault-model
    gauges (``engine.fault.p_*``) when a non-ideal :class:`FaultModel` is
    supplied. The label is the result's ``backend`` field with any ``@mb``
    chunking suffix stripped (e.g. ``auto:jax-fused``).

    ``mem`` is ``(B, rows, cols)`` (or ``(rows, cols)`` for B=1) uint8 initial
    state; the input is not mutated. Any batch packs into the canonical
    ``(W, cols+1, rows+1)`` uint32 layout (``W = ceil(B/32)``) and runs in
    one executor call; only ``max_batch`` (span chunking from the autotuner)
    and ``FaultModel`` runs — which keep the historic chunk widths (64 numpy
    / 32 jax) so same-seed Monte-Carlo draws stay bit-identical — split the
    batch. Every chunk runs the identical program, so the reported cycle
    count (the *parallel* latency of B independent arrays) is unchanged.

    ``backend`` selects the executor: ``"numpy"``/``"jax"`` use the fused
    macro-op schedule when ``cp`` carries one (the compile default) and fall
    back to per-cycle replay otherwise; ``"numpy-fused"``/``"jax-fused"``
    require fusion (attaching a schedule on demand), and
    ``"numpy-unfused"``/``"jax-unfused"`` force the legacy per-cycle paths.
    The auto jax backend also falls back to the unfused scan for heavily
    mode-interleaved traces (see ``fused.JAX_FUSE_MAX_SEGMENTS``) — fused
    lowering is always *correct*, but jit time grows with segment count.

    ``faults`` selects a device model: a
    :class:`repro.device.faults.FaultModel` (each crossbar draws an
    independent realization — stuck-at maps, per-gate switching failures,
    init disturb — seeded from ``rng``: ``None``/seed/Generator) or an
    explicit :class:`repro.device.faults.FaultRealization` whose per-cycle
    masks replay bit-identically on every backend that accepts them.
    Support matrix: numpy paths take both; the jax auto path serves a
    ``FaultModel`` through the unfused PRNG-threaded scan (unchanged
    behavior) and a ``FaultRealization`` through the fused runner.
    The fault machinery runs even for the ideal all-zero model —
    bit-identity with ``faults=None`` is a property-tested guarantee, not a
    shortcut — and never adds cycles: faults perturb state, not schedules.

    Two meta-backends layer on top of the four concrete paths.
    ``backend="auto"`` resolves a concrete backend (and optionally a
    span-chunking ``max_batch``) per ``(program key, batch bucket)`` from
    the autotuner's tunings table — ``tunings`` (a
    :class:`repro.core.autotune.TuningTable`) overrides the process default
    — falling back to a conservative heuristic when nothing is measured;
    the result's ``backend`` field records the choice as
    ``"auto:<resolved>"``. ``backend="pallas"`` lowers traces that carry a
    plan-attached ``pallas_spec`` (binary matvec, encoded matvec, conv)
    onto the ``repro.kernels`` Pallas kernels — interpret-mode off-TPU,
    Mosaic on TPU — and transparently falls back to jax/numpy for
    ineligible programs or fault runs (``backend`` field
    ``"pallas:fallback-<base>"``).

    ``mesh`` (or an ambient ``distributed.sharding.use_mesh``) shards a
    fault-free jax batch over the mesh's ``tiles`` axis (label suffix
    ``+mesh<D>``). When a multi-device mesh is given and the run cannot
    shard, it runs on one device and a
    :class:`~repro.distributed.mesh_exec.MeshDeclinedWarning` says why.
    """
    t0 = time.perf_counter()
    if mesh is None:
        mesh = _ambient_mesh()
    with _span("engine.execute", backend=backend) as sp:
        res = _execute_impl(cp, mem, backend, max_batch, faults, rng, tunings,
                            mesh)
        sp.set(resolved=res.backend, cycles=res.cycles)
    wall_us = (time.perf_counter() - t0) * 1e6
    label = res.backend.split("@", 1)[0]
    _metrics.counter("engine.execute.calls").inc()
    _metrics.counter(f"engine.execute.calls.{label}").inc()
    _metrics.histogram(f"engine.execute.wall_us.{label}").observe(wall_us)
    if isinstance(faults, FaultModel) and not faults.is_ideal:
        _metrics.counter("engine.execute.fault_runs").inc()
        _metrics.gauge("engine.fault.p_sa0").set(faults.p_sa0)
        _metrics.gauge("engine.fault.p_sa1").set(faults.p_sa1)
        _metrics.gauge("engine.fault.p_switch").set(faults.p_switch)
        _metrics.gauge("engine.fault.p_init").set(faults.p_init)
    elif isinstance(faults, FaultRealization):
        _metrics.counter("engine.execute.fault_runs").inc()
    return res


def _mesh_declined(devices: int, reason: str) -> None:
    """Tell the caller that a multi-device mesh ran on one device."""
    import warnings

    from ..distributed.mesh_exec import MeshDeclinedWarning
    _metrics.counter("engine.sharded.declined").inc()
    warnings.warn(f"mesh of {devices} devices declined: {reason}; running "
                  f"on one device", MeshDeclinedWarning, stacklevel=4)


def _execute_impl(
    cp: CompiledProgram,
    mem: np.ndarray,
    backend: str,
    max_batch: Optional[int],
    faults,
    rng,
    tunings,
    mesh=None,
) -> EngineResult:
    from .fused import (build_jax_fused, build_jax_fused_real,
                        jax_fuse_eligible, run_numpy_fused, schedule_for)

    squeeze = mem.ndim == 2
    if squeeze:
        mem = mem[None]
    assert mem.shape[1:] == (cp.rows, cp.cols), (mem.shape, cp.rows, cp.cols)
    mem = np.ascontiguousarray(mem, dtype=np.uint8)

    # device topology the batch could shard over: >1 only when the mesh has
    # a usable 'tiles' axis, the batch fills it, and the run is fault-free
    # (fault realizations stay on the audited single-device paths). A
    # multi-device mesh that ends up on one device says why (``declined``).
    topo, mesh_size, declined = 1, 1, None
    if mesh is not None and have_jax():
        from ..distributed.mesh_exec import mesh_devices
        mesh_size = mesh_devices(mesh)
        if mesh_size > 1:
            if faults is not None:
                declined = "fault runs stay on one device"
            elif mem.shape[0] < mesh_size:
                declined = f"batch of {mem.shape[0]} is smaller than the mesh"
            else:
                topo = mesh_size

    base, variant = parse_backend(backend)
    label = backend
    if base == "auto":
        from .autotune import resolve_auto
        resolved, mb, _src = resolve_auto(cp, mem.shape[0], faults=faults,
                                          table=tunings, topo=topo)
        base, variant = parse_backend(resolved)
        if max_batch is None and mb is not None:
            max_batch = mb
        label = (f"auto:{resolved}@{mb}" if mb is not None
                 else f"auto:{resolved}")
    elif base == "pallas":
        from .pallas_exec import pallas_eligible, run_pallas
        if pallas_eligible(cp, faults):
            if topo > 1:
                _mesh_declined(topo, "the pallas backend runs on one device")
            out = run_pallas(cp, mem)
            if squeeze:
                out = out[0]
            return EngineResult(mem=out, cycles=cp.n_cycles,
                                stats=dict(cp.stats), backend="pallas")
        base, variant = ("jax", "auto") if have_jax() else ("numpy", "auto")
        label = f"pallas:fallback-{base}"
    if base == "jax" and not have_jax():
        raise RuntimeError("jax backend requested but jax is not installed")
    B = mem.shape[0]
    if isinstance(faults, FaultModel):
        # FaultModel sampling is chunk-order-dependent: preserve the historic
        # chunk widths so same-seed Monte-Carlo draws stay bit-identical
        step = min(64 if base == "numpy" else WORD_BITS, B)
    else:
        step = B
    if max_batch:
        step = min(step, max(1, int(max_batch)))

    if variant == "auto":
        if isinstance(faults, FaultRealization):
            variant = "fused"        # the only faulty jax path; fine on numpy
        elif cp.schedule is None:
            variant = "unfused"
        elif base == "jax":
            variant = ("unfused" if faults is not None
                       or not jax_fuse_eligible(cp) else "fused")
        else:
            variant = "fused"
    if variant == "fused":
        schedule_for(cp)             # attach on demand for fuse=False traces
    if base == "jax":
        if variant == "fused" and isinstance(faults, FaultModel):
            raise ValueError(
                "jax-fused injects faults via FaultRealization (explicit "
                "per-cycle masks); for FaultModel sampling use backend='jax' "
                "(unfused PRNG path) or a numpy backend")
        if variant == "unfused" and isinstance(faults, FaultRealization):
            raise ValueError(
                "jax-unfused does not take a FaultRealization; use 'jax' "
                "(auto) or 'jax-fused'")
    if isinstance(faults, FaultRealization) and faults.batch != B:
        raise ValueError(
            f"FaultRealization batch {faults.batch} != memory batch {B}; "
            f"sample the realization for the batch it will run under")

    if topo > 1 and base != "jax":
        declined = f"the {base} backend does not shard"
    elif topo > 1:
        from ..distributed.mesh_exec import try_run_sharded
        sharded = try_run_sharded(cp, mem, variant, mesh)
        if sharded is not None:
            out, D, _n = sharded
            if squeeze:
                out = out[0]
            return EngineResult(mem=out, cycles=cp.n_cycles,
                                stats=dict(cp.stats),
                                backend=f"{label}+mesh{D}", faults=faults)
        declined = "the tiles axis does not shard this batch"
    if declined is not None:
        _mesh_declined(mesh_size, declined)

    rng = as_rng(rng) if isinstance(faults, FaultModel) else None
    chunks = []
    for i in range(0, B, step):
        sub = mem[i : i + step]
        f = (faults.narrow(i, i + sub.shape[0])
             if isinstance(faults, FaultRealization) else faults)
        if base == "numpy":
            run = run_numpy_fused if variant == "fused" else _run_numpy
            chunks.append(run(cp, sub, f, rng) if f is not None
                          else run(cp, sub))
        elif variant == "fused":
            chunks.append(build_jax_fused_real(cp)(sub, f)
                          if f is not None
                          else build_jax_fused(cp)(sub))
        else:
            chunks.append(_run_jax(cp, sub, f, rng) if f is not None
                          else _run_jax(cp, sub))
    out = chunks[0] if len(chunks) == 1 else np.concatenate(chunks, axis=0)
    if squeeze:
        out = out[0]
    return EngineResult(mem=out, cycles=cp.n_cycles, stats=dict(cp.stats),
                        backend=label, faults=faults)
