"""Pallas executor backend: lower eligible compiled traces onto kernels.

``execute(cp, mem, backend="pallas")`` runs the *algorithm* a trace encodes
— not its cycle-by-cycle gate replay — on the ``repro.kernels`` Pallas tri:

=================  =============================  ==========================
trace kind         kernel                         eligibility
=================  =============================  ==========================
binary matvec      ``binary_matmul``              always (int32 popcount
(±1 XNOR-popcount)  (XNOR + popcount reduction)    reduction is exact)
encoded matvec     ``splitk_matvec``              ``n·(2^N−1)² < 2^24``
(N-bit, mod 2^2N)   (split-K f32 accumulate)       (f32-exact integer range)
valid conv         ``conv2d_shift_tiled``         ``k²·(2^N−1)² < 2^24``;
(N-bit, mod 2^N)    (static tap-shift windows)     K known or stored in-array
=================  =============================  ==========================

The bridge works at the *plan* level: algorithm plans attach a
``pallas_spec`` (layout manifest) to the traces they compile, the backend
extracts operand bits from the INITIAL memory image through that layout,
computes with the kernels (interpret mode off TPU, Mosaic on TPU; see
``kernels.ops.interpret_mode``), and
writes only the plan's result field into an otherwise-zero image. Cycle and
stat accounting still come from the compiled trace — the backend changes
how fast the simulation runs, never what the simulated machine would cost.

Result contract: the plan's decode functions (``decode_y``,
``decode_popcount``, ``decode_out``) read bit-identical values off a pallas
run and an interp/numpy/jax replay — that is what the conformance suite
asserts. Scratch cells (popcount lanes, carry chains, multiplier lanes) are
left zero: they are not part of any plan's observable output.

Arithmetic bridges (why the results are *exactly* equal, not close):

* binary matvec — pad n to the packed word granularity with zero bits in
  BOTH operands (pad positions XNOR-match, so the mismatch count is
  untouched); ``mism = (K_pad − dot)/2``, ``pop = n − mism``, and the
  stored field is ``(pop − n//2) mod 2^W`` — the same two's-complement
  threshold form Phase 5 of the plan program produces.
* encoded matvec / conv — f32 accumulation of non-negative integers is
  exact below 2^24 (the mantissa width); eligibility enforces the bound on
  the *true* sum, the modulus is applied on the host afterwards.

Kernel shapes: every operand is zero-padded to blocks Mosaic accepts (the
(8, 128) tiling, or the whole array), so each shape a spec can carry
compiles for the chip; :func:`kernel_call` lists them and the v5e compile
tests (``tests/test_tpu_compile.py``) compile them for the paper's plans.

Ineligible traces (no spec, fault injection requested, bound exceeded, jax
absent) never error — ``engine.execute`` falls back to the best concrete
backend and labels the result ``"pallas:fallback-<base>"``.
"""
from __future__ import annotations

import importlib.util
from typing import Optional

import numpy as np

from .crossbar import decode_uint, encode_uint

# f32 mantissa: sums of non-negative ints below this are exactly represented
_F32_EXACT = 1 << 24


# ---------------------------------------------------------------------------
# Specs: layout manifests the algorithm plans attach at compile time
# ---------------------------------------------------------------------------


def binary_matvec_spec(plan) -> dict:
    """Layout manifest for :class:`repro.core.binary_matvec.BinaryMatvecPlan`."""
    P, cp, npp = plan.P, plan.cp, plan.npp
    return {
        "kind": "binary_matvec",
        "m": plan.m, "n": plan.n, "W": plan._W,
        # p-major: column j of A lives at a_cols[j] (load_into order)
        "a_cols": np.array([p * cp + plan.a_off[j]
                            for p in range(P) for j in range(npp)]),
        "x_cols": np.array([p * cp + plan.x_off[j]
                            for p in range(P) for j in range(npp)]),
        "total_cols": np.array(plan._total_field),
        "y_col": plan.y_off,
    }


def matvec_spec(plan) -> dict:
    """Layout manifest for :class:`repro.core.matvec.MatvecPlan`."""
    return {
        "kind": "matvec",
        "m": plan.m, "n": plan.n, "N": plan.N, "W": plan.W,
        "alpha": plan.alpha, "nb": plan.nb,
        "a_cols": np.array(plan.a_fields).reshape(-1),   # [j][b] order
        "x_cols": np.array(plan.x_fields).reshape(-1),
        "acc_cols": np.array(plan.acc),
    }


def conv_spec(plan) -> Optional[dict]:
    """Layout manifest for :class:`repro.core.conv.ConvPlan`.

    K-specialized / kernel-streaming programs bake K into the trace — the
    spec captures the bound kernel. Returns ``None`` (ineligible) if such a
    program was built without binding K (the dummy-K ``cycles`` probe).
    """
    k_in_program = plan.specialize or plan.stream_kernel
    if k_in_program and plan.K is None:
        return None
    return {
        "kind": "conv",
        "m": plan.m, "n": plan.n, "k": plan.k, "N": plan.N,
        "alpha": plan.alpha, "nb": plan.nb, "nin": plan.nin,
        "mpad": plan.mpad, "m_out": plan.m_out, "n_out": plan.n_out,
        "a_cols": np.array(plan.a_fields).reshape(-1),   # [e][b] order
        "out_fields": [np.array(f) for f in plan.out_fields],
        "kstore": np.array(plan.kstore, dtype=np.int64),
        "K": plan.K.copy() if k_in_program else None,
    }


# ---------------------------------------------------------------------------
# Eligibility
# ---------------------------------------------------------------------------


def pallas_eligible(cp, faults=None) -> bool:
    """Can ``cp`` run on the pallas backend bit-identically?

    Shape is never the reason to refuse: :func:`kernel_call` pads every
    operand to a block shape Mosaic lowers. What is refused is a trace
    without a spec, a fault run, and a sum the f32 accumulator cannot hold
    exactly.
    """
    spec = getattr(cp, "pallas_spec", None)
    if spec is None or faults is not None:
        return False
    if importlib.util.find_spec("jax") is None:
        return False
    kind = spec["kind"]
    if kind == "binary_matvec":
        return True          # int32 popcount reduction is always exact
    peak = (1 << spec["N"]) - 1
    if kind == "matvec":
        return spec["n"] * peak * peak < _F32_EXACT
    if kind == "conv":
        return spec["k"] ** 2 * peak * peak < _F32_EXACT
    return False


# ---------------------------------------------------------------------------
# Bit plumbing
# ---------------------------------------------------------------------------


def _pad_to(v: int, mult: int) -> int:
    return v if v % mult == 0 else (v // mult + 1) * mult


def _packed_width(n: int) -> int:
    """Packed words ``Kw`` for an ``n``-element operand: ``ceil(n/32)``,
    padded to a multiple of ``binary_matmul``'s k block when it spans more
    than one."""
    from ..kernels.binary_matmul import DEFAULT_BK

    words = -(-n // 32)
    return _pad_to(words, DEFAULT_BK) if words > DEFAULT_BK else words


def _pack_words(bits: np.ndarray) -> np.ndarray:
    """(…, n) {0,1} → (…, Kw) uint32, little-endian bit order, zero-padded
    to :func:`_packed_width` so ``binary_matmul``'s k block is the whole
    ``Kw`` or a multiple of 128 words.

    Same word convention as the engine's canonical packed layout
    (``engine.WORD_BITS`` = 32, bit ``b`` of word ``w`` = element
    ``32w + b``), just packed along the operand axis instead of the batch.
    """
    n = bits.shape[-1]
    words = _packed_width(n)
    pad = words * 32 - n
    if pad:
        z = np.zeros(bits.shape[:-1] + (pad,), dtype=bits.dtype)
        bits = np.concatenate([bits, z], axis=-1)
    w = bits.reshape(bits.shape[:-1] + (words, 32)).astype(np.uint32)
    return (w << np.arange(32, dtype=np.uint32)).sum(
        axis=-1, dtype=np.uint32)


def _write_field(mem: np.ndarray, rows: int, cols: np.ndarray,
                 values: np.ndarray) -> None:
    """Write ``values`` (ints, shape (rows,)) LSB-first into ``cols``."""
    mem[:rows, cols] = encode_uint(values, len(cols))


def _kernel_shapes(spec) -> tuple:
    """Padded operand shapes of the kernel call :func:`run_pallas` makes for
    one instance of ``spec``: every block is a whole array or a multiple of
    the (8, 128) tiling, which is what Mosaic lowers."""
    kind, m, n = spec["kind"], spec["m"], spec["n"]
    if kind == "binary_matvec":                        # binary_matmul
        kw = _packed_width(n)
        return (_pad_to(m, 128) if m > 128 else m, kw), (1, kw)
    if kind == "matvec":                               # splitk_matvec
        kcols = _pad_to(n, 512) if n > 512 else n
        return (_pad_to(m, 256) if m > 256 else m, kcols), (kcols,)
    k = spec["k"]                                      # conv2d_shift_tiled
    oh, ow = (_pad_to(v, 128) if v > 128 else v
              for v in (m - k + 1, n - k + 1))
    return (oh + k - 1, ow + k - 1), (k, k)


def kernel_call(spec) -> tuple:
    """``(kernel, ((shape, dtype), ...))``: the Pallas kernel and operand
    shapes :func:`run_pallas` passes per instance of ``spec``."""
    import jax.numpy as jnp

    from ..kernels.binary_matmul import binary_matmul
    from ..kernels.conv2d_shift import conv2d_shift_tiled
    from ..kernels.splitk_matvec import splitk_matvec

    kind = spec["kind"]
    fn = {"binary_matvec": binary_matmul, "matvec": splitk_matvec,
          "conv": conv2d_shift_tiled}[kind]
    dt = jnp.uint32 if kind == "binary_matvec" else jnp.float32
    return fn, tuple((shape, dt) for shape in _kernel_shapes(spec))


def _padded(a: np.ndarray, shape: tuple, dtype) -> np.ndarray:
    """``a`` zero-padded at the end of each axis to ``shape``."""
    out = np.zeros(shape, dtype)
    out[tuple(slice(0, d) for d in a.shape)] = a
    return out


# ---------------------------------------------------------------------------
# Per-kind lowerings (operate on ONE instance's initial image)
# ---------------------------------------------------------------------------


def _run_binary_matvec(spec, mems: np.ndarray, interpret: bool) -> np.ndarray:
    from ..kernels.binary_matmul import binary_matmul

    m, n, W = spec["m"], spec["n"], spec["W"]
    a_bits = mems[:, :m][:, :, spec["a_cols"]]         # (B, m, n)
    x_bits = mems[:, 0][:, spec["x_cols"]]             # (B, n)
    a_packed = _pack_words(a_bits)                     # (B, m, Kw)
    x_packed = _pack_words(x_bits)[:, None, :]         # (B, 1, Kw)
    kpad = a_packed.shape[-1] * 32
    a_shape, _ = _kernel_shapes(spec)

    out = np.zeros_like(mems)
    for b in range(mems.shape[0]):
        ap = _padded(a_packed[b], a_shape, np.uint32)
        dot = np.asarray(binary_matmul(ap, x_packed[b],
                                       interpret=interpret))[:m, 0]
        mism = (kpad - dot.astype(np.int64)) // 2      # pad bits all match
        total = (n - mism - n // 2) % (1 << W)         # pop − n/2, mod 2^W
        _write_field(out[b], m, spec["total_cols"], total)
        out[b, :m, spec["y_col"]] = 1 - ((total >> (W - 1)) & 1)
    return out


def _decode_fields(mems: np.ndarray, rows, cols: np.ndarray,
                   N: int) -> np.ndarray:
    """(B, |rows|, len(cols)) bit block → (B, |rows|, len(cols)//N) ints."""
    bits = mems[:, rows][:, :, cols]
    B, R = bits.shape[0], bits.shape[1]
    return decode_uint(bits.reshape(B, R, -1, N))


def _run_matvec(spec, mems: np.ndarray, interpret: bool) -> np.ndarray:
    from ..kernels.splitk_matvec import splitk_matvec

    m, n, N, W = spec["m"], spec["n"], spec["N"], spec["W"]
    alpha, nb = spec["alpha"], spec["nb"]
    B = mems.shape[0]
    A = np.zeros((B, m, n), dtype=np.int64)
    x = np.zeros((B, n), dtype=np.int64)
    for i in range(alpha):
        sl = slice(i * m, (i + 1) * m)
        A[:, :, i * nb:(i + 1) * nb] = _decode_fields(
            mems, sl, spec["a_cols"], N)
        x[:, i * nb:(i + 1) * nb] = decode_uint(
            mems[:, i * m][:, spec["x_cols"]].reshape(B, nb, N))

    a_shape, x_shape = _kernel_shapes(spec)
    out = np.zeros_like(mems)
    for b in range(B):
        af = _padded(A[b], a_shape, np.float32)
        xf = _padded(x[b], x_shape, np.float32)
        y = np.asarray(splitk_matvec(af, xf, interpret=interpret))[:m]
        y = np.rint(y).astype(np.int64) % (1 << W)     # exact (< 2^24)
        _write_field(out[b], m, spec["acc_cols"], y)
    return out


def _run_conv(spec, mems: np.ndarray, interpret: bool) -> np.ndarray:
    from ..kernels.conv2d_shift import conv2d_shift_tiled

    m, n, k, N = spec["m"], spec["n"], spec["k"], spec["N"]
    alpha, nb, nin, mpad = (spec["alpha"], spec["nb"], spec["nin"],
                            spec["mpad"])
    m_out, n_out = spec["m_out"], spec["n_out"]
    B = mems.shape[0]

    A = np.zeros((B, m, n), dtype=np.int64)
    for i in range(alpha):
        lo = i * mpad
        blk = _decode_fields(mems, slice(lo, lo + m), spec["a_cols"], N)
        c0 = i * nb
        valid = min(nin, n - c0)
        if valid > 0:
            A[:, :, c0:c0 + valid] = blk[:, :, :valid]  # halo overlaps agree

    if spec["K"] is not None:
        Ks = np.broadcast_to(spec["K"], (B, k, k))
    else:
        # K bits live in-array (kstore, band-replicated): bit β of the flat
        # LSB-first kernel stream sits at (row β % m, col kstore[β // m]) —
        # read band 0 per instance (serving can batch distinct kernels)
        beta = np.arange(k * k * N)
        kb = mems[:, beta % m, spec["kstore"][beta // m]]    # (B, k²·N)
        Ks = decode_uint(kb.reshape(B, k * k, N)).reshape(B, k, k)

    a_shape, _ = _kernel_shapes(spec)
    out = np.zeros_like(mems)
    for b in range(B):
        o = np.asarray(conv2d_shift_tiled(_padded(A[b], a_shape, np.float32),
                                          Ks[b].astype(np.float32),
                                          interpret=interpret))
        o = o[:m - k + 1, :n - k + 1]                  # crop the padding
        o = np.rint(o).astype(np.int64) % (1 << N)     # exact (< 2^24)
        for i in range(alpha):
            lo = i * mpad
            for c in range(nb):
                col = i * nb + c
                if col >= n_out:
                    break
                _write_field(out[b, lo:], m_out, spec["out_fields"][c],
                             o[:, col])
    return out


_RUNNERS = {
    "binary_matvec": _run_binary_matvec,
    "matvec": _run_matvec,
    "conv": _run_conv,
}


def run_pallas(cp, mems: np.ndarray) -> np.ndarray:
    """Run an eligible trace's algorithm on the Pallas kernels.

    ``mems`` is ``(B, rows, cols)`` uint8 initial state; returns the final
    image per the result contract above (result field populated, scratch
    zero). Caller (``engine.execute``) checks :func:`pallas_eligible` first.
    """
    from ..kernels.ops import interpret_mode

    spec = cp.pallas_spec
    mems = np.ascontiguousarray(mems, dtype=np.uint8)
    return _RUNNERS[spec["kind"]](spec, mems, interpret=interpret_mode())


__all__ = [
    "binary_matvec_spec", "conv_spec", "kernel_call", "matvec_spec",
    "pallas_eligible", "run_pallas",
]
