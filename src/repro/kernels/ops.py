"""Jit'd public wrappers around the Pallas kernels (+ CPU fallbacks).

On TPU the kernels compile to Mosaic and ``use_pallas`` defaults on. On any
other platform Pallas only *interprets* — far slower than the jnp ``ref``
fallbacks — so the default follows :func:`interpret_mode` and dispatches to
``ref`` off-TPU; pass ``use_pallas=True`` explicitly to force
interpret-mode Pallas (the kernel test suites do). The wrappers are what
models/ and the serving engine call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .binary_matmul import binary_matmul
from .conv2d_shift import binary_conv2d, conv2d_shift, conv2d_shift_tiled
from .splitk_matvec import splitk_matvec

pack_bits = ref.pack_bits


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode: exactly when the
    default platform is not a TPU. The one place that decides it."""
    return jax.default_backend() != "tpu"


def as_packed_words(w) -> jnp.ndarray:
    """Reinterpret a packed-bit word array as the uint32 words kernels take.

    The simulator packs bits into whatever unsigned word width fits the
    batch (``core.engine._pack``: uint8/16/32/64); the Pallas kernels
    consume uint32 lanes. Feeding a uint64 array straight to ``jnp.asarray``
    under disabled x64 silently truncates to 32 bits — half the packed bits
    vanish without an error. This helper instead *views* the underlying
    bytes as little-endian uint32 (bit k of the wide word stays bit k of
    the word stream), so any unsigned width is accepted with zero copies on
    the hot path and no repack.

    Signed arrays are rejected outright: an int32/int64 "packed" array is
    almost always an accidental upcast, and reinterpreting sign bits as
    payload would corrupt popcounts silently.
    """
    if isinstance(w, jnp.ndarray):
        if w.dtype == jnp.uint32:
            return w
        w = np.asarray(w)
    arr = np.asarray(w)
    if arr.dtype == np.uint32:
        return jnp.asarray(arr)
    if arr.dtype.kind != "u":
        raise TypeError(
            f"packed words must be unsigned (uint8/16/32/64), got "
            f"{arr.dtype}; an int32/int64 array here usually means an "
            f"accidental repack — view/cast it as unsigned upstream")
    if arr.ndim == 0 or (arr.shape[-1] * arr.dtype.itemsize) % 4:
        raise ValueError(
            f"last axis of {arr.dtype} shape {arr.shape} is not a whole "
            f"number of 32-bit words")
    le = np.ascontiguousarray(arr.astype(arr.dtype.newbyteorder("<"),
                                         copy=False))
    return jnp.asarray(le.view(np.dtype("<u4")))


def binary_dense(x: jnp.ndarray, w_packed: jnp.ndarray, K: int,
                 use_pallas: bool | None = None) -> jnp.ndarray:
    """±1 dense layer: x (..., K) real → sign-binarized → XNOR-GEMM vs packed
    weights w (N, K/32). Returns (..., N) int32 ±1 dot values.

    Straight-through binarization of activations; weights pre-packed.
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)
    xp = pack_bits(x2, axis=-1)
    w_packed = as_packed_words(w_packed)
    if use_pallas is None:
        use_pallas = not interpret_mode()
    if use_pallas:
        y = binary_matmul(xp, w_packed, interpret=interpret_mode())
    else:
        y = ref.binary_matmul_packed_ref(xp, w_packed, K)
    return y.reshape(*lead, -1)


def matvec(a: jnp.ndarray, x: jnp.ndarray, use_pallas: bool | None = None
           ) -> jnp.ndarray:
    if use_pallas is None:
        use_pallas = not interpret_mode()
    if use_pallas:
        return splitk_matvec(a, x, interpret=interpret_mode())
    return ref.splitk_matvec_ref(a, x)


def conv2d(a: jnp.ndarray, k: jnp.ndarray, tiled: bool = False,
           use_pallas: bool | None = None) -> jnp.ndarray:
    if use_pallas is None:
        use_pallas = not interpret_mode()
    if not use_pallas:
        return ref.conv2d_shift_ref(a, k)
    fn = conv2d_shift_tiled if tiled else conv2d_shift
    return fn(a, k, interpret=interpret_mode())


def conv2d_binary(a_packed: jnp.ndarray, k_packed: jnp.ndarray,
                  use_pallas: bool | None = None) -> jnp.ndarray:
    a_packed = as_packed_words(a_packed)
    k_packed = as_packed_words(k_packed)
    if use_pallas is None:
        use_pallas = not interpret_mode()
    if use_pallas:
        return binary_conv2d(a_packed, k_packed, interpret=interpret_mode())
    return ref.binary_conv2d_ref(a_packed, k_packed)
