"""Full-precision matrix-vector product over N-bit unsigned operands.

The guarantee: the result is ``A @ x mod 2^(2N)`` exactly. The control
accumulates in 32 bits, as ``jax.numpy`` does with 64-bit mode off.
"""
import numpy as np


def operands(spec, rng, batch=()):
    m, n = spec["shape"]
    top = 1 << int(spec["N"])
    return (rng.integers(0, top, size=batch + (m, n), dtype=np.int64),
            rng.integers(0, top, size=batch + (n,), dtype=np.int64))


def reference(spec, a, b):
    # uint64 arithmetic wraps mod 2^64, a multiple of 2^(2N)
    y = (a.astype(np.uint64) * b[..., None, :].astype(np.uint64)).sum(
        axis=-1, dtype=np.uint64)
    bits = 2 * int(spec["N"])
    return y & np.uint64((1 << bits) - 1) if bits < 64 else y


def control(spec, a, b, xp):
    return (a.astype(xp.uint32) * b[..., None, :].astype(xp.uint32)).sum(
        axis=-1, dtype=xp.uint32)


def exact(spec, values):
    """Exact unsigned integers mod ``2^(2N)`` (the service may return
    Python integers in an object array)."""
    bits = 2 * int(spec["N"])
    if values.dtype == object:
        mask = (1 << bits) - 1
        return np.vectorize(lambda z: int(z) & mask, otypes=[np.uint64])(
            values)
    return values.astype(np.uint64)


def submit(svc, spec, a, b):
    return svc.submit("matvec", a, b, int(spec["N"]))
