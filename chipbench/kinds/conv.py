"""Full-precision 2D convolution over N-bit unsigned operands: an (m, n)
image A and a (k, k) kernel K, as MatPIM §III computes it (valid
cross-correlation, the kernel not flipped).

The guarantee: ``Out[r, c] = sum over v, h of A[r+v, c+h] * K[v, h]
mod 2^N`` exactly, for every output of the (m-k+1, n-k+1) map. The control
flips the kernel: the true convolution, what a program reading the kernel
in the wrong order gives. (A wrap at 32 bits is the guarantee itself at
N=32, so it cannot serve as the control.)
"""
import numpy as np


def operands(spec, rng, batch=()):
    m, n = spec["shape"]
    k, top = int(spec["k"]), 1 << int(spec["N"])
    return (rng.integers(0, top, size=batch + (m, n), dtype=np.int64),
            rng.integers(0, top, size=batch + (k, k), dtype=np.int64))


def _correlate(a, kern, k, acc):
    """Valid cross-correlation of the last two axes, summed in ``acc``."""
    mo, no = a.shape[-2] - k + 1, a.shape[-1] - k + 1
    out = 0
    for v in range(k):
        for h in range(k):
            out = out + a[..., v:v + mo, h:h + no].astype(acc) \
                * kern[..., v, h, None, None].astype(acc)
    return out


def reference(spec, a, b):
    # uint64 arithmetic wraps mod 2^64, a multiple of 2^N
    out = _correlate(np.asarray(a), np.asarray(b), int(spec["k"]), np.uint64)
    return out & np.uint64((1 << int(spec["N"])) - 1)


def control(spec, a, b, xp):
    # uint32 sums are exact mod 2^N for N <= 32; only the orientation breaks
    out = _correlate(a, xp.flip(b, axis=(-2, -1)), int(spec["k"]), xp.uint32)
    return out & xp.uint32((1 << int(spec["N"])) - 1)


def exact(spec, values):
    """Exact unsigned integers mod ``2^N`` (the service may return Python
    integers in an object array)."""
    if values.dtype == object:
        mask = (1 << int(spec["N"])) - 1
        return np.vectorize(lambda z: int(z) & mask, otypes=[np.uint64])(
            values)
    return values.astype(np.uint64)


def submit(svc, spec, a, b):
    return svc.submit("conv", a, b, int(spec["N"]))
