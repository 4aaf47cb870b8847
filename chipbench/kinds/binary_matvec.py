"""Binary matrix-vector product over ±1 operands: ``sign(A @ x)``.

The guarantee: ties (a zero sum) resolve to +1, the in-array majority
threshold. The control resolves them to -1.
"""
import numpy as np


def operands(spec, rng, batch=()):
    m, n = spec["shape"]
    A = rng.integers(0, 2, size=batch + (m, n), dtype=np.int8) * 2 - 1
    x = rng.integers(0, 2, size=batch + (n,), dtype=np.int8) * 2 - 1
    return A.astype(np.int8), x.astype(np.int8)


def reference(spec, a, b):
    s = np.einsum("...mn,...n->...m", a.astype(np.int32), b.astype(np.int32))
    return np.where(s >= 0, 1, -1).astype(np.int64)


def control(spec, a, b, xp):
    s = xp.einsum("...mn,...n->...m", a.astype(xp.int32), b.astype(xp.int32))
    return xp.where(s > 0, 1, -1)


def exact(spec, values):
    return values.astype(np.int64)


def submit(svc, spec, a, b):
    return svc.submit("binary_matvec", a, b)
