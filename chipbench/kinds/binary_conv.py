"""Binary 2D convolution over ±1 values stored as bits (0 for -1, 1 for
+1): an (m, n) frame A and the configuration's (k, k) kernel K, as MatPIM
§III-C computes it (valid cross-correlation, the kernel not flipped),
quantised to its sign.

The guarantee: ``Out[r, c] = sign(sum over v, h of A[r+v, c+h] * K[v, h])``
for every output of the (m-k+1, n-k+1) map, a zero sum going to +1. A
result is its sign bits (1 for +1) packed along the output columns, as
``np.packbits`` packs them. The kernel is the deployment's, one for every
frame, and is not drawn. The control flips it: the true convolution, what
a program reading the kernel in the wrong order gives.
"""
import numpy as np

BLOCK = 16      # frames per block of the reference: its sums stay small


def operands(spec, rng, batch=()):
    m, n = spec["shape"]
    k = int(spec["k"])
    kern = np.asarray(spec["kernel"])
    if kern.shape != (k, k) or not np.isin(kern, (-1, 1)).all():
        raise ValueError(f"kernel must be a {k}x{k} array of -1 and +1")
    return (rng.integers(0, 2, size=batch + (m, n), dtype=np.uint8),
            np.broadcast_to((kern > 0).astype(np.uint8), batch + (k, k)))


def _signs(a, kern, k, acc):
    """Sign bits of the valid ±1 cross-correlation of bit frames ``a`` with
    bit kernels ``kern`` over the last two axes, summed in ``acc``."""
    s, w = 2 * a.astype(acc) - 1, 2 * kern.astype(acc) - 1
    mo, no = a.shape[-2] - k + 1, a.shape[-1] - k + 1
    out = 0
    for v in range(k):
        for h in range(k):
            out = out + s[..., v:v + mo, h:h + no] * w[..., v, h, None, None]
    return out >= 0


def reference(spec, a, b):
    a, b, k = np.asarray(a), np.asarray(b), int(spec["k"])
    lead = a.shape[:-2]
    a = a.reshape((-1,) + a.shape[-2:])
    b = np.broadcast_to(b, lead + (k, k)).reshape(-1, k, k)
    out = np.concatenate([
        np.packbits(_signs(a[i:i + BLOCK], b[i:i + BLOCK], k, np.int16),
                    axis=-1)
        for i in range(0, len(a), BLOCK)])
    return out.reshape(lead + out.shape[1:])


def control(spec, a, b, xp):
    return xp.packbits(
        _signs(a, xp.flip(b, axis=(-2, -1)), int(spec["k"]), xp.int32),
        axis=-1)


def exact(spec, values):
    """Packed sign bits; the service's ±1 map is packed here."""
    if values.dtype == np.uint8:
        return values
    return np.packbits(values > 0, axis=-1)


def submit(svc, spec, a, b):
    return svc.submit_binary_conv(a, 2 * np.asarray(b, np.int64) - 1)
