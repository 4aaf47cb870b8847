"""``MatvecPlan`` on one crossbar; its accumulator, read for a batch."""
import numpy as np


def make(spec, geometry):
    from repro.core import MatvecPlan
    m, n = spec["shape"]
    return MatvecPlan(m, n, int(spec["N"]), int(spec.get("alpha", 1)),
                      **geometry)


def decode(spec, plan, mems):
    """``decode_y`` vectorised over the batch: the accumulator's bits, LSB
    first, packed into one unsigned 64-bit integer per row."""
    bits = mems[:, :plan.m][:, :, np.asarray(plan.acc)]
    pad = 64 - bits.shape[-1]
    if pad:
        bits = np.concatenate(
            [bits, np.zeros(bits.shape[:-1] + (pad,), np.uint8)], axis=-1)
    packed = np.packbits(bits, axis=-1, bitorder="little")
    return np.ascontiguousarray(packed).view("<u8")[..., 0]
