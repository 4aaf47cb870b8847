"""``ConvPlan`` on one crossbar; its output map, read for a batch."""
import numpy as np


def make(spec, geometry):
    from repro.core import ConvPlan
    m, n = spec["shape"]
    return ConvPlan(m, n, int(spec["k"]), int(spec["N"]), **geometry)


def decode(spec, plan, mems):
    """``decode_out`` vectorised over the batch: each output field's bits,
    LSB first, packed into one unsigned 64-bit integer."""
    B, N = mems.shape[0], plan.N
    out = np.zeros((B, plan.m_out, plan.n_out), np.uint64)
    for i in range(plan.alpha):       # column block i lies in row band i
        lo, c0 = plan.band(i)[0], i * plan.nb
        nb = min(plan.nb, plan.n_out - c0)
        if nb <= 0:
            break
        cols = np.asarray(plan.out_fields[:nb]).reshape(-1)
        bits = mems[:, lo:lo + plan.m_out][:, :, cols] \
            .reshape(B, plan.m_out, nb, N)
        packed = np.packbits(bits, axis=-1, bitorder="little")
        packed = np.concatenate(
            [packed, np.zeros(packed.shape[:-1] + (8 - packed.shape[-1],),
                              np.uint8)], axis=-1)
        out[:, :, c0:c0 + nb] = np.ascontiguousarray(packed).view("<u8")[
            ..., 0]
    return out
