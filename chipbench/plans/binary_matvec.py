"""``BinaryMatvecPlan`` on one crossbar; its result bit, read for a batch."""
import numpy as np


def make(spec, geometry):
    from repro.core import BinaryMatvecPlan
    m, n = spec["shape"]
    return BinaryMatvecPlan(m, n, **geometry)


def decode(spec, plan, mems):
    """``decode_y`` vectorised over the batch: the sign bit in ``y_off``."""
    return np.where(mems[:, :plan.m, plan.y_off] > 0, 1, -1).astype(np.int64)
