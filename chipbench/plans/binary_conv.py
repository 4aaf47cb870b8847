"""``BinaryConvPlan`` on one crossbar, specialised to the configuration's
kernel; its output map, read for a batch."""
import numpy as np


def make(spec, geometry):
    from repro.core import BinaryConvPlan
    m, n = spec["shape"]
    plan = BinaryConvPlan(m, n, int(spec["k"]), **geometry)
    plan.ensure_program(np.asarray(spec["kernel"]))
    return plan


def decode(spec, plan, mems):
    """``decode_out`` vectorised over the batch, as sign bits (1 for +1)
    packed along the output columns: output row r lies in crossbar row
    r + k - 1 (the counter shift), output column c in partition c // npp."""
    c = np.arange(plan.n_out)
    cols = (c // plan.npp) * plan.cp + np.asarray(plan.out_off)[c % plan.npp]
    r0 = plan.k - 1
    return np.packbits(mems[:, r0:r0 + plan.m_out, cols] > 0, axis=-1)
