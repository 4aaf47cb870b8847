"""Peak table and the replay's roofline byte count.

The peaks are keyed by ``jax.Device.device_kind``; a device that is not in
the table is an error, never a default. The byte count is the least traffic
a replay of one packed word (32 crossbars) must move through device memory,
counted from the compiled trace alone: every gate op reads ``arity`` input
lines and writes one output line over the cells its write mask selects, and
every init rectangle writes its cells, each cell one 4-byte word.
"""
from __future__ import annotations

import numpy as np

# Google Cloud documentation, "TPU v5e" (system architecture table):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}

# inputs per FELIX gate (MatPIM/FELIX gate set); outputs are always one line
ARITY = {"NOT": 1, "OR2": 2, "NOR2": 2, "NOR3": 3, "NAND2": 2, "MIN3": 3,
         "MIN5": 5, "OAI3": 3}

WORD_BYTES = 4   # one packed cell: a uint32 holding 32 crossbars


def peak(device_kind: str) -> dict:
    """The peak row for ``device_kind``; raises ``KeyError`` if unknown."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak table entry for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def word_bytes(cp) -> int:
    """Bytes one packed word's replay of ``cp`` must read and write."""
    from repro.core.compile import GATE_IDS, MODE_COL, MODE_INIT, MODE_ROW

    arity = np.zeros(len(GATE_IDS), np.int64)
    for name, gid in GATE_IDS.items():
        arity[gid] = ARITY[name]
    rsel = cp.row_masks[:, :cp.rows].sum(axis=1).astype(np.int64)
    csel = cp.col_masks[:, :cp.cols].sum(axis=1).astype(np.int64)
    live = np.arange(cp.gate.shape[1])[None, :] < cp.nops[:, None]
    cells = 0
    for mode, lines in ((MODE_COL, rsel), (MODE_ROW, csel)):
        on = (cp.mode == mode)[:, None] & live
        # mask ids index the row pool in column mode, the column pool in row
        # mode; id 0 (all-False) stands in for the other mode's cycles
        gates = np.where(on, cp.gate, 0).astype(np.int64)
        sel = np.where(on, cp.sel, 0)
        cells += int(((arity[gates] + 1) * lines[sel] * on).sum())
    init = (cp.mode == MODE_INIT)[:, None]
    cells += int((rsel[cp.init_r] * csel[cp.init_c] * init).sum())
    return cells * WORD_BYTES


def replayed(trace, words_per_call: int):
    """``(packed words, device seconds)`` of the replay in a traced engine
    window, or ``None`` where the trace cannot tell.

    Where the window holds whole ``execute_batch`` calls, the words are
    theirs and the seconds those of every program that ran inside them,
    whatever the number of programs per word. Where the trace ended inside
    the first call, each whole program counts as one word, the replay's
    structure today; more programs than the call has words shows that this
    no longer holds, and nothing is read.
    """
    if trace is None or not words_per_call:
        return None
    if trace["calls"]:
        if not trace["call_programs"]:
            return None
        return words_per_call * trace["calls"], trace["call_program_s"]
    if 0 < trace["programs"] <= words_per_call:
        return trace["programs"], trace["program_s"]
    return None


def roofline_pct(bytes_moved: float, seconds: float,
                 device_kind: str) -> float:
    """Share of the memory roofline: least time for ``bytes_moved`` at the
    peak bandwidth over the measured ``seconds``, in percent."""
    return 100.0 * bytes_moved / peak(device_kind)["hbm_bytes_per_s"] / seconds
