"""Trace reduction: busy union, idle share, time by op name, and idle gaps
named by the benchmark's host annotations; and the roofline byte count."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import roofline, tracekit  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def synthetic():
    # window 0..100 ns; device busy 10-30 (two overlapping ops) and 60-70
    return tracekit.Trace(
        devices={"/device:TPU:0": [(10, 25, "%fusion.1 = u32[8] fusion()"),
                                   (20, 30, "%fusion.2 = u32[8] fusion()"),
                                   (60, 70, "%fusion.1 = u32[8] fusion()")]},
        host=[(0, 100, "window"), (0, 50, "execute_batch"),
              (50, 100, "decode-check"), (31, 59, "submit")],
        modules={"/device:TPU:0": [(10, 30, "jit_body"),
                                   (60, 70, "jit_body")]})


def test_merge_and_gaps():
    assert tracekit.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert tracekit.gaps([(1, 4), (5, 8)], 0, 10) == [(0, 1), (4, 5), (8, 10)]
    assert tracekit.clip([(-5, 5), (8, 20)], 0, 10) == [(0, 5), (8, 10)]


def test_reduce_synthetic():
    r = tracekit.reduce(synthetic(), top=10)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)          # union, not the sum
    assert r["idle_pct"] == pytest.approx(70.0)
    assert r["device_ops"][0] == ["%fusion.1", pytest.approx(25e-9)]
    assert r["programs"] == 2 and r["program_s"] == pytest.approx(30e-9)
    # one whole execute_batch call (0-50) holds the program at 10-30
    assert r["calls"] == 1 and r["call_programs"] == 1
    assert r["call_program_s"] == pytest.approx(20e-9)
    assert r["truncated"] is False
    # each gap is named by the annotation that covers most of it
    named = {name: round(s * 1e9) for name, s in r["idle_gaps"]}
    assert named == {"submit": 30,           # 30..60: submit covers 28 ns
                     "decode-check": 30,     # 70..100
                     "execute_batch": 10}    # 0..10
    assert r["idle_gaps"][-1][0] == "execute_batch"   # longest first


def test_reduce_averages_devices():
    t = synthetic()
    t.devices["/device:TPU:1"] = [(0, 100, "fusion.3")]
    r = tracekit.reduce(t)
    assert r["busy_s"] == pytest.approx(65e-9) and r["devices"] == 2
    assert r["idle_pct"] == pytest.approx(35.0)


def test_dropped_buffers_end_the_window():
    t = synthetic()
    t.drops = [65.0]          # the profiler dropped events from 65 ns on
    r = tracekit.reduce(t)
    assert r["truncated"] is True
    assert r["window_s"] == pytest.approx(65e-9)
    assert r["busy_s"] == pytest.approx(25e-9)           # 10-30, 60-65
    assert r["programs"] == 1                            # 60-70 runs past it


def test_window_and_empty_trace_are_errors():
    with pytest.raises(ValueError):
        tracekit.Trace(devices={}, host=[]).window()
    with pytest.raises(ValueError):
        tracekit.reduce(tracekit.Trace(devices={}, host=[(0, 10, "window")]))


def test_recorded_trace_host_annotations():
    # recorded on the CPU: a window with two execute_batch / decode-check
    # pairs; the CPU has no TPU device plane
    t = tracekit.load_xplane(DATA / "cpu_window.xplane.pb")
    names = [n for _, _, n in t.host]
    assert names.count("execute_batch") == 2
    assert names.count("decode-check") == 2
    lo, hi = t.window()
    assert all(lo <= s and e <= hi for s, e, _ in t.host)
    assert t.devices == {}
    cpu = tracekit.load_xplane(DATA / "cpu_window.xplane.pb",
                               device_prefix="/host:CPU")
    assert cpu.devices["/host:CPU"] == []   # no op line on a host plane


def test_roofline_bytes_by_hand():
    from repro.core.compile import compile_program
    from repro.core.isa import ColOp, InitOp, RowOp

    prog = [[InitOp(slice(None), [0, 1], 0)],           # 8 rows x 2 cols
            [ColOp("NOT", (0,), 1, None)],              # (1+1) x 8 rows
            [ColOp("NOR2", (0, 1), 2, slice(0, 4))],    # (2+1) x 4 rows
            [RowOp("NAND2", (0, 1), 2, [0, 1, 2])]]     # (2+1) x 3 cols
    cp = compile_program(prog, 8, 8, 1, 1)
    cells = 8 * 2 + 2 * 8 + 3 * 4 + 3 * 3
    assert roofline.word_bytes(cp) == 4 * cells


@pytest.mark.parametrize("trace,want", [
    # whole calls: their words, however many programs ran in them
    ({"calls": 2, "call_programs": 2, "call_program_s": 1.0,
      "programs": 5, "program_s": 2.0}, (64, 1.0)),
    ({"calls": 2, "call_programs": 5000, "call_program_s": 1.0,
      "programs": 5000, "program_s": 1.0}, (64, 1.0)),
    ({"calls": 1, "call_programs": 0, "call_program_s": 0.0,
      "programs": 0, "program_s": 0.0}, None),
    # the trace ended inside the first call: one program per word
    ({"calls": 0, "call_programs": 0, "call_program_s": 0.0,
      "programs": 25, "program_s": 5.0}, (25, 5.0)),
    ({"calls": 0, "call_programs": 0, "call_program_s": 0.0,
      "programs": 33, "program_s": 5.0}, None),
    ({"calls": 0, "call_programs": 0, "call_program_s": 0.0,
      "programs": 0, "program_s": 0.0}, None),
    (None, None)],
    ids=["calls", "calls-split", "calls-none", "partial", "partial-split",
         "partial-none", "untraced"])
def test_replayed_words(trace, want):
    assert roofline.replayed(trace, 32) == want


def test_peak_table():
    assert roofline.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peak("TPU v9 imaginary")
    assert roofline.roofline_pct(819e9, 2.0, "TPU v5 lite") == \
        pytest.approx(50)


@pytest.mark.parametrize("name", ["device_idle_pct.engine",
                                  "device_idle_pct.serve"])
def test_idle_reader_skips_a_cut_window(name):
    from chipbench import bench
    read = bench.load_reader(name)
    whole = tracekit.reduce(synthetic())
    assert read({"trace": whole}) == pytest.approx(70.0)
    t = synthetic()
    t.drops = [65.0]
    assert read({"trace": tracekit.reduce(t)}) is None
