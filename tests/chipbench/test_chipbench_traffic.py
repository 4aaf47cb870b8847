"""The traffic generator: a seed repeats exactly, seeds differ, and every
seed draws the same work in another order."""
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import bench, ops, traffic  # noqa: E402

MIX = {"loop": "open", "arrivals": "poisson", "rate_per_s": 4.0,
       "max_units": 64,
       "mix": {"bmv": 50, "bmv-tiled": 20, "mv": 25, "conv": 5}}
BIG = 2**40 + 17   # seeds beyond 32 bits


def test_schedule_repeats_for_a_seed():
    assert traffic.open_schedule(MIX, 30, BIG) == \
        traffic.open_schedule(MIX, 30, BIG)


def test_schedule_differs_across_seeds_with_the_same_work():
    a = traffic.open_schedule(MIX, 30, BIG)
    b = traffic.open_schedule(MIX, 30, BIG + 1)
    assert a != b
    assert Counter(k for _, k in a) == Counter(k for _, k in b)
    assert Counter(k for _, k in a) == {"bmv": 60, "bmv-tiled": 24,
                                        "mv": 30, "conv": 6}
    gaps = [np.diff([0.0] + [t for t, _ in s]) for s in (a, b)]
    assert np.allclose(sorted(gaps[0]), sorted(gaps[1]))
    assert all(0 < t < 30 for t, _ in a)
    assert a[-1][0] == pytest.approx(b[-1][0])


def test_kinds_keep_proportion():
    kinds = traffic.kinds_for({"a": 1, "b": 2}, 10)
    assert Counter(kinds) in ({"a": 3, "b": 7}, {"a": 4, "b": 6})
    assert len(traffic.kinds_for(MIX["mix"], 7)) == 7


@pytest.mark.parametrize("spec", [
    {"op": "binary_matvec", "shape": [8, 12]},
    {"op": "matvec", "shape": [8, 4], "N": 32}])
def test_operands_repeat_and_differ(spec):
    one = ops.operands(spec, traffic.rng(BIG, traffic.STREAM_OPERANDS))
    two = ops.operands(spec, traffic.rng(BIG, traffic.STREAM_OPERANDS))
    other = ops.operands(spec, traffic.rng(BIG + 1, traffic.STREAM_OPERANDS))
    assert all(np.array_equal(p, q) for p, q in zip(one, two))
    assert not all(np.array_equal(p, q) for p, q in zip(one, other))


@pytest.mark.parametrize("name", ["mc1024"])
def test_traffic_files_load(name):
    t = bench.load_traffic(name)
    assert bench.load_module("runners", t["loop"]).run
    if "arrivals" in t:
        assert len(traffic.open_schedule(t, 2.0, BIG)) == \
            round(2 * t["rate_per_s"])


def test_unknown_loop_is_refused(tmp_path):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "x.json").write_text('{"loop": "bursty"}')
    with pytest.raises(FileNotFoundError):
        bench.load_traffic("x", tmp_path)
