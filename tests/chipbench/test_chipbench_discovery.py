"""BENCHMARK.json and the files it names: every cell, mix and per-layer
metric is found by name, and the file keeps to the benchmark's contract."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import bench  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][:2] == ["python3", "chipbench/run.py"]
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_keys():
    names = []
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in names
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    cells = [w["name"] for w in SPEC["workloads"]]
    assert len(cells) == len(set(cells))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(w):
    assert NAME.match(w["name"]) and w["chips"] in (1, 4)
    assert len(w["why"]) <= 200 and "\n" not in w["why"]
    bench.load_config(w["config"])
    mix = bench.load_traffic(w["traffic"])
    assert bench.load_module("runners", mix["loop"]).run
    e2e = bench.end_to_end(SPEC, w["name"])
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert bench.per_layer(SPEC, w["name"])


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file(c):
    assert c["file"] == f"chipbench/configs/{c['name']}.json"
    cfg = json.loads((ROOT / c["file"]).read_text())
    assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    assert cfg["source"] and cfg["assumed"] and cfg["guarantees"]
    assert any(w["config"] == c["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_metric_reader(m):
    read = bench.load_reader(m["name"])
    assert read({}) is None   # nothing to read: the metric is left out
    assert m["workloads"]
    for w in m["workloads"]:
        assert m["moves"] in {e["name"] for e in bench.end_to_end(SPEC, w)}


def test_new_metric_is_found_by_name(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "x.y.py").write_text(
        "def read(ctx):\n    return ctx.get('v')\n")
    assert bench.load_reader("x.y", tmp_path)({"v": 3}) == 3


@pytest.mark.parametrize("kind", bench.NAMED)
def test_new_module_is_found_by_name(tmp_path, kind):
    (tmp_path / kind).mkdir()
    (tmp_path / kind / "new-one.py").write_text("VALUE = 5\n")
    assert bench.load_module(kind, "new-one", tmp_path).VALUE == 5
    with pytest.raises(FileNotFoundError):
        bench.load_module(kind, "absent", tmp_path)


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_request_kinds_resolve(w):
    """Every request kind a cell sends has its module, and an engine cell's
    kind its plan."""
    cfg = bench.load_config(w["config"])
    for spec in cfg.get("requests", []) + [cfg.get("plan")]:
        if spec is None:
            continue
        k = bench.load_module("kinds", spec["op"])
        assert all(callable(getattr(k, f)) for f in
                   ("operands", "reference", "control", "exact", "submit"))
    if "plan" in cfg:
        p = bench.load_module("plans", cfg["plan"]["op"])
        assert callable(p.make) and callable(p.decode)
