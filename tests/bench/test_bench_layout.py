"""BENCHMARK.json and the files it names: every cell finds its config,
traffic, driver and metric readers by name, and every name keeps to the
benchmark's character rules."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BM["workloads"]]


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"}
    assert BM["command"][:2] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench", "tests/bench"]
    assert 1 <= BM["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist(cell):
    w = next(x for x in BM["workloads"] if x["name"] == cell)
    assert (ROOT / "bench" / "configs" / f"{w['config']}.json").is_file()
    assert (ROOT / "bench" / "configs" / f"{w['config']}.py").is_file()
    traffic = json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    assert (ROOT / "bench" / "drivers" / f"{traffic['driver']}.py").is_file()
    assert traffic["limits"]
    assert w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_required_metrics(cell):
    e2e = [m for m in BM["end_to_end"] if cell in m.get("workloads", [cell])]
    assert "setup_s" in [m["name"] for m in e2e]
    assert len(e2e) >= 2
    layer = [m for m in BM["per_layer"] if cell in m.get("workloads", [cell])]
    assert layer
    for m in layer:
        assert m["moves"] in [x["name"] for x in e2e]


@pytest.mark.parametrize("metric", [m["name"] for m in BM["per_layer"]])
def test_per_layer_reader_exists(metric):
    path = ROOT / "bench" / "metrics" / f"{metric}.py"
    assert path.is_file()
    assert "def read(ctx)" in path.read_text()


def test_names_and_units():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BM[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    metric_names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for c in BM["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/")


def test_bounds():
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert next(m for m in BM["end_to_end"] if m["name"] == "setup_s")["bound"] <= 0.25


def test_at_most_half_of_cells_on_four_chips():
    four = sum(w["chips"] == 4 for w in BM["workloads"])
    assert four <= max(1, len(CELLS) // 2)
