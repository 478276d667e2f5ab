"""Every piece BENCHMARK.json names is a file of its own, found by name,
and each metric reader says what BENCHMARK.json says of its metric."""

import json
from pathlib import Path

import pytest

import smoke
from harness import load_module, resolve

CHIP = Path(__file__).resolve().parents[1]
BENCH = json.loads((smoke.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_files(cell):
    found = resolve(BENCH, cell)
    assert found["config"]["name"] == found["cell"]["config"]
    assert (CHIP / "drivers" / f"{found['traffic']['driver']}.py").exists()
    assert (CHIP / "traffic" / f"{found['traffic']['generator']}.py").exists()
    assert found["limits"]["checks"]
    entry = next(c for c in BENCH["configs"] if c["name"] == found["cell"]["config"])
    assert sorted(entry["reduced"]) == sorted(found["config"]["reduced"])
    assert entry["source"] == found["config"]["source"]


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_metric_reader(metric):
    mod = load_module(CHIP / "metrics" / f"{metric['name']}.py")
    assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
        metric["layer"], metric["unit"], metric["moves"], metric["source"])
    reported = {m["name"] for m in BENCH["end_to_end"]
                if set(metric["workloads"]) <= set(m.get("workloads", metric["workloads"]))}
    assert metric["moves"] in reported
