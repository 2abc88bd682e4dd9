"""`BENCHMARK.json` against the files under `benchmark/` it names."""

import json
import os
import re

import pytest

from benchmark import chip

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


MANIFEST = load("BENCHMARK.json")
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def cells_of(metric):
    return set(metric.get("workloads")
               or [w["name"] for w in MANIFEST["workloads"]])


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_cell_has_its_files(cell):
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert len(cell["why"]) <= 200 and cell["chips"] in (1, 4)
    spec = load("benchmark", "workloads", f"{cell['name']}.json")
    for key in ("name", "config", "traffic", "chips", "why"):
        assert spec[key] == cell[key], key
    config = load("benchmark", "configs", f"{cell['config']}.json")
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == cell["config"])
    assert entry["file"] == f"benchmark/configs/{cell['config']}.json"
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    load("benchmark", "traffic", f"{cell['traffic']}.json")
    for kind in ("runners", "adapters"):
        key = kind[:-1]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", kind, f"{config[key]}.py"))
    # what the cell's file says it reports is what the manifest says
    for group in ("end_to_end", "per_layer"):
        listed = {m["name"] for m in MANIFEST[group]
                  if cell["name"] in cells_of(m)}
        assert set(spec[group]) == listed, group
    assert "setup_s" in spec["end_to_end"] and len(spec["end_to_end"]) > 1


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_has_its_file(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    spec = load("benchmark", "metrics", f"{metric['name']}.json")
    for key in ("name", "unit", "better", "source"):
        assert spec[key] == metric[key], key
    if "moves" not in metric:       # end to end
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        return
    assert spec["layer"] == metric["layer"]
    module, fn = spec["reader"].rsplit(".", 1)
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "readers", f"{module}.py"))
    # the metric it should move is reported wherever it is
    moved = next(m for m in MANIFEST["end_to_end"]
                 if m["name"] == metric["moves"])
    assert cells_of(metric) <= cells_of(moved)


def test_unknown_device_kind_raises():
    assert chip.peak_for("TPU v5 lite", ROOT)["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit, match="peaks.json"):
        chip.peak_for("TPU v9 imaginary", ROOT)


def test_four_chip_cells_are_at_most_a_quarter():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 4)
