"""BENCHMARK.json is whole: every name resolves to its file, and every
metric is reported where it says."""

import json
import pathlib
import re

import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_names_and_files():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for entry in SPEC["configs"] + SPEC["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"] == f"bench/configs/{c['name']}.json"
    for w in SPEC["workloads"]:
        assert (ROOT / "bench/traffic" / f"{w['traffic']}.json").is_file()
        assert w["config"] in {c["name"] for c in SPEC["configs"]}
    for m in metrics:
        assert callable(harness.reader(m["name"]))
    assert len({m["name"] for m in metrics}) == len(metrics)


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_enough(cell):
    e2e = {m["name"] for m in harness.cell_metrics(SPEC, cell, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = harness.cell_metrics(SPEC, cell, True)
    assert layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
