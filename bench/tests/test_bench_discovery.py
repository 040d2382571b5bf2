"""A cell, configuration, traffic mix and metric added as new files (and
new entries in BENCHMARK.json) are found by name, with no edit to any
existing file of the harness."""

import json
import pathlib
import shutil
import time

import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture(autouse=True)
def cpu_as_the_chip(monkeypatch):
    """The harness's look for a chip, passed on the CPU."""
    def device_info(chips):
        import jax
        d = jax.devices()[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices())}
    monkeypatch.setattr(harness, "device_info", device_info)


def _root_with_new_cell(tmp_path: pathlib.Path) -> pathlib.Path:
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "emr-13-smoke", "source": "test",
                            "file": "bench/configs/emr-13-smoke.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "emr-13-smoke.serve-trickle",
                              "config": "emr-13-smoke",
                              "traffic": "serve-trickle", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "requests_per_flush", "unit": "req",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "broker", "moves": "decision_p50_ms",
                              "workloads": ["emr-13-smoke.serve-trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    config = json.loads((ROOT / "bench/configs/emr-13.json").read_text())
    config.update(name="emr-13-smoke", workload="smoke")
    config["demand"]["sizes"] = {"map": {"1": 6, "13": 1}, "reduce": {"2": 3}}
    (tmp_path / "bench/configs/emr-13-smoke.json").write_text(
        json.dumps(config))
    (tmp_path / "bench/traffic/serve-trickle.json").write_text(json.dumps({
        "driver": "open_loop", "why": "test", "clients": 2,
        "arrivals": {"process": "poisson"}, "rate_rps": 200.0}))
    (tmp_path / "bench/metrics/requests_per_flush.py").write_text(
        "def read(ctx):\n"
        "    c = ctx['window']['counters']\n"
        "    return c['requests'] / c['flushes'] if c['flushes'] else None\n")
    return tmp_path


def test_new_files_are_found_by_name(tmp_path):
    root = _root_with_new_cell(tmp_path)
    spec, cell, config, traffic = harness.cell_files(
        "emr-13-smoke.serve-trickle", root)
    assert config["workload"] == "smoke" and traffic["rate_rps"] == 200.0
    e2e = [m["name"] for m in harness.cell_metrics(spec, cell["name"], False)]
    assert e2e == [m["name"] for m in spec["end_to_end"]]
    layer = [m["name"] for m in harness.cell_metrics(spec, cell["name"], True)]
    assert layer == ["requests_per_flush"]
    assert callable(harness.reader("requests_per_flush", root))


def test_new_cell_runs_end_to_end(tmp_path):
    root = _root_with_new_cell(tmp_path)
    out = harness.run("emr-13-smoke.serve-trickle", 2**33 + 5, 0.5, False,
                      t_start=time.perf_counter(), root=root)
    assert out["correct"] and out["attempted"] > 0
    spec = json.loads((root / "BENCHMARK.json").read_text())
    assert set(out["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert list(out)[-1] == "checks"
