"""The command refuses to run without a TPU, and prints no result."""

import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "emr-13.serve-poisson", "--seed", str(2**33 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(cwd: pathlib.Path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def _no_result(proc) -> bool:
    return not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


def test_cpu_only_backend_exits_nonzero_without_a_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "TPU" in proc.stderr


def test_bare_checkout_exits_nonzero_without_a_result(tmp_path):
    # a directory with only BENCHMARK.json and the benchmark's own files
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc)
