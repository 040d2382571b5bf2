"""The benchmark's command: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on the machine that holds the chips the
cell asks for.  Set-up (JAX and the chip, the cell's trace, forest and
traffic, compiling every shape, a second of warm-up traffic) is timed from
the start of this process; then ``--seconds`` of traffic are measured, and
every answer is checked against the plain reference.  The last line of
standard output is the result object; the compared numbers and their
limits are the last lines of standard error.  With ``--trace 1`` the window
is profiled and the per-layer metrics are reported instead of the
end-to-end ones.  Exits non-zero, printing no result, when JAX finds no
TPU or fewer chips than the cell asks for."""

import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(harness.main(t_start=T_START))
