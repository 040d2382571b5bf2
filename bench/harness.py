"""Runs one cell of ``BENCHMARK.json`` and prints its result line.

Everything a cell needs is found by name: the cell's entry in
``BENCHMARK.json`` names its configuration (``bench/configs/<config>.json``)
and its traffic mix (``bench/traffic/<traffic>.json``); the mix names the
driver that serves it (``DRIVERS``); every metric is read by
``bench/metrics/<metric>.py``, whose ``read(ctx)`` returns a number or
``None`` when it finds nothing to read (the metric is then left out)."""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import pathlib
import re
import shutil
import sys
import tempfile
import time

import numpy as np

from bench.witness import MachineWitness

ROOT = pathlib.Path(__file__).resolve().parent.parent

# the modules that serve each kind of traffic mix
DRIVERS = {"open_loop": "bench.serve_cell"}

# the grouped forest kernel's events in a TPU profiler trace are named after
# its jitted launcher
KERNEL_EVENT = re.compile(r"grouped_call")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def cell_files(name: str, root: pathlib.Path = ROOT):
    """(BENCHMARK.json, the cell's entry, its configuration, its traffic)."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                       + ", ".join(sorted(cells)))
    cell = cells[name]
    config = load_json(root / "bench" / "configs" / f"{cell['config']}.json")
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return spec, cell, config, traffic


def cell_metrics(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (``trace`` false) or per-layer metrics
    (``trace`` true), in the order ``BENCHMARK.json`` lists them."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def reader(name: str, root: pathlib.Path = ROOT):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s) ({devs[0].device_kind})")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks, default=0))


class CompileClock:
    """Counts JAX backend compiles and their seconds, from any thread."""

    def __init__(self):
        self.n, self.s = 0, 0.0
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == _COMPILE_EVENT:
            self.n += 1
            self.s += duration


@contextlib.contextmanager
def profiled(enabled: bool):
    """Profile the window when ``enabled``; yields ``(start, holder)`` where
    ``start()`` begins the trace and its window span and ``holder`` receives
    the trace's events and the ``time.monotonic`` at which the span began."""
    holder: dict = {}
    if not enabled:
        yield (lambda: None), holder
        return
    import jax
    from bench import trace_reduce
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    state = {}

    def start():
        jax.profiler.start_trace(tmp, profiler_options=opts)
        # made once the profiler runs: a span made before it records nothing
        state["span"] = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        state["span"].__enter__()
        holder["span_start"] = time.monotonic()

    try:
        yield start, holder
    finally:
        if state:
            state["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            holder["events"] = trace_reduce.load(tmp)
        shutil.rmtree(tmp, ignore_errors=True)


def pause_summary(pauses) -> dict:
    """Count, total and longest seconds of the machine's pauses."""
    return {"n": len(pauses), "seconds": float(sum(d for _, d in pauses)),
            "longest_s": float(max((d for _, d in pauses), default=0.0))}


def setup(workload: str, root: pathlib.Path = ROOT):
    """What every use of a cell needs before its system is built: its files,
    the chips it asks for (``NoChip`` when they are not there), JAX's
    compile cache and the driver of its traffic.  Returns
    ``(spec, cell, config, traffic, device, driver)``."""
    spec, cell, config, traffic = cell_files(workload, root)
    device = device_info(int(cell["chips"]))
    from repro.util import enable_compile_cache
    enable_compile_cache()
    driver = importlib.import_module(DRIVERS[traffic["driver"]])
    return spec, cell, config, traffic, device, driver


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: pathlib.Path = ROOT) -> dict:
    """One run of one cell: set-up, the measured window, the check.
    Returns the result object.  Raises ``NoChip`` before any work when the
    chips the cell asks for are not there."""
    spec, cell, config, traffic, device, driver = setup(workload, root)
    compiles = CompileClock()
    sut = driver.ServeCell(config, traffic, seed)
    try:
        sut.start()
        setup_s = time.perf_counter() - t_start
        c0 = (compiles.n, compiles.s)
        with MachineWitness() as witness, \
                profiled(trace) as (start_trace, traced):
            win = sut.window(seconds, on_start=start_trace)
        compiled = (compiles.n - c0[0], compiles.s - c0[1])
        device["memory_peak_bytes"] = memory_peak_bytes(int(cell["chips"]))
    finally:
        sut.stop()
    checks = driver.check(win, sut)
    limits = driver.LIMITS
    correct = all(checks[k] <= limits[k] for k in limits)

    pauses = witness.within(win["t0"], win["t_end"])
    tr = None
    if "events" in traced:
        from bench import trace_reduce
        tr = trace_reduce.reduce(
            trace_reduce.with_pauses(traced["events"], pauses,
                                     traced["span_start"]), KERNEL_EVENT)
    if trace and tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
    ctx = {"cell": workload, "config": config, "traffic": traffic,
           "seconds": seconds, "setup_s": setup_s, "window": win,
           "trace": tr, "device": device, "driver": driver,
           "compiles_in_window": compiled, "notes": []}
    metrics = {}
    for m in cell_metrics(spec, workload, trace):
        v = reader(m["name"], root)(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    lat = driver.latencies_ms(win)
    failed = int(checks["unanswered"])
    result = {"correct": bool(correct), "attempted": int(lat.size),
              "failed": failed, "metrics": metrics, "device": device}
    if trace and tr is not None:
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["compiles_in_window"] = compiled[0]
    result["machine_pauses"] = pause_summary(pauses)
    result["notes"] = ctx["notes"]
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in limits}
    return result


def main(argv=None, *, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="python bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for note in result["notes"]:
        print(f"note {note}", file=sys.stderr)
    mp = result["machine_pauses"]
    print(f"machine pauses in the window: {mp['n']}, {mp['seconds']!r} s, "
          f"longest {mp['longest_s']!r} s", file=sys.stderr)
    for k, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {k} = {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(result, default=_jsonable), flush=True)
    return 0


def _jsonable(x):
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.floating,)):
        return float(x)
    raise TypeError(f"not JSON-serialisable: {type(x)}")
