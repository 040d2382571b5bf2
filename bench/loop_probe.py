"""One reading of an ATLAS decision loop on the chip, for the loop cells the
benchmark does not have yet.

    python bench/loop_probe.py --config <config> --seed <n>

Runs one ``atlas-fifo`` cell of the configuration as
``run_sweep(executor="async")`` serves it (wave 1: the fifo training run and
the fit; wave 2: the ATLAS cell, its predictor's flushes scored through one
``AsyncBroker`` on the path the program picks for the backend, retraining
every 600 simulated seconds) with JAX's persistent compile cache off, so
every compile is paid as a deployment pays it.  Prints one JSON line: wave
2's wall seconds, the simulated seconds it covered and their ratio, the
backend compiles inside wave 2 and their seconds, and the broker's scored
rows over the rows the scheduler asked for.  Needs the chip."""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def reading(config: dict, seed: int) -> dict:
    import jax
    import jax.monitoring
    from repro.cluster.fleet import SweepSpec, run_sweep
    jax.config.update("jax_enable_compilation_cache", False)
    compiles: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **_: compiles.append(
            (time.perf_counter(), duration))
        if event == _COMPILE_EVENT else None)
    marks = {}

    def log(msg, *_, **__):
        if "wave 1 done" in str(msg):
            marks["wave2"] = time.perf_counter()

    spec = SweepSpec(schedulers=("atlas-fifo",), seeds=(seed,),
                     scenarios=(config["scenario"],),
                     workloads=(config["workload"],),
                     fleet_sizes=(config["fleet_size"],))
    stats: dict = {}
    out = run_sweep(spec, executor="async", fault_stats=stats, log=log)
    t_end = time.perf_counter()
    t2 = marks["wave2"]
    (cell,) = out["cells"]
    broker = out["perf"]["broker"]
    wall = t_end - t2
    sim_s = cell["metrics"]["sim_time"]
    in_wave2 = [d for t, d in compiles if t >= t2]
    return {"config": config["name"], "seed": seed,
            "wave1_s": t2 - T_START, "wave2_s": wall, "sim_s": sim_s,
            "sim_s_per_s": sim_s / wall,
            "refit_compiles": len(in_wave2),
            "refit_compile_s": sum(in_wave2),
            "wave1_compile_s": sum(d for t, d in compiles if t < t2),
            "scored_rows": broker["rows"],
            "demand_rows": broker["demand_rows"],
            "scored_per_demanded_row": broker["rows"] / broker["demand_rows"],
            "flushes": broker["flushes"],
            "device_flushes": stats.get("device_flushes"),
            "fallbacks": stats.get("fallbacks")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python bench/loop_probe.py")
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    try:
        device = harness.device_info(1)
    except harness.NoChip as e:
        print(f"loop_probe: {e}", file=sys.stderr)
        return 3
    config = harness.load_json(ROOT / "bench" / "configs"
                               / f"{args.config}.json")
    print(json.dumps({**reading(config, args.seed), "device": device}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
