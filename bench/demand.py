"""Records the request sizes of a configuration's ATLAS deployment.

    JAX_PLATFORMS=cpu python bench/demand.py --config <config> --seeds 0,1,2

For each seed, runs one ``atlas-fifo`` cell of the configuration's fleet,
job mix and chaos scenario in the program's simulator, as
``run_sweep(executor="async")`` runs wave 2: a fifo run makes the training
trace, a ``BrokerPredictor`` is fitted on it and ATLAS schedules with it
(retraining every 600 simulated seconds).  Every scoring call the scheduler
makes (``p_success``: one row; ``p_success_nodes``: one row per candidate
node) is counted by task kind and size: that is the scheduler's demand, the
requests a predictor service answers.  The flushes the predictor would send
to a broker (tick priming included) are counted too, for comparison.
Prints one JSON object: the size histograms summed over the seeds, and the
simulated span, so that a rate can be read as clusters' worth of decisions.
The serve cells draw their request sizes from the ``demand`` histogram that
a configuration file keeps from this output.  Runs on the CPU: the sizes do
not depend on where the forest is scored."""

import argparse
import collections
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def record(config: dict, seed: int) -> dict:
    from repro.cluster.experiment import run_scheduler
    from repro.online.broker import BrokerPredictor

    from bench.serve_cell import experiment_config
    cfg = experiment_config(config, seed)
    _, trace, _ = run_scheduler("fifo", cfg)
    pred = BrokerPredictor(algo=cfg.algo, seed=cfg.seed,
                           min_samples=cfg.min_samples,
                           max_train=cfg.max_train)
    pred.fit_datasets(*trace.datasets())
    demand = collections.Counter()
    flushes = collections.Counter()
    one, many, flush = pred.p_success, pred.p_success_nodes, pred._flush

    def p_success(sim, task, node, speculative=False):
        if pred.model_for_kind(task.kind) is not None:
            demand[(task.kind, 1)] += 1
        return one(sim, task, node, speculative)

    def p_success_nodes(sim, task, nodes, speculative=False):
        if pred.model_for_kind(task.kind) is not None and len(nodes):
            demand[(task.kind, len(nodes))] += 1
        return many(sim, task, nodes, speculative)

    def counted_flush(groups):
        flushes[sum(len(X) for _, X in groups)] += 1
        return flush(groups)

    pred.p_success, pred.p_success_nodes = p_success, p_success_nodes
    pred._flush = counted_flush
    metrics, _, sim = run_scheduler("atlas-fifo", cfg, pred)
    return {"demand": demand, "flushes": flushes, "sim_s": float(sim.now),
            "demand_rows": pred.n_demand_rows,
            "scored_rows": pred.n_rows_scored}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python bench/demand.py")
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    config = json.loads(
        (ROOT / "bench" / "configs" / f"{args.config}.json").read_text())
    seeds = [int(s) for s in args.seeds.split(",")]
    demand, flushes = collections.Counter(), collections.Counter()
    sim_s = demand_rows = scored_rows = 0
    for seed in seeds:
        r = record(config, seed)
        demand.update(r["demand"])
        flushes.update(r["flushes"])
        sim_s += r["sim_s"]
        demand_rows += r["demand_rows"]
        scored_rows += r["scored_rows"]
    sizes = {k: {str(n): c for (kk, n), c in sorted(demand.items())
                 if kk == k} for k in sorted({k for k, _ in demand})}
    calls = sum(demand.values())
    print(json.dumps({
        "config": args.config, "seeds": seeds, "sizes": sizes,
        "calls": calls, "sim_s": sim_s,
        "calls_per_sim_s": calls / sim_s if sim_s else None,
        "mean_rows": demand_rows / calls if calls else None,
        "scored_per_demanded_row": scored_rows / max(demand_rows, 1),
        "flush_sizes": {str(n): c for n, c in sorted(flushes.items())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
