"""Serve cells: open-loop clients -> inproc transport -> ``AsyncBroker`` ->
``forest_predict_grouped`` -> the grouped forest kernel.

Set-up builds the decision stream, the configuration's recorded ATLAS
request sizes filled with launch rows of a fifo trace of its fleet (the
program's simulator), makes the benchmark's own forest per task
kind from the seed (``forest_ref.make_forest``), starts one ``AsyncBroker``
with the serving defaults on the path the program picks for the backend
(``serving_impl()``), compiles the kernel for every flush size the broker can
form and serves one second of the cell's traffic.  The window then serves
``--seconds`` of fresh arrivals; every reply is kept for the check."""

from __future__ import annotations

import asyncio
import gc
import time

import numpy as np

from bench import forest_ref, load

# replies still missing this long after the window closed count as never
# answered
REPLY_WAIT_S = 60.0
WARM_SECONDS = 1.0
TAG_WARM, TAG_WINDOW = 1, 2

# the compared numbers and their limits: every answer is exact (the widest
# gap to the reference is 0) and every request is answered
LIMITS = {"prob_max_gap": 0.0, "unanswered": 0}


class FlushLog:
    """The broker's flush observer (``AsyncBroker.obs``): per flush, its rows,
    requests, the task-kind models it read and its host time.  The served
    models report each read of their trees into ``kinds``."""

    def __init__(self):
        self.kinds: set = set()
        self.records: list = []
        self.on = False

    def record_flush(self, rows, n_requests, n_dispatches, dt):
        if self.on:
            self.records.append((int(rows), int(n_requests),
                                 max(len(self.kinds), 1), float(dt)))
        self.kinds.clear()


def _served_model(params, kind: str, flushes: FlushLog):
    from repro.ml.models import RandomForest

    class ServedForest(RandomForest):
        """The program's R.F. model over the benchmark's trees."""

        @property
        def params(self):
            flushes.kinds.add(kind)
            return params

    return ServedForest()


def experiment_config(config: dict, seed: int):
    """The program's experiment config of the configuration's fleet, job mix
    and chaos scenario at this seed, as a fleet sweep builds its cell."""
    from repro.cluster.fleet import SweepSpec, cell_config, expand
    spec = SweepSpec(schedulers=("fifo",), seeds=(int(seed),),
                     scenarios=(config["scenario"],),
                     workloads=(config["workload"],),
                     fleet_sizes=(config["fleet_size"],))
    (cell,) = expand(spec)
    return cell_config(spec, cell)


def trace_rows(config: dict, seed: int):
    """((map X, y), (reduce X, y)) launch rows of a fifo run of the
    configuration's fleet, workload and chaos scenario for this seed."""
    from repro.cluster.experiment import run_scheduler
    _, trace, _ = run_scheduler("fifo", experiment_config(config, seed),
                                with_trace=True)
    return trace.datasets()


class ServeCell:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.ml.forest import ForestParams, serving_impl
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        (mx, my), (rx, ry) = trace_rows(config, self.seed)
        f = config["forest"]
        self.forests = {}
        for i, (kind, X, y) in enumerate((("map", mx, my),
                                          ("reduce", rx, ry))):
            if X.shape[0] == 0:                  # a kind the trace never ran
                X, y = (rx, ry) if kind == "map" else (mx, my)
            self.forests[kind] = forest_ref.make_forest(
                load.rng_for(self.seed, 100 + i), X, y,
                n_trees=f["n_trees"], depth=f["depth"])
        mix = load.size_mix(config)
        self.max_rows = max(n for _, n, _ in mix)
        self.requests = load.cut_requests(mx, rx, mix,
                                          load.rng_for(self.seed, 200))
        self.flushes = FlushLog()
        self.models = {
            k: _served_model(ForestParams(**v), k, self.flushes)
            for k, v in self.forests.items()}
        self.impl = serving_impl()
        self.broker = None
        self.address = None

    # ------------------------------------------------------------ lifecycle
    def start(self):
        """Start the broker, compile every flush shape, serve the warm-up."""
        from repro.kernels import forest as forest_kernels
        from repro.online.server import AsyncBroker
        b = self.config["broker"]
        self.broker = AsyncBroker(self.models, impl=self.impl,
                                  policy=b["policy"], depth=b["depth"],
                                  slo_ms=b["slo_ms"]).start()
        self.broker.obs = self.flushes
        self.address = self.broker.serve("")
        if self.impl == "pallas":
            f = self.config["forest"]
            forest_kernels.warmup_grouped(
                len(self.models), f["n_trees"], f["depth"],
                self.requests[0][1].shape[1], b["depth"] + self.max_rows)
        self.window(WARM_SECONDS, TAG_WARM)
        # free set-up's garbage (the simulator run that made the trace) and
        # exempt what set-up keeps from later collections, so a collection in
        # the window scans only what serving allocated
        gc.collect()
        gc.freeze()

    def stop(self):
        if self.broker is not None:
            self.broker.stop()
            self.broker = None
            gc.unfreeze()

    def counters(self) -> dict:
        from repro.kernels import forest as forest_kernels
        s = self.broker.stats()
        return {"rows": s["rows"], "flushes": s["flushes"],
                "requests": s["requests"],
                "device_flushes": self.broker.n_device_flushes,
                "device_passes": forest_kernels.n_device_passes}

    # ------------------------------------------------------------ the window
    def window(self, seconds: float, tag: int = TAG_WINDOW,
               on_start=None) -> dict:
        """Serve ``seconds`` of the traffic's arrivals and wait for the
        replies.  ``on_start`` runs just before the first send."""
        plans = load.plan_clients(len(self.requests), self.traffic, seconds,
                                  self.seed, tag)
        slo = self.config["broker"]["slo_ms"]
        c0 = self.counters()
        self.flushes.records = []
        self.flushes.on = True
        if on_start is not None:
            on_start()
        t0 = time.perf_counter() + 0.05
        logs = [load.ClientLog(idxs, t0 + offs) for idxs, offs in plans]
        deadline = t0 + seconds + REPLY_WAIT_S

        async def drive():
            await asyncio.gather(*[
                load.open_loop_client(self.address, self.requests, log, slo,
                                      deadline) for log in logs])

        asyncio.run_coroutine_threadsafe(drive(), self.broker.loop).result(
            seconds + REPLY_WAIT_S + 60)
        t_end = time.perf_counter()
        self.flushes.on = False
        c1 = self.counters()
        return {"t0": t0, "t_end": t_end, "seconds": seconds, "logs": logs,
                "deadline": deadline,
                "counters": {k: c1[k] - c0[k] for k in c0},
                "flushes": list(self.flushes.records)}


def latencies_ms(win: dict) -> np.ndarray:
    """Latency of every request scheduled in the window, from its scheduled
    send to its reply.  A request that failed or was never answered counts
    as answered at the reply deadline, past any limit."""
    out = []
    for log in win["logs"]:
        done = log.done.copy()
        bad = np.isnan(done) | np.array([e is not None for e in log.errors],
                                        bool)
        done[bad] = win["deadline"]
        out.append(done - log.sched)
    return 1e3 * np.concatenate(out) if out else np.zeros(0)


def send_lag_ms(win: dict) -> np.ndarray:
    """How late each request left the generator: actual minus scheduled."""
    lags = [log.sent - log.sched for log in win["logs"]]
    lag = np.concatenate(lags) if lags else np.zeros(0)
    return 1e3 * lag[~np.isnan(lag)]


def check(win: dict, cell: ServeCell, probs=forest_ref.reference_probs
          ) -> dict:
    """Every answer the clients received against ``probs`` on the same rows.
    Returns the compared numbers: the widest gap, and the requests that
    failed or were never answered."""
    answers, rows, missing = {"map": [], "reduce": []}, \
        {"map": [], "reduce": []}, 0
    for log in win["logs"]:
        for j, qi in enumerate(log.idxs):
            if log.probs[j] is None:
                missing += 1
                continue
            kind, X = cell.requests[qi]
            answers[kind].append(log.probs[j])
            rows[kind].append(X)
    gap = max(forest_ref.max_gap(answers[k], rows[k], cell.forests[k], probs)
              for k in answers)
    return {"prob_max_gap": gap, "unanswered": missing}
