"""Where a decision's and a flush's time goes in one serve cell, read from
the serving path's own instrumentation.

Runs one cell of ``BENCHMARK.json`` as ``bench/run.py`` builds it (its
configuration, traffic and broker, ``bench.serve_cell``) for one window under
the profiler, then reads:

- the stage spans of every flush (``repro.obs.spans``) that lie inside the
  window: the median of each, and the summed time of the scoring stages
  (``broker.pack`` through ``forest.unpack``) over the summed flush time the
  broker's observer recorded (``record_flush``);
- the window's deltas of the wait histograms of
  ``AsyncBroker.timing_stats()``: the median ``hop_in``, ``queue_wait`` and
  ``hop_out``;
- the median decision against send lag + hop_in + queue_wait + flush +
  reply + hop_out, and what is left over;
- the window's share of replies the broker put in their channels inside
  the flush (``n_direct_replies``) rather than by an asyncio Task
  (``n_task_replies``), which ``hop_out`` follows;
- the benchmark's breakdown of the device's idle gaps, each named after the
  host span that covers most of it (``bench.trace_reduce``).

    PYTHONPATH=src python -m benchmarks.serving_stages --workload <cell> \\
        --seed <n> [--seconds 20]

Needs the chips the cell asks for, like ``bench/run.py``.  Prints one JSON
object; its numbers are host-clock and profiler-clock times of that run."""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from bench import harness, trace_reduce
from bench.witness import MachineWitness
from repro.obs.metrics import WAIT_EDGES_S, percentile_from_hist

# the stages of a flush's scoring pass (the numpy mirror has one
# ``forest.numpy`` in place of operands, launch and fetch), then its reply
SCORING = ("broker.pack", "forest.operands", "forest.launch", "forest.fetch",
           "forest.numpy", "forest.unpack")
STAGES = SCORING + ("broker.reply",)
WAITS = ("hop_in", "queue_wait", "hop_out")


def host_spans(host: list, lo: float, hi: float) -> dict:
    """``{name: [seconds]}``: the duration of every span on a Python
    thread's line (``trace_reduce.load``'s host events) that begins and ends
    inside ``[lo, hi]``, by name, the benchmark's window span left out."""
    out: dict = {}
    for t, n, s, e in host:
        if t == "python" and lo <= s and e <= hi \
                and n != trace_reduce.WINDOW_SPAN:
            out.setdefault(n, []).append((e - s) * 1e-9)
    return out


def wait_p50_ms(counts) -> float | None:
    """Median milliseconds of a wait histogram's counts over
    ``WAIT_EDGES_S`` (the upper edge of its bucket: at most 5% high);
    ``None`` when it counted nothing."""
    counts = np.asarray(counts, np.int64)
    if not counts.sum():
        return None
    return 1e3 * percentile_from_hist(WAIT_EDGES_S, counts, 0.5)


def _median(values) -> float | None:
    return float(np.median(values)) if len(values) else None


def _ms(seconds: float | None) -> float | None:
    return None if seconds is None else 1e3 * seconds


def decompose(win: dict, events: dict, timing0: dict, timing1: dict,
              driver) -> dict:
    """The split of one window: ``win`` from ``ServeCell.window``,
    ``events`` its profiler trace (``trace_reduce.load``, pauses added),
    ``timing0``/``timing1`` the broker's ``timing_stats()`` at its start
    and end, ``driver`` the cell's module (``bench.serve_cell``)."""
    bounds = trace_reduce.window_bounds(events)
    spans = host_spans(events["host"], *bounds) if bounds else {}
    stages = {n: _ms(_median(spans[n])) for n in STAGES if n in spans}
    waits = {k: wait_p50_ms(np.subtract(timing1[k], timing0[k]))
             for k in WAITS}
    flush_s = [f[3] for f in win["flushes"]]
    scored = sum(sum(spans.get(n, ())) for n in SCORING)
    parts = {"send_lag": _median(driver.send_lag_ms(win)), **waits,
             "flush": _ms(_median(flush_s)),
             "reply": stages.get("broker.reply")}
    decision = _median(driver.latencies_ms(win))
    total = None if None in parts.values() else sum(parts.values())
    tr = trace_reduce.reduce(events, harness.KERNEL_EVENT, top=12)
    return {"flushes": len(flush_s),
            "stage_p50_ms": stages,
            "stage_counts": {n: len(spans[n]) for n in stages},
            "scoring_over_flush": (scored / sum(flush_s) if flush_s
                                   else None),
            "queued_s": float(sum(spans.get("broker.queued", ()))),
            "wait_counts": {k: int(np.subtract(timing1[k], timing0[k]).sum())
                            for k in WAITS},
            "decision_p50_parts_ms": parts,
            "parts_sum_ms": total,
            "decision_p50_ms": decision,
            "residual_ms": (None if total is None or decision is None
                            else decision - total),
            "idle_gaps": None if tr is None else tr["idle_gaps"]}


def reply_counts(broker) -> dict:
    """The broker's replies so far: put directly, and sent by a Task."""
    return {"direct": broker.n_direct_replies, "task": broker.n_task_replies}


def direct_share(replies0: dict, replies1: dict) -> float | None:
    """The share of a window's replies that went in directly, from
    ``reply_counts`` at its start and end; ``None`` when it had none."""
    direct = replies1["direct"] - replies0["direct"]
    total = direct + replies1["task"] - replies0["task"]
    return direct / total if total else None


def run(workload: str, seed: int, seconds: float,
        root=harness.ROOT) -> dict:
    """One traced window of ``workload``, split by ``decompose``."""
    _, _, config, traffic, device, driver = harness.setup(workload, root)
    sut = driver.ServeCell(config, traffic, seed)
    timing0: dict = {}
    replies0: dict = {}
    try:
        sut.start()
        with MachineWitness() as witness, \
                harness.profiled(True) as (start_trace, traced):
            def on_start():              # the waits' counts, then the trace
                timing0.update(sut.broker.timing_stats())
                replies0.update(reply_counts(sut.broker))
                start_trace()

            win = sut.window(seconds, on_start=on_start)
            timing1 = sut.broker.timing_stats()
            replies1 = reply_counts(sut.broker)
    finally:
        sut.stop()
    checks = driver.check(win, sut)
    pauses = witness.within(win["t0"], win["t_end"])
    events = trace_reduce.with_pauses(traced["events"], pauses,
                                      traced["span_start"])
    out = {"cell": workload, "seed": seed, "seconds": seconds,
           "device": device,
           "correct": all(checks[k] <= driver.LIMITS[k]
                          for k in driver.LIMITS),
           "machine_pauses": harness.pause_summary(pauses)}
    out.update(decompose(win, events, timing0, timing1, driver))
    out["direct_reply_share"] = direct_share(replies0, replies1)
    out["replies"] = {k: replies1[k] - replies0[k] for k in replies1}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m benchmarks.serving_stages")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds)
    except harness.NoChip as e:
        print(f"serving_stages: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out, default=float), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
