"""Knee sweep of a serve cell: the highest offered rate the broker sustains.

    python bench/knee.py --workload <cell> --seed <n> --seconds <s>
        --rates 500,1000,2000 [--slo-ms 25]

Sets the cell up once, then serves ``--repeat`` windows per rate (the
traffic file's mix and arrival process, its rate replaced) and prints one
JSON line per rate: per window latency p50/p99/max, how late the generator
ran, the completed request rate, the longest flush and garbage-collector
pause, the backlog trend (median latency of the window's last fifth over
its first fifth) and the longest pause of the whole machine in it
(``bench.witness``), with the medians over the windows the machine did not
pause in.  A window the machine paused in is served again, up to
``repeat`` more times: such a pause stops every process on the host and
sets the window's p99 whatever the broker does.  The knee is the highest
rate at which the median unpaused window's p99 meets the SLO and shows no
growing backlog (trend at most ``MAX_TREND``), below the lowest rate that
misses; the last line names it.
A cell's traffic file then fixes its rate at 0.8 of the knee.  Like the
benchmark itself it needs the chip."""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import harness, serve_cell  # noqa: E402
from bench.witness import MachineWitness  # noqa: E402

MAX_TREND = 1.5


def trend(win: dict) -> float:
    """Median latency of requests scheduled in the last fifth of the window
    over that of the first fifth: well above 1 when a queue keeps growing."""
    t = np.concatenate([log.sched for log in win["logs"]]) - win["t0"]
    lat = serve_cell.latencies_ms(win)
    s = win["seconds"]
    first, last = lat[t < 0.2 * s], lat[t >= 0.8 * s]
    if not first.size or not last.size:
        return float("nan")
    return float(np.median(last) / np.median(first))


class GcClock:
    """Longest and total garbage-collector pause while installed."""

    def __init__(self):
        self.t0, self.max_ms, self.total_ms, self.n = None, 0.0, 0.0, 0

    def __call__(self, phase, info):
        if phase == "start":
            self.t0 = time.perf_counter()
        elif self.t0 is not None:
            ms = 1e3 * (time.perf_counter() - self.t0)
            self.max_ms, self.total_ms = max(self.max_ms, ms), \
                self.total_ms + ms
            self.n += 1


def window_row(sut, seconds: float, witness) -> dict:
    """Serve one window at the traffic's current rate and summarise it."""
    gcc = GcClock()
    gc.callbacks.append(gcc)
    try:
        win = sut.window(seconds)
    finally:
        gc.callbacks.remove(gcc)
    lat = serve_cell.latencies_ms(win)
    lag = serve_cell.send_lag_ms(win)
    c = win["counters"]
    return {"attempted": int(lat.size),
            "unanswered": int(serve_cell.check(win, sut)["unanswered"]),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "max_ms": float(lat.max()),
            "send_lag_p99_ms": float(np.percentile(lag, 99)),
            "completed_rps": c["requests"] / seconds,
            "rows_per_flush": c["rows"] / max(c["flushes"], 1),
            "flush_max_ms": 1e3 * max((f[3] for f in win["flushes"]),
                                      default=0.0),
            "gc_max_ms": gcc.max_ms, "trend": trend(win),
            "machine_pause_max_ms": 1e3 * max(
                (d for _, d in witness.within(win["t0"], win["t_end"])),
                default=0.0)}


def sweep(workload: str, seed: int, seconds: float, rates, slo_ms: float,
          repeat: int = 3) -> list[dict]:
    """One row per rate over ``repeat`` windows the machine did not pause
    in.  A rate is sustained when the median of those windows meets the SLO
    at its 99th percentile and shows no growing backlog, and every request
    is answered."""
    _, _, config, traffic, device, _ = harness.setup(workload)
    sut = serve_cell.ServeCell(config, dict(traffic), seed)
    out = []
    try:
        sut.start()
        with MachineWitness() as witness:
            for rate in rates:
                out.append(rate_row(sut, float(rate), seconds, slo_ms,
                                    repeat, witness))
                out[-1]["device"] = device
                print(json.dumps(out[-1]), flush=True)
    finally:
        sut.stop()
    return out


def rate_row(sut, rate: float, seconds: float, slo_ms: float, repeat: int,
             witness) -> dict:
    sut.traffic["rate_rps"] = rate
    wins = []
    for _ in range(2 * repeat):
        wins.append(window_row(sut, seconds, witness))
        clean = [w for w in wins if w["machine_pause_max_ms"] == 0.0]
        if len(clean) == repeat:
            break
    row = {"rate_rps": rate, "windows": wins}
    if clean:
        for k in ("p50_ms", "p95_ms", "p99_ms", "trend", "completed_rps"):
            row[k] = float(np.median([w[k] for w in clean]))
    row["sustained"] = bool(
        clean and row["p99_ms"] <= slo_ms and row["trend"] <= MAX_TREND
        and all(w["unanswered"] == 0 for w in wins))
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python bench/knee.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--slo-ms", type=float, default=25.0)
    ap.add_argument("--repeat", type=int, default=3,
                    help="windows per rate; the median decides")
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    try:
        rows = sweep(args.workload, args.seed, args.seconds, rates,
                     args.slo_ms, args.repeat)
    except harness.NoChip as e:
        print(f"knee: {e}", file=sys.stderr)
        return 3
    knee = None
    for r in sorted(rows, key=lambda r: r["rate_rps"]):
        if not r["sustained"]:
            break
        knee = r["rate_rps"]
    print(json.dumps({"workload": args.workload, "knee_rps": knee,
                      "setup_and_sweep_s": time.perf_counter() - T_START}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
