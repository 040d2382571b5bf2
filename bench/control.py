"""Readings that set a serve cell's correctness limits, on the chip.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process: set the cell up, serve one window of its
traffic at its own rate, then compare every answer the clients received
with the plain reference (the program's reading, which sets the lower end
of each limit) and the reference computed one precision down with the
program's answers (the control's reading, which sets the upper end).
Prints one JSON line per seed.  The benchmark's own runs never run it."""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import forest_ref, harness, serve_cell  # noqa: E402


def control_answers(win: dict, cell) -> dict:
    """The window with every answer replaced by the control's: the reference
    one precision down, put in the program's place."""
    logs = []
    for log in win["logs"]:
        new = serve_cell.load.ClientLog(log.idxs, log.sched)
        new.sent, new.done = log.sent, log.done
        for j, qi in enumerate(log.idxs):
            if log.probs[j] is not None:
                kind, X = cell.requests[qi]
                new.probs[j] = forest_ref.control_probs(cell.forests[kind], X)
        logs.append(new)
    return {**win, "logs": logs}


def readings(workload: str, seeds, seconds: float):
    _, _, config, traffic, device, _ = harness.setup(workload)
    for seed in seeds:
        sut = serve_cell.ServeCell(config, traffic, seed)
        try:
            sut.start()
            win = sut.window(seconds)
        finally:
            sut.stop()
        program = serve_cell.check(win, sut)
        control = serve_cell.check(control_answers(win, sut), sut)
        yield {"workload": workload, "seed": seed, "device": device,
               "requests": int(serve_cell.latencies_ms(win).size),
               "rows": win["counters"]["rows"],
               "program": program, "control": control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        for row in readings(args.workload, seeds, args.seconds):
            print(json.dumps(row), flush=True)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
