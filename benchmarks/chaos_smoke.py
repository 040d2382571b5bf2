"""Chaos gate: the serving path must absorb faults without changing results.

``make chaos-smoke`` runs this.  Gates, all required:

1. **Fault parity** — the smoke sweep on ``--executor async`` under a seeded
   retriable FaultPlan (drops, delays, duplicates, one scheduled broker
   restart) emits SWEEP.json byte-identical to the fault-free control, with
   nonzero client-retry / broker-replay counters and *zero* fallbacks (every
   fault was absorbed by retry + idempotent replay, never by degradation).
2. **Retry-machinery overhead** — arming the full fault-tolerance path
   (request ids, per-attempt timeouts, replay slots, injector wrapping) via
   a zero-probability plan on a fault-free sweep costs within ``--budget``
   (default 10%) of the plain run in at least one of ``--attempts`` paired
   runs.  (Injected faults are excluded by construction: a dropped reply
   necessarily costs its detection timeout — that cost is the plan's, not
   the machinery's.)
3. **Outage degradation** — under a heavy early fault burst with a tight
   client deadline the sweep still completes every cell (the paper's
   graceful degradation: schedule anyway), with nonzero fallback counters.
4. **Kill-and-resume** — a ``fleet --resume`` CLI sweep SIGKILLed mid-run
   restarts from its cell ledger to byte-identical SWEEP.json.

Chaos stats land in ``experiments/CHAOS_SMOKE.json`` and are stamped into
``experiments/BENCH_<pr>.json`` under ``"chaos"``.  Non-zero exit on any
gate failure.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import re
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from common import save_json  # noqa: E402

import repro  # noqa: E402
from repro.cluster.fleet import SweepSpec, run_sweep, sweep_json  # noqa: E402
from repro.online.faults import FaultPlan  # noqa: E402

_quiet = lambda *a, **k: None

_SPEC = SweepSpec(schedulers=("fifo", "atlas-fifo"), seeds=2,
                  scenarios=("baseline",), workloads=("smoke",),
                  min_samples=40, max_train=40)

# The overhead gate runs a wider matrix so the measured fraction isn't noise
# on a sub-second baseline.
_OVERHEAD_SPEC = SweepSpec(schedulers=("fifo", "atlas-fifo"), seeds=10,
                           scenarios=("baseline",), workloads=("smoke",),
                           min_samples=40, max_train=40)

# Retriable chaos: drops + delays + duplicates + one scheduled broker
# restart, every one survivable inside the client's generous deadline, so
# the sweep must come out byte-identical.  This gate is about *correctness*
# under faults — a dropped reply necessarily costs its detection timeout,
# so wall clock is not gated here.
_PARITY_PLAN = FaultPlan(seed=7, drop=0.12, delay=0.2,
                         delay_s=(0.0005, 0.002), duplicate=0.08,
                         restart_after=(40,), max_events=24,
                         request_timeout_s=0.25, deadline_s=120.0)

# Zero-probability plan: the full fault-tolerance machinery (request ids,
# per-attempt timeouts, replay slots, injector wrapping) armed on a
# fault-free run — what the ≤10% retry-overhead budget actually measures.
# The timeout is deliberately above any barrier round's tail so no spurious
# retry pollutes the measurement.
_OVERHEAD_PLAN = FaultPlan(seed=7, max_events=0,
                           request_timeout_s=1.0, deadline_s=120.0)

# Outage chaos: a dense early burst of dropped/severed replies against a
# deadline barely above one attempt — clients exhaust their retry budget,
# predictors degrade to schedule-anyway, and the budget cap ends the outage
# so the tail of the sweep (and every done/ack) runs clean.
_OUTAGE_PLAN = FaultPlan(seed=13, drop=0.5, abrupt_close=0.2, max_events=48,
                         request_timeout_s=0.05, deadline_s=0.2)


def _fail(msg: str) -> int:
    print(f"[chaos] FAIL: {msg}", file=sys.stderr)
    return 1


def _timed_sweep(plan=None, spec=_SPEC):
    stats = {} if plan is not None else None
    t0 = time.perf_counter()
    result = run_sweep(spec, executor="async", fault_plan=plan,
                       fault_stats=stats, log=_quiet)
    return sweep_json(result), time.perf_counter() - t0, stats, result


def _cli_env():
    # the child sweeps are host-side gates, and this process has already
    # touched JAX: on a TPU host it holds the chip, so children take the CPU
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = (src + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else src)
    return env


def _fleet_cmd(out_dir, *extra):
    return [sys.executable, "-m", "repro.cluster.fleet",
            "--schedulers", "fifo,atlas-fifo", "--seeds", "2",
            "--scenarios", "baseline", "--workloads", "smoke",
            "--min-samples", "40", "--executor", "async",
            "--out", str(out_dir), *extra]


def _gate_kill_and_resume(td: pathlib.Path) -> tuple[int, dict]:
    env = _cli_env()
    control = td / "control"
    victim = td / "victim"

    subprocess.run(_fleet_cmd(control), env=env, check=True,
                   stdout=subprocess.DEVNULL)
    control_bytes = (control / "SWEEP.json").read_text()

    # start the victim with --resume, kill it as soon as its ledger shows
    # the first finished cell — a genuinely mid-sweep SIGKILL
    proc = subprocess.Popen(_fleet_cmd(victim, "--resume"), env=env,
                            stdout=subprocess.DEVNULL)
    cells = victim / "cells"
    deadline = time.time() + 120
    while time.time() < deadline and proc.poll() is None \
            and not list(cells.glob("w1__*.json")):
        time.sleep(0.01)
    if proc.poll() is not None:
        return _fail("victim sweep finished before it could be killed "
                     "(widen the spec)"), {}
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=30)
    n_ledger = len(list(cells.glob("w1__*.json")))
    if n_ledger == 0:
        return _fail("no ledger cells survived the kill"), {}

    # resume: finished cells come from the ledger, the rest re-run
    subprocess.run(_fleet_cmd(victim, "--resume"), env=env, check=True,
                   stdout=subprocess.DEVNULL)
    resumed_bytes = (victim / "SWEEP.json").read_text()
    if resumed_bytes != control_bytes:
        return _fail("resumed SWEEP.json differs from the uninterrupted "
                     "control"), {}
    print(f"[chaos] kill-and-resume OK: killed with {n_ledger} ledger "
          f"cells, resumed to byte-identical SWEEP.json")
    return 0, {"ledger_cells_at_kill": n_ledger}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--budget", type=float, default=0.10,
                    help="max fractional wall-clock overhead of the faulted "
                         "sweep vs the clean control")
    ap.add_argument("--attempts", type=int, default=3,
                    help="paired overhead attempts; any within budget passes")
    args = ap.parse_args(argv)

    rc = 0
    t0 = time.perf_counter()

    # -------------------------------------------------- gate 1: fault parity
    clean_bytes, t_clean, _, small_clean = _timed_sweep()
    fault_bytes, t_fault, parity_stats, _ = _timed_sweep(_PARITY_PLAN)
    if fault_bytes != clean_bytes:
        rc |= _fail("faulted SWEEP.json differs from the clean control")
    inj = parity_stats["injected"]
    if inj["drops"] == 0 or inj["delays"] == 0 or inj["restarts"] == 0:
        rc |= _fail(f"fault mix incomplete for the acceptance claim: {inj}")
    if parity_stats["client_retries"] == 0:
        rc |= _fail("no client retries — the faults never reached the "
                    "request path")
    if parity_stats["fallbacks"] != 0:
        rc |= _fail(f"{parity_stats['fallbacks']} fallbacks under retriable "
                    "chaos: parity held by luck, not retries")
    if rc == 0:
        print(f"[chaos] parity OK: {inj['events']} injected events "
              f"({inj['drops']} drops, {inj['delays']} delays, "
              f"{inj['restarts']} restart) absorbed by "
              f"{parity_stats['client_retries']} retries / "
              f"{parity_stats['replays']} replays, bytes identical "
              f"({t_fault:.2f}s vs {t_clean:.2f}s clean)")

    # ------------------------- gate 2: resilience-machinery overhead (clean)
    overhead_frac = None
    for attempt in range(args.attempts):
        _, t_off, _, _ = _timed_sweep(spec=_OVERHEAD_SPEC)
        on_bytes, t_on, on_stats, _ = _timed_sweep(_OVERHEAD_PLAN,
                                                   spec=_OVERHEAD_SPEC)
        frac = max(t_on - t_off, 0.0) / t_off
        overhead_frac = frac if overhead_frac is None \
            else min(overhead_frac, frac)
        print(f"[chaos] overhead attempt {attempt + 1}: plain {t_off:.2f}s "
              f"vs armed {t_on:.2f}s (+{frac * 100:.1f}%)")
        if frac <= args.budget:
            break
    else:
        rc |= _fail(f"retry-machinery overhead {overhead_frac * 100:.1f}% "
                    f"above {args.budget * 100:.0f}% budget in all "
                    f"{args.attempts} attempts")
    if on_stats["injected"]["events"] != 0:
        rc |= _fail("zero-probability plan injected faults — the overhead "
                    "measurement is contaminated")

    # -------------------------------------------- gate 3: outage degradation
    _, t_outage, outage_stats, outage = _timed_sweep(_OUTAGE_PLAN)
    if len(outage["cells"]) != len(small_clean["cells"]):
        rc |= _fail(f"outage sweep lost cells: {len(outage['cells'])} of "
                    f"{len(small_clean['cells'])}")
    if outage_stats["fallbacks"] == 0:
        rc |= _fail("outage never degraded the predictor — deadline too "
                    "generous for the gate to mean anything")
    else:
        print(f"[chaos] outage OK: all {len(outage['cells'])} cells "
              f"completed with {outage_stats['fallbacks']} fallbacks "
              f"({outage_stats['fallback_rows']} rows, "
              f"{t_outage:.2f}s)")

    # -------------------------------------------- gate 4: kill-and-resume
    with tempfile.TemporaryDirectory() as td:
        rc4, resume_info = _gate_kill_and_resume(pathlib.Path(td))
        rc |= rc4

    # ------------------------------------------------- artifacts + stamp
    result = {
        "ok": rc == 0,
        "parity": parity_stats is not None,
        "overhead_frac": (round(overhead_frac, 4)
                          if overhead_frac is not None else None),
        "retries": parity_stats["client_retries"] if parity_stats else None,
        "reconnects": (parity_stats["client_reconnects"]
                       if parity_stats else None),
        "replays": parity_stats["replays"] if parity_stats else None,
        "dup_requests": (parity_stats["dup_requests"]
                         if parity_stats else None),
        "injected": parity_stats["injected"] if parity_stats else None,
        "outage_fallbacks": outage_stats["fallbacks"],
        "outage_fallback_rows": outage_stats["fallback_rows"],
        "outage_cells": len(outage["cells"]),
        **resume_info,
    }
    path = save_json("CHAOS_SMOKE", result)
    print(f"[chaos] -> {path}")

    m = re.match(r"PR(\d+)", repro.PR_TAG)
    if m:
        bench_path = (pathlib.Path(__file__).resolve().parents[1]
                      / "experiments" / f"BENCH_{m.group(1)}.json")
        art = (json.loads(bench_path.read_text()) if bench_path.exists()
               else {"pr": repro.PR_TAG})
        art["chaos"] = {k: result[k] for k in
                        ("parity", "overhead_frac", "retries", "replays",
                         "dup_requests", "outage_fallbacks")}
        art["chaos"]["injected_events"] = (result["injected"] or
                                           {}).get("events")
        bench_path.write_text(json.dumps(art, indent=2, sort_keys=True)
                              + "\n")
        print(f"[chaos] stamped chaos stats into {bench_path}")

    print(f"[chaos] {'PASS' if rc == 0 else 'FAIL'} "
          f"({time.perf_counter() - t0:.1f}s total)")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
