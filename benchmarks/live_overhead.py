"""Live-telemetry gate: the wire path must observe without perturbing.

``make live-smoke`` runs this.  One TelemetryCollector (behind an
``AsyncBroker`` on ``tcp://127.0.0.1`` plus the ``/snapshot`` / ``/delta``
HTTP server) receives the smoke fleet matrix streamed live while a poller
thread curls ``/delta?since=<seq>`` mid-run.  Gates, all required:

1. **Byte parity** — the ``--obs-live`` sweep's SWEEP.json equals the
   no-telemetry run's bytes exactly (live path observes, never perturbs).
2. **Nonzero snapshot** — ``/snapshot`` reports every cell as a source with
   a nonzero frame count.
3. **Gapless deltas** — the seqs collected by the mid-run poller chain
   contiguously 1..seq with no resync.
4. **Replay equality** — folding the polled delta entries through a fresh
   collector reproduces the live aggregates bit-for-bit, and so does
   replaying the post-hoc NDJSON file of a cell run with *both* sinks
   attached (wire view == file view).
5. **Overhead** — the paired-median CPU estimator from
   ``benchmarks/obs_overhead.py``, with the on-side streaming to the live
   collector instead of a file, stays within ``--budget`` (default 5%) on
   the bench-smoke cell.  The consumer stack for this gate runs as a
   separate ``python -m repro.obs.live`` process — the way a deployment
   runs it — so ``time.process_time`` charges only the producer side
   (TransportSink thread, serialization, tcp send); collector fold CPU
   belongs to the service, not the simulator.

Live-path stats (frames/s ingested, max collector lag observed mid-run,
delta sizes) are stamped into ``experiments/BENCH_<pr>.json`` under
``"live"`` via the existing PR_TAG mechanism; the full result lands in
``experiments/LIVE_SMOKE.json``.  Non-zero exit on any gate failure.
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import os
import pathlib
import re
import statistics
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import obs_overhead  # noqa: E402
from common import save_json  # noqa: E402

import repro  # noqa: E402
from repro.cluster.experiment import run_scheduler  # noqa: E402
from repro.cluster.fleet import SweepSpec, run_sweep, sweep_json  # noqa: E402
from repro.obs import (LiveServer, TelemetryCollector,  # noqa: E402
                       read_ndjson)
from repro.online.server import AsyncBroker  # noqa: E402

_counter = itertools.count()

# the obs-smoke matrix: 2 schedulers x 1 seed on the bursty_tt/smoke cell
_SPEC = SweepSpec(schedulers=("fifo", "atlas-fifo"), seeds=1,
                  scenarios=("bursty_tt",), workloads=("smoke",))

_quiet = lambda *a, **k: None


def _get_json(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.load(r)


class _Poller(threading.Thread):
    """Mid-run ``/delta`` chain poller: collects every entry exactly once
    and tracks delta sizes + the max collector lag seen on ``/snapshot``."""

    def __init__(self, base_url: str):
        super().__init__(daemon=True, name="delta-poller")
        self.base = base_url
        self.stop_evt = threading.Event()
        self.entries: list[dict] = []
        self.delta_sizes: list[int] = []
        self.max_lag_s = 0.0
        self.resyncs = 0
        self.error: Exception | None = None

    def _poll_once(self):
        since = self.entries[-1]["seq"] if self.entries else 0
        r = _get_json(f"{self.base}/delta?since={since}")
        if r.get("resync"):
            self.resyncs += 1
        if r["frames"]:
            self.entries.extend(r["frames"])
            self.delta_sizes.append(len(r["frames"]))

    def run(self):
        n = 0
        try:
            while not self.stop_evt.is_set():
                self._poll_once()
                if n % 5 == 0:
                    h = _get_json(f"{self.base}/snapshot")["health"]
                    self.max_lag_s = max(self.max_lag_s, h["lag_max_s"])
                n += 1
                time.sleep(0.05)
            self._poll_once()            # final drain after the run ends
        except Exception as e:          # surfaced by the main thread
            self.error = e


def _fail(msg: str) -> int:
    print(f"[live] FAIL: {msg}", file=sys.stderr)
    return 1


def _live_smoke_cfg(addr: str):
    """obs_overhead-style cfg factory: the ``obs_dir`` slot becomes the
    on/off toggle for the live wire (None = off, anything = stream)."""
    def make_cfg(obs_dir, frame_every):
        cfg = obs_overhead._smoke_cfg(None, frame_every)
        if obs_dir is not None:
            cfg = dataclasses.replace(
                cfg, obs_live_addr=addr,
                obs_source=f"overhead_{next(_counter)}")
        return cfg
    return make_cfg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--pairs", type=int, default=9,
                    help="off/on pairs per overhead attempt")
    ap.add_argument("--attempts", type=int, default=3,
                    help="overhead attempts; any within budget passes")
    ap.add_argument("--budget", type=float, default=0.05,
                    help="max fractional slowdown with the live wire on")
    ap.add_argument("--frame-every", type=float, default=60.0)
    args = ap.parse_args(argv)

    rc = 0
    t0 = time.perf_counter()

    # -------------------------------------------- baseline (no telemetry)
    off_bytes = sweep_json(run_sweep(_SPEC, executor="serial", log=_quiet))
    print(f"[live] baseline sweep done ({time.perf_counter() - t0:.1f}s)")

    # ------------------------------------- live stack: broker + collector
    collector = TelemetryCollector()
    broker = AsyncBroker().start()
    broker.collector = collector
    addr = broker.serve("tcp://127.0.0.1:0")
    http = LiveServer(collector).start()
    print(f"[live] collector listening on {addr}, http {http.address}")

    poller = _Poller(http.address)
    poller.start()
    on_bytes = sweep_json(run_sweep(_SPEC, executor="serial",
                                    obs_live=addr, log=_quiet))
    # cell sinks are closed by now (SimObserver.finish), so every frame is
    # on the wire; give the broker loop a moment to drain into the collector
    deadline = time.time() + 30
    while time.time() < deadline:
        seq = collector.seq
        time.sleep(0.2)
        if collector.seq == seq:
            break
    poller.stop_evt.set()
    poller.join(timeout=30)

    # gate 1: byte parity
    if on_bytes != off_bytes:
        rc |= _fail("SWEEP.json bytes differ with --obs-live on")
    else:
        print("[live] parity OK: SWEEP.json byte-identical with the wire on")

    # gate 2: nonzero snapshot over HTTP, one source per cell
    snap = _get_json(f"{http.address}/snapshot")
    n_sources = len(snap["aggregates"])
    n_frames = snap["health"]["frames"]
    if n_frames == 0 or n_sources == 0:
        rc |= _fail("collector snapshot is empty")
    bad = [s for s, a in snap["aggregates"].items() if a["frames"] == 0]
    if bad:
        rc |= _fail(f"zero-frame sources in snapshot: {bad}")
    print(f"[live] snapshot OK: {n_sources} sources, {n_frames} frames, "
          f"{snap['health']['frames_per_s']} frames/s")

    # gate 3: gapless mid-run deltas
    if poller.error is not None:
        rc |= _fail(f"delta poller died: {poller.error!r}")
    seqs = [e["seq"] for e in poller.entries]
    if poller.resyncs or seqs != list(range(1, snap["seq"] + 1)):
        rc |= _fail(f"delta chain not gapless: {len(seqs)} entries, "
                    f"{poller.resyncs} resyncs, final seq {snap['seq']}")
    else:
        print(f"[live] deltas OK: {len(seqs)} entries gapless over "
              f"{len(poller.delta_sizes)} polls, max lag "
              f"{poller.max_lag_s:.3f}s")

    # gate 4a: polled deltas replay to the live aggregates
    replayed = TelemetryCollector()
    for e in poller.entries:
        replayed.ingest(e["frame"], source=e["source"])
    if replayed.aggregates() != collector.aggregates():
        rc |= _fail("replaying polled deltas diverges from live aggregates")
    else:
        print("[live] replay OK: polled deltas reproduce the aggregates")

    # gate 4b: wire view == post-hoc NDJSON view for a dual-sink cell
    with tempfile.TemporaryDirectory() as td:
        dual = TelemetryCollector()
        broker2 = AsyncBroker().start()
        broker2.collector = dual
        addr2 = broker2.serve("tcp://127.0.0.1:0")
        path = f"{td}/dual.ndjson"
        cfg = obs_overhead._smoke_cfg(None, args.frame_every)
        cfg = dataclasses.replace(cfg, obs_path=path, obs_live_addr=addr2,
                                  obs_source="dual")
        run_scheduler("fifo", cfg)
        deadline = time.time() + 30
        while time.time() < deadline:
            seq = dual.seq
            time.sleep(0.2)
            if dual.seq == seq:
                break
        broker2.stop()
        from_file = TelemetryCollector()
        for frame in read_ndjson(path):
            from_file.ingest(frame, source="dual")
        if from_file.aggregates() != dual.aggregates():
            rc |= _fail("NDJSON replay diverges from the wire aggregates")
        else:
            print("[live] replay OK: post-hoc NDJSON matches the wire view")

        # tear the in-process stack down before measuring: gate 5 streams
        # to its own subprocess consumer, and an idle broker loop + HTTP
        # poll thread in the measured process only add CPU noise
        final_health = collector.health()
        http.stop()
        broker.stop()

        # gate 5: live-wire overhead on the bench-smoke cell.  The
        # consumer runs as a separate process so process_time charges
        # only the producer side (sink thread + serialization + send) —
        # in deployment the collector is a service, not a thread of the
        # simulator.
        # this process has touched JAX (and holds the chip on a TPU host):
        # the consumer is host-side, so it runs JAX on the CPU
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
        env["PYTHONPATH"] = (src + os.pathsep + env["PYTHONPATH"]
                             if env.get("PYTHONPATH") else src)
        consumer = subprocess.Popen(
            [sys.executable, "-m", "repro.obs.live",
             "--listen", "tcp://127.0.0.1:0", "--http", "127.0.0.1:0"],
            stdout=subprocess.PIPE, text=True, env=env)
        try:
            addr5 = json.loads(consumer.stdout.readline())["listen"]
            overhead = obs_overhead._gate(
                "live smoke", _live_smoke_cfg(addr5), td, args,
                schedulers=("fifo", "atlas-fifo"))
        finally:
            consumer.terminate()
            consumer.wait(timeout=10)
        if not overhead["ok"]:
            rc |= _fail(f"live overhead {overhead['overhead_frac'] * 100:.2f}"
                        f"% exceeds {args.budget * 100:.0f}% budget in all "
                        f"{len(overhead['attempts'])} attempts")

    # ------------------------------------------------- artifacts + stamp
    result = {
        "ok": rc == 0,
        "listen": addr,
        "sources": n_sources,
        "frames": n_frames,
        "frames_per_s": final_health["frames_per_s"],
        "max_lag_s": round(poller.max_lag_s, 3),
        "delta_polls": len(poller.delta_sizes),
        "delta_size_p50": (statistics.median(poller.delta_sizes)
                           if poller.delta_sizes else 0),
        "delta_size_max": max(poller.delta_sizes, default=0),
        "resyncs": poller.resyncs,
        "parity": on_bytes == off_bytes,
        "overhead": overhead,
    }
    path = save_json("LIVE_SMOKE", result)
    print(f"[live] -> {path}")

    m = re.match(r"PR(\d+)", repro.PR_TAG)
    if m:
        bench_path = (pathlib.Path(__file__).resolve().parents[1]
                      / "experiments" / f"BENCH_{m.group(1)}.json")
        art = (json.loads(bench_path.read_text()) if bench_path.exists()
               else {"pr": repro.PR_TAG})
        art["live"] = {k: result[k] for k in
                       ("frames", "frames_per_s", "max_lag_s",
                        "delta_size_p50", "delta_size_max", "parity")}
        art["live"]["overhead_frac"] = overhead["overhead_frac"]
        bench_path.write_text(json.dumps(art, indent=2, sort_keys=True)
                              + "\n")
        print(f"[live] stamped live stats into {bench_path}")

    print(f"[live] {'PASS' if rc == 0 else 'FAIL'} "
          f"({time.perf_counter() - t0:.1f}s total)")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
