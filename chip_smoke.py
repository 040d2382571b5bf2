"""Chip smoke test: the ATLAS serving path end to end on one TPU chip.

    python chip_smoke.py [--seed N]

Runs in one process and makes everything from ``--seed``.  Phases, one line
each on stdout, every line with its wall time and its compile time apart:

  device  the JAX devices and the jax / jaxlib / libtpu versions.  Anything
          but a TPU is a failure: the script never carries on on the CPU.
  fit     R.F. map and reduce predictors trained on a fifo trace of a
          1000-node fleet running the ``map_heavy`` workload (``bursty_tt``
          chaos), once on the chip and once on the CPU backend of this
          process: the splits must agree exactly, the leaves within
          ``LEAF_RTOL``.
  score   one packed flush of 4096+ trace rows over R.F. (24 x depth 5) and
          Tree (1 x depth 6) models, so padding is exercised: the compiled
          grouped kernel must reproduce the numpy mirror bit for bit, and its
          compiled program must hold the Pallas kernel (``tpu_custom_call``).
  serve   ``run_sweep(..., executor="async")``: fifo and atlas-fifo, two seeds,
          ``bursty_tt`` / ``map_heavy`` at 1000 nodes, invariant checks on.
          The ATLAS cells are transport clients of one AsyncBroker whose every
          flush scores on the chip.  Every cell must complete with no
          invariant violation and no degraded (fallback) decision, every
          flush must reach the device, no flush may wait on the compiler, and
          the ATLAS cells must equal those of the same sweep scored by the
          numpy mirror (``executor="serial"``) in this process.

Any failed check exits non-zero before the last line, which on success is
exactly ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

FLEET_SIZE = 1000
SCENARIO = "bursty_tt"
WORKLOAD = "map_heavy"
SCORE_ROWS = 4096
# fit leaves are weighted label means; a division that rounds differently on
# the chip may move one by an ulp, never more
LEAF_RTOL = 1e-6

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_event(event: str, duration: float, **_):
    if event in _COMPILE_EVENTS:
        _compile_s[0] += duration


class Phase:
    """Times a phase on the wall clock and counts the compile time inside it
    (JAX's tracing, lowering and backend-compile events, from any thread)."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = _compile_s[0]
        return self

    def line(self, ok: bool, **fields):
        wall = time.perf_counter() - self.t0
        comp = _compile_s[0] - self.c0
        print(f"[{self.name}] {'PASS' if ok else 'FAIL'} wall={wall:.3f}s "
              f"compile={comp:.3f}s " + json.dumps(fields, sort_keys=True),
              flush=True)
        return ok

    def __exit__(self, *exc):
        return False


class SmokeFailure(RuntimeError):
    pass


def _require(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


def phase_device():
    with Phase("device") as ph:
        devs = jax.devices()
        d = devs[0]
        try:
            from importlib.metadata import version
            libtpu = version("libtpu")
        except Exception:
            libtpu = "not installed"
        import jaxlib
        info = {"platform": d.platform, "kind": d.device_kind,
                "count": len(devs), "jax": jax.__version__,
                "jaxlib": jaxlib.__version__, "libtpu": libtpu}
        ok = d.platform == "tpu"
        ph.line(ok, **info)
        _require(ok, f"no TPU: JAX found {d.platform} ({d.device_kind}) "
                     f"x{len(devs)}")
    return d, {"platform": d.platform, "kind": d.device_kind,
               "count": len(devs)}


def _trace_datasets(seed: int):
    from repro.cluster.experiment import run_scheduler
    from repro.cluster.fleet import CellSpec, SweepSpec, cell_config
    spec = SweepSpec(schedulers=("fifo",), seeds=(seed,),
                     scenarios=(SCENARIO,), workloads=(WORKLOAD,),
                     fleet_sizes=(FLEET_SIZE,))
    cell = CellSpec("fifo", SCENARIO, WORKLOAD, seed, FLEET_SIZE)
    _, trace, _ = run_scheduler("fifo", cell_config(spec, cell),
                                with_trace=True)
    return trace.datasets()


def phase_fit(seed: int, chip):
    from repro.ml.models import RandomForest, Tree
    cpu = jax.devices("cpu")[0]
    with Phase("fit") as ph:
        (mx, my), (rx, ry) = _trace_datasets(seed)
        fields = {"trace_rows": {"map": int(mx.shape[0]),
                                 "reduce": int(rx.shape[0])},
                  "leaf_rtol": LEAF_RTOL}
        models, ok = {}, True
        for kind, X, y in (("map", mx, my), ("reduce", rx, ry)):
            t0 = time.perf_counter()
            with jax.default_device(chip):
                rf = RandomForest().fit(X, y).params
                models[("R.F.", kind)] = rf
                models[("Tree", kind)] = Tree().fit(X, y).params
            t_chip = time.perf_counter() - t0
            t0 = time.perf_counter()
            with jax.default_device(cpu):
                ref = RandomForest().fit(X, y).params
            t_cpu = time.perf_counter() - t0
            splits = (np.array_equal(rf.feat_idx, ref.feat_idx)
                      and np.array_equal(rf.thresholds, ref.thresholds))
            leaf_diff = float(np.max(np.abs(rf.leaves - ref.leaves)
                                     / np.maximum(np.abs(ref.leaves), 1e-30)))
            ok &= splits and leaf_diff <= LEAF_RTOL
            fields[kind] = {"splits_equal": bool(splits),
                            "leaf_max_rel_diff": leaf_diff,
                            "fit_chip_s": round(t_chip, 3),
                            "fit_cpu_s": round(t_cpu, 3)}
        ph.line(ok, **fields)
        _require(ok, "forest fit on the chip differs from the CPU fit")
    return models, np.concatenate([mx, rx])


def phase_score(seed: int, models, rows):
    from repro.kernels import forest as fk
    from repro.ml.forest import forest_predict_grouped, pack_forests
    with Phase("score") as ph:
        rng = np.random.RandomState(seed)
        order = [("R.F.", "map"), ("R.F.", "reduce"), ("Tree", "map"),
                 ("Tree", "reduce")]
        sizes = [1700, 1300, 700, SCORE_ROWS + 13 - 3700]   # uneven segments
        X = rows[rng.randint(0, rows.shape[0], sum(sizes))]
        groups, at = [], 0
        for key, n in zip(order, sizes):
            groups.append((models[key], X[at:at + n]))
            at += n
        passes0 = fk.n_device_passes
        t0 = time.perf_counter()
        got, _ = forest_predict_grouped(groups, impl="pallas")
        t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        got, _ = forest_predict_grouped(groups, impl="pallas")
        t_second = time.perf_counter() - t0
        want, _ = forest_predict_grouped(groups, impl="numpy")
        equal = all(np.array_equal(g, w) for g, w in zip(got, want))
        max_diff = max(float(np.max(np.abs(g - w)))
                       for g, w in zip(got, want))

        packed = pack_forests([models[k] for k in order])
        M, T, D = packed.feat_idx.shape
        seg_of_tile, _ = fk.grouped_layout(sizes, 128)
        sel, thr, lv, nt = fk.grouped_blocks(
            packed.feat_idx, packed.thresholds, packed.leaves,
            packed.n_trees, X.shape[1])
        xp = np.zeros((seg_of_tile.size * 128, X.shape[1]), np.float32)
        text = fk.grouped_call.lower(seg_of_tile, nt, xp, sel, thr, lv, D=D,
                                     block_b=128,
                                     interpret=False).compile().as_text()
        custom_call = "tpu_custom_call" in text
        ran = fk.n_device_passes - passes0 == 2
        ok = equal and custom_call and ran
        ph.line(ok, rows=int(sum(sizes)), models=M, padded_shape=[T, D],
                bit_equal=bool(equal), max_abs_diff=max_diff,
                tpu_custom_call=custom_call, device_passes=fk.n_device_passes
                - passes0, first_call_s=round(t_first, 4),
                second_call_s=round(t_second, 4))
        _require(ok, "grouped kernel on the chip disagrees with the numpy "
                     "mirror, or did not run as a Pallas kernel")


def phase_serve(seed: int):
    from repro.cluster.fleet import (WARM_FLUSH_ROWS, SweepSpec, expand,
                                     run_sweep)
    from repro.cluster.telemetry import N_FEATURES
    from repro.kernels import forest as fk
    from repro.ml.models import forest_shape
    spec = SweepSpec(schedulers=("fifo", "atlas-fifo"),
                     seeds=(seed, seed + 1), scenarios=(SCENARIO,),
                     workloads=(WORKLOAD,), fleet_sizes=(FLEET_SIZE,),
                     check_invariants=True)
    quiet = lambda *a, **k: None
    with Phase("serve") as ph:
        shapes0 = fk.grouped_call._cache_size()
        stats: dict = {}
        t0 = time.perf_counter()
        served = run_sweep(spec, executor="async", fault_stats=stats,
                           log=quiet)
        t_served = time.perf_counter() - t0
        new_shapes = fk.grouped_call._cache_size() - shapes0
        n_atlas = sum(c.scheduler.startswith("atlas") for c in expand(spec))
        # the broker warmed these shapes before its clients started; a
        # re-run hits the jit cache and only counts them
        warmed = fk.warmup_grouped(2 * n_atlas, *forest_shape(spec.algo),
                                   N_FEATURES, WARM_FLUSH_ROWS)
        t0 = time.perf_counter()
        ref = run_sweep(spec, executor="serial", log=quiet)
        t_ref = time.perf_counter() - t0

        broker = served["perf"]["broker"]
        want_cells = {c.cell_id for c in expand(spec)}
        done = {c["cell_id"] for c in served["cells"]} == want_cells
        violations = sum(c["metrics"].get("invariant_violations", 0)
                         for c in served["cells"])
        checks = sum(c["metrics"].get("invariant_checks", 0)
                     for c in served["cells"])
        atlas = lambda r: {c["cell_id"]: (c["metrics"], c["stats"])
                           for c in r["cells"]
                           if c["scheduler"].startswith("atlas")}
        same = atlas(served) == atlas(ref)
        fields = {
            "cells": len(served["cells"]), "all_cells_completed": done,
            "fallbacks": stats["fallbacks"], "flushes": broker["flushes"],
            "device_flushes": stats["device_flushes"],
            "rows": broker["rows"], "max_flush_rows": broker["max_flush_rows"],
            "invariant_checks": int(checks),
            "invariant_violations": int(violations),
            "atlas_metrics_equal_numpy": same,
            "kernel_shapes_compiled": new_shapes, "warmed_shapes": warmed,
            "serve_s": round(t_served, 3), "numpy_reference_s": round(t_ref, 3),
        }
        ok = (done and stats["fallbacks"] == 0 and broker["flushes"] > 0
              and stats["device_flushes"] == broker["flushes"]
              and violations == 0 and checks > 0 and same
              and new_shapes == warmed)
        ph.line(ok, **fields)
        _require(ok, "serving path check failed: " + json.dumps(fields))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        chip, device = phase_device()
        from repro.util import enable_compile_cache
        enable_compile_cache()
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        models, rows = phase_fit(args.seed, chip)
        phase_score(args.seed, models, rows)
        phase_serve(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
