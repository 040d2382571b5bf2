import os
os.environ["XLA_FLAGS"] = os.environ.get("REPRO_DRYRUN_XLA_FLAGS",
                                         "--xla_force_host_platform_device_count=512")
# The two lines above MUST run before any other import (jax locks the device count
# on first initialisation).  Everything below is ordinary.

"""Multi-pod dry-run: lower + compile every (architecture x input shape) cell on the
production meshes (16x16 single-pod and 2x16x16 multi-pod), recording
memory_analysis / cost_analysis / collective-traffic for EXPERIMENTS.md §Dry-run and
the §Roofline table.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]

Results cache to experiments/dryrun/<arch>__<shape>__<mesh>.json; --force recomputes.
"""

import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs import SHAPES, get_arch, cell_supported, ARCH_IDS
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def run_cell(arch_id: str, shape_id: str, *, multi_pod: bool = False,
             force: bool = False, verbose: bool = True) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    out_path = OUT_DIR / f"{arch_id}__{shape_id}__{mesh_name}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    arch = get_arch(arch_id)
    shape = SHAPES[shape_id]
    ok, why = cell_supported(arch, shape)
    if not ok:
        rec = {"arch": arch_id, "shape": shape_id, "mesh": mesh_name,
               "status": "skipped", "reason": why}
        out_path.write_text(json.dumps(rec, indent=2))
        return rec

    t0 = time.time()
    rec = {"arch": arch_id, "shape": shape_id, "mesh": mesh_name}
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        with mesh:
            cell = build_cell(arch, shape, mesh)
            jitted = jax.jit(cell["fn"], in_shardings=cell["in_shardings"],
                             out_shardings=cell["out_shardings"],
                             donate_argnums=cell["donate_argnums"])
            lowered = jitted.lower(*cell["args"])
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
            # loop-aware accounting: XLA's cost_analysis counts while bodies once,
            # which undercounts scan-over-layers models by ~n_layers (see
            # repro.launch.hlo_cost + tests/test_hlo_cost.py)
            la = hlo_cost.analyze(hlo)

            rec.update({
                "status": "ok",
                "lower_s": round(t_lower, 1),
                "compile_s": round(t_compile, 1),
                "memory": {
                    "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                    "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                    "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                    "generated_code_bytes":
                        getattr(mem, "generated_code_size_in_bytes", 0),
                    "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
                },
                "cost_xla_raw": {
                    "flops": float(cost.get("flops", 0.0)),
                    "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
                },
                "cost": {
                    "flops": la["flops"],
                    "bytes_accessed": la["traffic_bytes"],
                    "transcendentals": la["transcendentals"],
                    "unknown_trip_loops": la["unknown_trip_loops"],
                },
                "collectives": la["collectives"],
                "n_devices": mesh.devices.size,
            })
            if verbose:
                print(f"[dryrun] {arch_id} x {shape_id} x {mesh_name}: OK "
                      f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s, "
                      f"temp {rec['memory']['temp_bytes']/2**30:.2f} GiB/dev, "
                      f"flops/dev {rec['cost']['flops']:.3g}, "
                      f"coll {la['collectives'].get('total', 0)/2**30:.2f} GiB/dev)")
    except Exception as e:  # noqa: BLE001 — a failed cell is a recorded result
        rec.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
        if verbose:
            print(f"[dryrun] {arch_id} x {shape_id} x {mesh_name}: "
                  f"FAILED {type(e).__name__}: {e}")
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS))
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]

    n_ok = n_skip = n_err = 0
    for mp in meshes:
        for a in archs:
            for s in shapes:
                rec = run_cell(a, s, multi_pod=mp, force=args.force)
                st = rec["status"]
                n_ok += st == "ok"
                n_skip += st == "skipped"
                n_err += st == "error"
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
