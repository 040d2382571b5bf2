"""Training launcher: `python -m repro.launch.train --arch <id> [options]`.

On a real TPU fleet this builds the production mesh and runs the sharded train
step under the ATLAS elastic runtime; on the CPU host it runs the reduced config
(the full configs are exercised via the dry-run).  Either way the control loop is
the same ElasticTrainer (checkpoint/restart, ATLAS placement, speculative shard
duplication, adaptive heartbeats)."""

from __future__ import annotations

import argparse
import pathlib

from repro.configs import ARCH_IDS, get_arch, smoke_reduce
from repro.data import DataConfig
from repro.runtime import ElasticTrainer, RuntimeConfig
from repro.util import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--hosts", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full architecture (TPU fleets only)")
    ap.add_argument("--fail-rate", type=float, default=0.01)
    ap.add_argument("--atlas", dest="atlas", action="store_true", default=True)
    ap.add_argument("--no-atlas", dest="atlas", action="store_false")
    ap.add_argument("--checkpoint-dir", default="checkpoints")
    ap.add_argument("--checkpoint-every", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    arch = get_arch(args.arch)
    if not args.full_config:
        arch = smoke_reduce(arch)
    print(f"[train] arch={arch.name} layers={arch.n_layers} "
          f"d_model={arch.d_model} atlas={args.atlas}")

    rcfg = RuntimeConfig(n_hosts=args.hosts, steps=args.steps,
                         checkpoint_every=args.checkpoint_every,
                         atlas=args.atlas, fail_rate=args.fail_rate,
                         seed=args.seed)
    ckpt = pathlib.Path(args.checkpoint_dir) / arch.name
    trainer = ElasticTrainer(
        arch, rcfg, ckpt,
        data_cfg=DataConfig(vocab_size=arch.vocab_size, seq_len=args.seq_len,
                            global_batch=args.global_batch, seed=args.seed))
    out = trainer.run()
    for k, v in out.items():
        print(f"[train] {k}: {v}")


if __name__ == "__main__":
    main()
