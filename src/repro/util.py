"""Small shared utilities with no heavy dependencies."""

from __future__ import annotations

import hashlib
import os
import pathlib

import numpy as np

# the checkout this package runs from (src/repro/util.py -> repo root)
REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]


def array_digest(arr: np.ndarray, n_hex: int = 16) -> str:
    """Short content digest of an array's raw bytes (sha256 prefix) — the
    integrity stamp used by both the model registry and checkpoint store."""
    return hashlib.sha256(np.asarray(arr).tobytes()).hexdigest()[:n_hex]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps the cache there and
    nothing here overrides it.  Otherwise it lives at ``<checkout>/.jax_cache``:
    a fixed path, since a cache whose directory moves is never hit.  Small
    kernels compile in well under JAX's default one-second floor for caching,
    so every compile is kept.  Returns the directory in use."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
