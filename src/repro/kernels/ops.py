"""Jit-ready dispatch wrappers around the Pallas kernels.

Every op takes ``impl``:
  "xla"        pure-jnp flash-style path (ref.py) — CPU smoke tests + the multi-pod
               dry-run (Pallas TPU kernels don't lower on the CPU host backend).
  "pallas"     compiled Pallas TPU kernel — the production path on real hardware.
  "interpret"  Pallas kernel body interpreted on CPU — correctness tests.

``impl=None`` picks from the backend (``default_impl``): the compiled kernel
on a TPU, the ``xla`` reference everywhere else.
"""

from __future__ import annotations

import jax

from repro.kernels import ref

_VALID = ("xla", "pallas", "interpret")


def default_impl() -> str:
    """The kernel path for this process's default JAX backend."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _resolve(impl: str | None) -> str:
    impl = impl or default_impl()
    if impl not in _VALID:
        raise ValueError(f"impl must be one of {_VALID}, got {impl!r}")
    return impl


def flash_attention(q, k, v, *, causal=True, window=0, impl=None,
                    q_chunk=512, kv_chunk=512):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       q_chunk=q_chunk, kv_chunk=kv_chunk)
    from repro.kernels import flash_attention as fk
    return fk.flash_attention(q, k, v, causal=causal, window=window,
                              interpret=(impl == "interpret"))


def decode_attention(q, k, v, kv_len, *, window=0, impl=None, kv_chunk=1024):
    impl = _resolve(impl)
    if impl == "xla":
        # full-cache einsum form: GSPMD shards it over kv_seq with automatic
        # partial-softmax merge collectives (see ref.decode_attention_xla)
        return ref.decode_attention_xla(q, k, v, kv_len, window=window)
    from repro.kernels import decode_attention as dk
    return dk.decode_attention(q, k, v, kv_len, window=window,
                               interpret=(impl == "interpret"))


def rwkv6_scan(r, k, v, w, u, state0, *, impl=None):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.rwkv6_scan_ref(r, k, v, w, u, state0)
    from repro.kernels import rwkv6_scan as rk
    return rk.rwkv6_scan(r, k, v, w, u, state0, interpret=(impl == "interpret"))


def mamba2_ssd(x, dt, A, B, C, state0, *, impl=None):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.mamba2_ssd_ref(x, dt, A, B, C, state0)
    from repro.kernels import mamba2_ssd as mk
    return mk.mamba2_ssd(x, dt, A, B, C, state0, interpret=(impl == "interpret"))


def forest_infer(x, feat_idx, thresholds, leaves, *, impl=None):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.forest_infer_ref(x, feat_idx, thresholds, leaves)
    from repro.kernels import forest as fk
    return fk.forest_infer(x, feat_idx, thresholds, leaves,
                           interpret=(impl == "interpret"))


def forest_infer_grouped(x, seg_sizes, feat_idx, thresholds, leaves, n_trees,
                         *, impl=None):
    """Block-diagonal grouped forest inference over the packed multi-model
    layout (see ml.forest.pack_forests); rows stacked segment-by-segment."""
    import numpy as np

    impl = _resolve(impl)
    if impl == "xla":
        import jax.numpy as jnp
        seg_ids = np.repeat(np.arange(len(seg_sizes), dtype=np.int32),
                            np.asarray(seg_sizes))
        return ref.forest_infer_grouped_ref(
            jnp.asarray(x, jnp.float32), jnp.asarray(seg_ids),
            jnp.asarray(feat_idx), jnp.asarray(thresholds),
            jnp.asarray(leaves), jnp.asarray(n_trees))
    from repro.kernels import forest as fk
    return fk.forest_infer_grouped(x, seg_sizes, feat_idx, thresholds,
                                   leaves, n_trees,
                                   interpret=(impl == "interpret"))
