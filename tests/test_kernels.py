"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle in ref.py,
swept over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.forest import forest_infer
from repro.kernels.mamba2_ssd import mamba2_ssd
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.ml.forest import ForestParams, forest_predict_np


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,Hkv,D,qb,kb", [
    (1, 128, 4, 4, 64, 64, 64),      # MHA
    (2, 256, 8, 2, 64, 128, 64),     # GQA 4:1
    (1, 512, 4, 1, 128, 128, 256),   # MQA, head_dim 128
    (2, 128, 6, 2, 32, 32, 64),      # odd head count
])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 64)])
def test_flash_attention(dtype, B, S, H, Hkv, D, qb, kb, causal, window):
    key = jax.random.PRNGKey(42)
    q = jax.random.normal(key, (B, S, H, D), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, D), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, D), dtype)
    want = ref.attention_naive(q, k, v, causal=causal, window=window)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          q_block=qb, kv_block=kb, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_ref_matches_naive(dtype):
    """The chunked XLA path (used by models + dry-run) against the naive oracle."""
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (2, 256, 8, 64), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 256, 4, 64), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 256, 4, 64), dtype)
    want = ref.attention_naive(q, k, v, causal=True)
    got = ref.flash_attention_ref(q, k, v, causal=True, q_chunk=64, kv_chunk=128)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,D,Smax,kb", [
    (2, 4, 4, 64, 512, 128),
    (3, 8, 2, 64, 1024, 256),
    (1, 8, 1, 128, 2048, 512),
])
def test_decode_attention(dtype, B, H, Hkv, D, Smax, kb):
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (B, 1, H, D), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Smax, Hkv, D), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Smax, Hkv, D), dtype)
    kv_len = jnp.asarray(
        np.random.RandomState(0).randint(1, Smax + 1, (B,)), jnp.int32)
    want = ref.decode_attention_ref(q, k, v, kv_len)
    got = decode_attention(q, k, v, kv_len, kv_block=kb, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_decode_attention_window():
    key = jax.random.PRNGKey(4)
    B, H, Hkv, D, Smax = 2, 4, 2, 64, 1024
    q = jax.random.normal(key, (B, 1, H, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Smax, Hkv, D), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Smax, Hkv, D), jnp.float32)
    kv_len = jnp.array([1024, 700], jnp.int32)
    want = ref.decode_attention_ref(q, k, v, kv_len, window=256)
    got = decode_attention(q, k, v, kv_len, window=256, kv_block=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,Dh,chunk", [
    (1, 64, 2, 16, 16),
    (2, 128, 4, 64, 64),
    (1, 256, 8, 32, 128),
])
def test_rwkv6_scan(dtype, B, S, H, Dh, chunk):
    key = jax.random.PRNGKey(5)
    r = jax.random.normal(key, (B, S, H, Dh), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, Dh), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, Dh), dtype)
    w = jax.nn.sigmoid(jax.random.normal(
        jax.random.fold_in(key, 3), (B, S, H, Dh), jnp.float32) * 2).astype(dtype)
    u = (jax.random.normal(jax.random.fold_in(key, 4), (H, Dh), jnp.float32)
         * 0.3).astype(dtype)
    s0 = jax.random.normal(jax.random.fold_in(key, 5), (B, H, Dh, Dh), jnp.float32)
    want_y, want_s = ref.rwkv6_scan_ref(r, k, v, w, u, s0)
    got_y, got_s = rwkv6_scan(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(np.asarray(got_y, np.float32),
                               np.asarray(want_y, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-3, atol=1e-3)


def test_rwkv6_scan_chunk_boundary_consistency():
    """Chunk size must not change results (state carry across chunks is exact)."""
    key = jax.random.PRNGKey(6)
    B, S, H, Dh = 1, 128, 2, 32
    mk = lambda i: jax.random.normal(jax.random.fold_in(key, i), (B, S, H, Dh),
                                     jnp.float32)
    r, k, v = mk(0), mk(1), mk(2)
    w = jax.nn.sigmoid(mk(3))
    u = jax.random.normal(jax.random.fold_in(key, 4), (H, Dh)) * 0.1
    s0 = jnp.zeros((B, H, Dh, Dh), jnp.float32)
    y32, s32 = rwkv6_scan(r, k, v, w, u, s0, chunk=32, interpret=True)
    y128, s128 = rwkv6_scan(r, k, v, w, u, s0, chunk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(y32), np.asarray(y128), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s32), np.asarray(s128), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 64, 2, 16, 16, 16),
    (2, 128, 4, 64, 64, 64),
    (1, 256, 8, 32, 16, 128),
])
def test_mamba2_ssd(dtype, B, S, H, P, N, chunk):
    key = jax.random.PRNGKey(8)
    x = jax.random.normal(key, (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(
        jax.random.fold_in(key, 1), (B, S, H), jnp.float32)).astype(dtype)
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (H,)) * 0.5)
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (B, S, N), dtype)
    Cm = jax.random.normal(jax.random.fold_in(key, 4), (B, S, N), dtype)
    s0 = jax.random.normal(jax.random.fold_in(key, 5), (B, H, P, N), jnp.float32)
    want_y, want_s = ref.mamba2_ssd_ref(x, dt, A, Bm, Cm, s0)
    got_y, got_s = mamba2_ssd(x, dt, A.astype(jnp.float32), Bm, Cm, s0,
                              chunk=chunk, interpret=True)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(np.asarray(got_y, np.float32),
                               np.asarray(want_y, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("B,F,T,D,bb", [
    (32, 16, 8, 4, 16),
    (100, 32, 64, 6, 32),    # non-divisible batch -> padding path
    (256, 24, 128, 6, 128),
])
def test_forest_infer(B, F, T, D, bb):
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(B, F), jnp.float32)
    feat_idx = jnp.asarray(rs.randint(0, F, (T, D)), jnp.int32)
    thr = jnp.asarray(rs.randn(T, D), jnp.float32)
    leaves = jnp.asarray(rs.randn(T, 2 ** D), jnp.float32)
    want = ref.forest_infer_ref(x, feat_idx, thr, leaves)
    got = forest_infer(x, feat_idx, thr, leaves, block_b=bb, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # the kernel's arithmetic is the numpy mirror's: bit for bit
    params = ForestParams(np.asarray(feat_idx), np.asarray(thr),
                          np.asarray(leaves))
    np.testing.assert_array_equal(np.asarray(got),
                                  forest_predict_np(params, np.asarray(x)))


def test_forest_infer_vs_sklearn_style_traversal():
    """Independent python traversal (no jnp) as a second oracle."""
    rs = np.random.RandomState(2)
    B, F, T, D = 17, 8, 5, 3
    x = rs.randn(B, F).astype(np.float32)
    feat_idx = rs.randint(0, F, (T, D))
    thr = rs.randn(T, D).astype(np.float32)
    leaves = rs.randn(T, 2 ** D).astype(np.float32)
    want = np.zeros(B)
    for b in range(B):
        for t in range(T):
            leaf = 0
            for d in range(D):
                leaf = (leaf << 1) | int(x[b, feat_idx[t, d]] > thr[t, d])
            want[b] += leaves[t, leaf]
    want /= T
    got = forest_infer(jnp.asarray(x), jnp.asarray(feat_idx, jnp.int32),
                       jnp.asarray(thr), jnp.asarray(leaves), interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_forest_infer_interpret_bitwise_real_widths():
    """The predictor's real widths (24 trees x depth 5 over 22 features), a
    ragged batch, features sitting exactly on thresholds and just off a bf16
    rounding boundary: the interpreted kernel equals the numpy mirror bit
    for bit."""
    rs = np.random.RandomState(5)
    F, T, D = 22, 24, 5
    params = ForestParams(rs.randint(0, F, (T, D)).astype(np.int32),
                          rs.randn(T, D).astype(np.float32),
                          rs.rand(T, 2 ** D).astype(np.float32))
    x = (rs.randn(300, F) * 10.0 ** rs.randint(-3, 4, (1, F))) \
        .astype(np.float32)
    x[:40, params.feat_idx[0, 0]] = params.thresholds[0, 0]
    x[40:80] *= np.float32(1 + 2.0 ** -12)
    got = forest_infer(jnp.asarray(x), jnp.asarray(params.feat_idx),
                       jnp.asarray(params.thresholds),
                       jnp.asarray(params.leaves), interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  forest_predict_np(params, x))


def test_kernel_impl_follows_the_backend():
    """On the CPU backend ops resolve to the xla reference and the serving
    brokers score through the numpy mirror; only a TPU selects the kernel."""
    from repro.kernels import ops
    from repro.ml.forest import serving_impl
    assert jax.default_backend() == "cpu"
    assert ops.default_impl() == "xla"
    assert ops._resolve(None) == "xla"
    assert ops._resolve("interpret") == "interpret"
    assert serving_impl() == "numpy"
    with pytest.raises(ValueError):
        ops._resolve("cuda")


def test_forest_predict_np_matches_kernel_reference():
    """The numpy small-batch mirror (the scheduler's per-decision inference
    path) must agree with the XLA/ref kernel path — including tree_slice —
    for batches on both sides of the SMALL_BATCH routing threshold."""
    from repro.ml.forest import (ForestParams, SMALL_BATCH, forest_predict,
                                 forest_predict_np)
    rs = np.random.RandomState(3)
    F, T, D = 22, 24, 5
    params = ForestParams(
        feat_idx=rs.randint(0, F, (T, D)).astype(np.int32),
        thresholds=rs.randn(T, D).astype(np.float32),
        leaves=rs.rand(T, 2 ** D).astype(np.float32))
    for B in (1, 13, SMALL_BATCH, SMALL_BATCH + 1, 200):
        x = rs.randn(B, F).astype(np.float32)
        want = np.asarray(ref.forest_infer_ref(
            jnp.asarray(x), jnp.asarray(params.feat_idx),
            jnp.asarray(params.thresholds), jnp.asarray(params.leaves)))
        got_np = forest_predict_np(params, x)
        got_routed = forest_predict(params, x)          # auto small/large path
        np.testing.assert_allclose(got_np, want, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got_routed, want, rtol=1e-5, atol=1e-6)
    # tree_slice parity on a sub-forest
    x = rs.randn(9, F).astype(np.float32)
    sl = slice(4, 16)
    want = np.asarray(ref.forest_infer_ref(
        jnp.asarray(x), jnp.asarray(params.feat_idx[sl]),
        jnp.asarray(params.thresholds[sl]), jnp.asarray(params.leaves[sl])))
    got = forest_predict_np(params, x, tree_slice=sl)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
