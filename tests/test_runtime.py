"""Runtime substrate tests: checkpoint/restore, gradient compression, data
pipeline determinism, and the ATLAS elastic trainer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch, smoke_reduce
from repro.data import DataConfig, SyntheticStream
from repro.optim.compression import BLOCK, compress, compressed_psum, decompress
from repro.runtime import ElasticTrainer, RuntimeConfig


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (16, 8)),
            "b": {"c": jnp.arange(5, dtype=jnp.int32),
                  "d": jnp.float32(3.5)}}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path, async_write=False)
    t = _tree()
    mgr.save(7, t)
    got = mgr.restore(7, t)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_keeps_latest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2, async_write=False)
    t = _tree()
    for s in (1, 2, 3, 4):
        mgr.save(s, t)
    assert mgr.all_steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_detects_corruption(tmp_path):
    mgr = CheckpointManager(tmp_path, async_write=False)
    t = _tree()
    mgr.save(1, t)
    # corrupt the shard
    shard = next((tmp_path / "step_000000001").glob("*.npz"))
    data = dict(np.load(shard))
    data["leaf_0"] = data["leaf_0"] + 1.0
    np.savez(shard, **data)
    with pytest.raises(IOError, match="digest"):
        mgr.restore(1, t)


def test_checkpoint_async_write(tmp_path):
    mgr = CheckpointManager(tmp_path, async_write=True)
    t = _tree()
    mgr.save(3, t)
    mgr.wait()
    assert mgr.latest_step() == 3


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_compress_roundtrip_error_bounded():
    g = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3.0
    q, scale, resid = compress(g)
    deq = decompress(q, scale, g.shape)
    # error bounded by scale/2 per element
    err = np.abs(np.asarray(deq) - np.asarray(g))
    per_block_scale = np.repeat(np.asarray(scale, np.float32),
                                BLOCK)[: g.size]
    assert (err <= per_block_scale * 0.5 + 1e-6).all()
    np.testing.assert_allclose(np.asarray(resid), np.asarray(g) - np.asarray(deq),
                               rtol=1e-5, atol=1e-6)


def test_error_feedback_mean_converges():
    """With error feedback, the time-average of dequantised gradients converges to
    the true mean gradient (the residual doesn't accumulate)."""
    rs = np.random.RandomState(0)
    g_true = jnp.asarray(rs.randn(512).astype(np.float32))
    resid = jnp.zeros_like(g_true)
    total = jnp.zeros_like(g_true)
    T = 50
    for _ in range(T):
        q, scale, resid = compress(g_true + resid)
        total = total + decompress(q, scale, g_true.shape)
    np.testing.assert_allclose(np.asarray(total / T), np.asarray(g_true),
                               rtol=0.05, atol=0.02)


def test_compressed_psum_single_device():
    g = jnp.ones((300,)) * 0.5
    mesh = jax.make_mesh((1,), ("x",))
    from jax.sharding import PartitionSpec as P
    out, resid = jax.shard_map(
        lambda g: compressed_psum(g, "x"), mesh=mesh,
        in_specs=(P(),), out_specs=(P(), P()))(g)
    np.testing.assert_allclose(np.asarray(out), 0.5, rtol=1e-2)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_stream_deterministic_and_resharding_consistent():
    cfg = DataConfig(vocab_size=128, seq_len=32, global_batch=8, seed=3)
    s = SyntheticStream(cfg)
    b1 = s.batch(5, 0, 2)
    b2 = s.batch(5, 0, 2)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = s.batch(5, 1, 2)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    assert b1["tokens"].shape == (4, 32)
    full = s.batch(5, 0, 1)
    assert full["tokens"].shape == (8, 32)


def test_stream_tokens_in_vocab():
    cfg = DataConfig(vocab_size=64, seq_len=16, global_batch=4, seed=0)
    b = SyntheticStream(cfg).batch(0, 0, 1)
    assert b["tokens"].min() >= 0 and b["tokens"].max() < 64


# ---------------------------------------------------------------------------
# elastic trainer
# ---------------------------------------------------------------------------

def _tiny_arch():
    import jax.numpy as jnp
    arch = smoke_reduce(get_arch("stablelm-1.6b"))
    return dataclasses.replace(arch, n_layers=2, d_model=64, d_ff=128,
                               vocab_size=256, n_heads=2, n_kv_heads=2,
                               head_dim=32)


def test_elastic_trainer_no_chaos_trains(tmp_path):
    arch = _tiny_arch()
    rcfg = RuntimeConfig(n_hosts=4, steps=12, fail_rate=0.0, degrade_rate=0.0,
                         checkpoint_every=5, seed=0)
    out = ElasticTrainer(arch, rcfg, tmp_path / "ck",
                         data_cfg=DataConfig(vocab_size=arch.vocab_size,
                                             seq_len=32, global_batch=8)).run()
    assert out["committed"] == 12
    assert out["rollbacks"] == 0
    assert out["final_loss"] < out["first_loss"]  # it actually learns


def test_elastic_trainer_survives_chaos(tmp_path):
    arch = _tiny_arch()
    rcfg = RuntimeConfig(n_hosts=4, steps=15, fail_rate=0.06, degrade_rate=0.15,
                         checkpoint_every=3, seed=1)
    out = ElasticTrainer(arch, rcfg, tmp_path / "ck",
                         data_cfg=DataConfig(vocab_size=arch.vocab_size,
                                             seq_len=32, global_batch=8)).run()
    # reaches the target step count despite failures (via rollbacks)
    assert out["committed"] >= 15
    assert np.isfinite(out["final_loss"])


def test_atlas_reduces_lost_steps_vs_baseline(tmp_path):
    """The headline property transported to training: ATLAS placement +
    speculative duplication loses fewer steps under the same chaos seed."""
    arch = _tiny_arch()
    dc = DataConfig(vocab_size=arch.vocab_size, seq_len=32, global_batch=8)
    results = {}
    for atlas in (False, True):
        rcfg = RuntimeConfig(n_hosts=4, steps=20, fail_rate=0.05,
                             degrade_rate=0.2, checkpoint_every=4,
                             atlas=atlas, seed=7)
        out = ElasticTrainer(arch, rcfg, tmp_path / f"ck_{atlas}",
                             data_cfg=dc).run()
        results[atlas] = out
    assert results[True]["lost_steps"] <= results[False]["lost_steps"] + 1
