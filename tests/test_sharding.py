"""Distribution-layer tests: rule resolution, spec building, and a miniature
end-to-end sharded train step on a small host mesh (fast — no 512-dev compile;
the full grid is covered by launch/dryrun.py artifacts)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_arch, smoke_reduce, cell_supported
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import arch_rules, batch_specs, build_cell
from repro.parallel.axes import logical_to_spec, make_rules


def _mesh22():
    """Rule-resolution tests only read axis names — an AbstractMesh needs no
    devices, so these run on a single-device host too."""
    if jax.device_count() >= 4:
        return jax.make_mesh((2, 2), ("data", "model"))
    return jax.sharding.AbstractMesh((2, 2), ("data", "model"))


def test_rules_resolution_basics():
    mesh = _mesh22()
    rules = make_rules()
    assert logical_to_spec(("batch", "seq"), rules, mesh) == P("data", None)
    assert logical_to_spec(("embed", "ff"), rules, mesh) == P(None, "model")
    # 'pod' dropped on single-pod meshes
    assert logical_to_spec(("batch",), rules, mesh) == P("data")


def test_rules_no_duplicate_mesh_axes():
    mesh = _mesh22()
    rules = make_rules(fsdp=True)
    # embed->data, but batch already used data: second use must drop
    spec = logical_to_spec(("batch", "embed"), rules, mesh)
    flat = [a for part in spec if part for a in
            (part if isinstance(part, tuple) else (part,))]
    assert len(flat) == len(set(flat))


def test_fsdp_rules_shard_embed():
    mesh = _mesh22()
    rules = make_rules(fsdp=True)
    assert logical_to_spec(("embed", "ff"), mesh=mesh, rules=rules) == \
        P("data", "model")


def test_decode_rules_shard_kv_seq():
    arch = get_arch("stablelm-1.6b")
    mesh = _mesh22()
    rules = arch_rules(arch, SHAPES["decode_32k"], mesh)
    spec = logical_to_spec(("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
                           rules, mesh)
    assert spec[2] == "model"  # cache sequence dim sharded over model


def test_long_context_rules_sequence_parallel():
    arch = get_arch("rwkv6-1.6b")
    mesh = _mesh22()
    rules = arch_rules(arch, SHAPES["long_500k"], mesh)
    assert rules["batch"] is None  # batch=1 cannot shard


def test_cell_supported_matrix():
    grid = [(a, s) for a in ("stablelm-12b", "rwkv6-1.6b", "zamba2-1.2b")
            for s in SHAPES.values()]
    results = {(a, s.name): cell_supported(get_arch(a), s)[0] for a, s in grid}
    assert results[("stablelm-12b", "long_500k")] is False
    assert results[("rwkv6-1.6b", "long_500k")] is True
    assert results[("zamba2-1.2b", "long_500k")] is True
    assert all(results[(a, s)] for a in ("stablelm-12b", "rwkv6-1.6b")
               for s in ("train_4k", "prefill_32k", "decode_32k"))


def test_production_mesh_shapes():
    # uses however many host devices exist; only the *structure* is asserted via
    # the axis names (actual 256/512-dev construction happens in dryrun.py)
    try:
        mesh = make_production_mesh()
    except ValueError:
        pytest.skip("not enough host devices outside the dryrun environment")
    assert mesh.axis_names == ("data", "model")


@pytest.mark.parametrize("arch_id", ["stablelm-1.6b", "deepseek-moe-16b",
                                     "rwkv6-1.6b", "zamba2-1.2b"])
def test_sharded_train_step_matches_unsharded(arch_id):
    """The same reduced config, same batch: train step on a (2,2) mesh must match
    the single-device step numerically (the sharding is semantics-preserving)."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 host devices (tests/conftest sets 8)")
    arch = smoke_reduce(get_arch(arch_id))
    arch = dataclasses.replace(arch, accum_steps=1)
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=4)

    from repro.models.steps import init_train_state, make_train_step
    from repro.optim import AdamWConfig
    opt = AdamWConfig(warmup_steps=1, total_steps=4)

    # unsharded
    step_fn, _ = make_train_step(arch, opt)
    state0 = init_train_state(arch, jax.random.PRNGKey(0), opt)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                                arch.vocab_size, jnp.int32)
    _, m_ref = jax.jit(step_fn)(state0, {"tokens": tokens})

    # sharded
    mesh = _mesh22()
    with mesh:
        cell = build_cell(arch, shape, mesh)
        jitted = jax.jit(cell["fn"], in_shardings=cell["in_shardings"],
                         out_shardings=cell["out_shardings"])
        state1 = init_train_state(arch, jax.random.PRNGKey(0), opt)
        _, m_sh = jitted(state1, {"tokens": tokens})
    np.testing.assert_allclose(float(m_sh["loss"]), float(m_ref["loss"]),
                               rtol=5e-3, atol=5e-4)


def test_batch_specs_shapes():
    arch = get_arch("llama-3.2-vision-90b")
    b = batch_specs(arch, SHAPES["train_4k"])
    assert b["tokens"].shape == (256, 4096)
    assert b["media"].shape == (256, 1024, 8192)
    d = batch_specs(arch, SHAPES["decode_32k"])
    assert d["tokens"].shape == (128, 1)
    assert d["pos"].shape == (128,)
