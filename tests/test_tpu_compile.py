"""The forest kernels compile for a TPU v5e at the predictor's real widths.

No chip is needed: the TPU compiler compiles for a described topology, which
catches what interpret mode cannot (Mosaic layout and tiling rules).  The
topology is described inside a module fixture, never at import time, so
every test worker collects the same tests and only the worker that runs
this file loads the TPU library.  Nothing runs: a pass here is a compile,
not a chip run."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import forest as fk

F = 22                      # cluster.telemetry.N_FEATURES
RF_TREES, RF_DEPTH = 24, 5  # ml.models.RandomForest


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("B", [37, 4096])
def test_forest_infer_compiles_for_v5e(one_chip, B):
    """forest_infer's device half (its tree sums; the host divides by T)."""
    T, D = RF_TREES, RF_DEPTH
    compiled = fk.forest_tree_sums.lower(
        _shape(one_chip, (B, F)), _shape(one_chip, (T, D), jnp.int32),
        _shape(one_chip, (T, D)), _shape(one_chip, (T, 1 << D)),
        block_b=256, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_forest_infer_grouped_compiles_for_v5e(one_chip):
    """24 models padded to 24 trees x depth 6 (an R.F. and a Tree share one
    block), 4096 rows: the serving flush shape."""
    M, T, D, rows, block_b = 24, RF_TREES, 6, 4096, 128
    n_tiles = fk._tile_bucket(rows // block_b + M)
    compiled = fk.grouped_call.lower(
        _shape(one_chip, (n_tiles,), jnp.int32),
        _shape(one_chip, (M,), jnp.int32),
        _shape(one_chip, (n_tiles * block_b, F)),
        _shape(one_chip, (M, T * D, F)),
        _shape(one_chip, (M, T * D, 1)),
        _shape(one_chip, (M, T, 1 << D)),
        D=D, block_b=block_b, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
