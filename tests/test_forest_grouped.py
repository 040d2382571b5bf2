"""Block-diagonal grouped inference tests: bit-parity of the packed single
pass against the per-model loop (ragged segments, padded tails, heterogeneous
shapes), the pack cache, and Pallas/XLA grouped-kernel parity."""

import numpy as np
import pytest

from repro.ml.forest import (GROUPED_KERNEL_ROWS, fit_oblivious_forest,
                             forest_predict_grouped, forest_predict_np,
                             pack_forests)


def _data(n=300, f=12, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.rand(n) > 0.8).astype(np.float32)
    return X, y


@pytest.fixture(scope="module")
def models():
    X, y = _data()
    return {
        "a": fit_oblivious_forest(X, y, n_trees=24, depth=5, seed=0),
        "b": fit_oblivious_forest(X, 1 - y, n_trees=24, depth=5, seed=1),
        # ragged shapes: fewer trees, shallower depth -> padded tail in the
        # packed block
        "c": fit_oblivious_forest(X, y, n_trees=8, depth=3, seed=2),
        "d": fit_oblivious_forest(X, 1 - y, n_trees=16, depth=4, seed=3),
    }


def _check_bitwise(groups):
    outs, passes = forest_predict_grouped(groups)
    for (params, rows), out in zip(groups, outs):
        assert np.array_equal(out, forest_predict_np(params, rows)), \
            "block-diagonal pass differs from the per-model loop"
    return passes


@pytest.mark.parametrize("batches", [
    (1,), (1, 1, 1), (7, 33), (1, 64, 2), (65, 1, 5, 12),
])
def test_blockdiag_bitwise_same_shape(models, batches):
    Xq = _data(seed=4)[0]
    names = ["a", "b"]
    groups, at = [], 0
    for i, b in enumerate(batches):
        groups.append((models[names[i % 2]], Xq[at:at + b]))
        at += b
    assert _check_bitwise(groups) == 1


def test_blockdiag_bitwise_heterogeneous_shapes_single_pass(models):
    """Mixed (T, D) shapes pad into ONE block: still one pass, still
    bit-identical per model (the padded tail never enters the tree mean)."""
    Xq = _data(seed=5)[0]
    groups = [(models["a"], Xq[:9]), (models["c"], Xq[9:40]),
              (models["d"], Xq[40:41]), (models["a"], Xq[41:100]),
              (models["c"], Xq[100:103])]
    assert _check_bitwise(groups) == 1


def test_blockdiag_empty_and_single_groups(models):
    Xq = _data(seed=6)[0]
    outs, passes = forest_predict_grouped([(models["a"], Xq[:0])])
    assert passes == 0 and outs[0].shape == (0,)
    # single model takes the shared-block mirror; still bit-identical
    assert _check_bitwise([(models["a"], Xq[:50]),
                           (models["a"], Xq[50:51])]) == 1


def test_blockdiag_row_order_between_segments_irrelevant(models):
    """Interleaved group order (a, b, a, b) must score each row identically
    to contiguous per-model calls — the segment reshuffle is internal."""
    Xq = _data(seed=7)[0]
    groups = [(models["a"], Xq[:5]), (models["b"], Xq[5:30]),
              (models["a"], Xq[30:60]), (models["b"], Xq[60:61])]
    _check_bitwise(groups)


def test_pack_forests_padded_tail_layout(models):
    packed = pack_forests([models["a"], models["c"]])
    M, T, D = packed.feat_idx.shape
    assert (M, T, D) == (2, 24, 5)
    assert packed.n_trees.tolist() == [24, 8]
    # padded levels test +inf (bits identically False), padded trees have
    # all-zero leaves (contribute exactly 0 to any sum)
    assert np.all(np.isinf(packed.thresholds[1, :8, 3:]))
    assert np.all(np.isinf(packed.thresholds[1, 8:]))
    assert np.all(packed.leaves[1, 8:] == 0.0)
    # model c's leaf l lives at l << (5 - 3)
    c = models["c"]
    assert np.array_equal(packed.leaves[1][:8][:, np.arange(8) << 2], c.leaves)


def test_grouped_kernel_parity_xla_and_interpret(models):
    pytest.importorskip("jax.experimental.pallas")
    Xq = _data(seed=8, n=700)[0]
    groups = [(models["a"], Xq[:300]), (models["b"], Xq[300:550]),
              (models["c"], Xq[550:]), (models["a"], Xq[:0])]
    want, _ = forest_predict_grouped(groups)
    for impl in ("xla", "interpret"):
        outs, passes = forest_predict_grouped(groups, impl=impl)
        assert passes == 1
        for w, o in zip(want, outs):
            np.testing.assert_allclose(o, w, rtol=2e-5, atol=2e-5)
            if impl == "interpret":      # the Pallas kernel is exact
                np.testing.assert_array_equal(o, w)


@pytest.mark.parametrize("sizes", [
    (1, 127, 128, 129, 300),             # tile edges inside and across
    (700, 3, 0, 45, 1),                  # an empty group, tiny segments
])
def test_grouped_kernel_interpret_bitwise_heterogeneous(models, sizes):
    """Heterogeneous padded models (24x5, 8x3, 16x4 and a 1x6 tree packed
    to 24x6) over uneven segments: the interpreted grouped kernel equals
    forest_predict_np of each row's own model bit for bit."""
    X, y = _data(seed=11)
    tree = fit_oblivious_forest(X, y, n_trees=1, depth=6, n_bins=16,
                                bootstrap=False)
    order = [models["a"], models["c"], tree, models["d"], models["a"]]
    Xq = np.random.RandomState(12).rand(sum(sizes), 12).astype(np.float32)
    groups, at = [], 0
    for params, n in zip(order, sizes):
        groups.append((params, Xq[at:at + n]))
        at += n
    outs, passes = forest_predict_grouped(groups, impl="interpret")
    assert passes == 1
    for (params, rows), out in zip(groups, outs):
        np.testing.assert_array_equal(out, forest_predict_np(params, rows))


def test_auto_routes_fat_flushes_to_kernel(models):
    n = GROUPED_KERNEL_ROWS + 64
    Xq = np.random.RandomState(9).rand(n, 12).astype(np.float32)
    small, _ = forest_predict_grouped(
        [(models["a"], Xq[:8])], impl="auto")        # numpy path
    assert np.array_equal(small[0], forest_predict_np(models["a"], Xq[:8]))
    fat, passes = forest_predict_grouped(
        [(models["a"], Xq[:n // 2]), (models["b"], Xq[n // 2:])], impl="auto")
    assert passes == 1
    np.testing.assert_allclose(
        fat[0], forest_predict_np(models["a"], Xq[:n // 2]),
        rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        fat[1], forest_predict_np(models["b"], Xq[n // 2:]),
        rtol=2e-5, atol=2e-5)
