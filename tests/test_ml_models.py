"""Predictive-model tests: the six algorithms learn a separable task-failure
pattern; the forest trainer respects its structural invariants."""

import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.ml.cv import cross_validate, metrics
from repro.ml.forest import fit_oblivious_forest, forest_predict
from repro.ml.models import ALL_MODELS, forest_shape


def _synthetic(n=2000, seed=0):
    """Failure pattern similar to the simulator's hazard: outcome depends on a few
    features nonlinearly."""
    rs = np.random.RandomState(seed)
    X = rs.randn(n, 8).astype(np.float32)
    logit = 1.2 * X[:, 0] - 0.8 * X[:, 1] + 1.5 * (X[:, 2] > 0.5) - 0.6
    p = 1 / (1 + np.exp(-logit))
    y = (rs.rand(n) < p).astype(np.float32)
    return X, y


@pytest.mark.parametrize("name", list(ALL_MODELS))
def test_each_model_beats_majority_class(name):
    X, y = _synthetic()
    model = ALL_MODELS[name]()
    model.fit(X[:1500], y[:1500])
    pred = model.predict(X[1500:])
    acc = (pred == y[1500:]).mean()
    base = max(y[1500:].mean(), 1 - y[1500:].mean())
    assert acc > base + 0.02, f"{name}: acc={acc:.3f} vs majority {base:.3f}"


@pytest.mark.parametrize("name,shape", [
    ("R.F.", (24, 5)), ("Tree", (1, 6)), ("CTree", (1, 6)), ("Boost", None),
    ("Glm", None),
])
def test_forest_shape_is_the_fitted_block_shape(name, shape):
    """forest_shape gives the (trees, depth) block a single-forest model
    brings to a grouped flush, and None for every other algo."""
    assert forest_shape(name) == shape
    if shape is not None:
        X, y = _synthetic(n=300)
        assert ALL_MODELS[name]().fit(X, y).params.feat_idx.shape == shape


def test_random_forest_best_or_near_best():
    """The paper's finding: RF is the strongest of the six (we allow a small
    tolerance — Boost can tie on easy synthetic data)."""
    X, y = _synthetic(n=3000, seed=1)
    accs = {}
    for name in ALL_MODELS:
        m = ALL_MODELS[name]().fit(X[:2400], y[:2400])
        accs[name] = (m.predict(X[2400:]) == y[2400:]).mean()
    assert accs["R.F."] >= max(accs.values()) - 0.03, accs


def test_forest_leaves_are_probabilities():
    X, y = _synthetic()
    params = fit_oblivious_forest(X, y, n_trees=8, depth=4)
    assert params.leaves.min() >= 0.0 and params.leaves.max() <= 1.0
    p = forest_predict(params, X)
    assert p.min() >= 0.0 and p.max() <= 1.0


def test_forest_fold_masks_train_distinct_models():
    X, y = _synthetic(n=600)
    masks = np.zeros((2, 600), np.float32)
    masks[0, :300] = 1
    masks[1, 300:] = 1
    params = fit_oblivious_forest(X, y, n_trees=4, depth=3, fold_masks=masks)
    assert params.feat_idx.shape == (8, 3)  # 2 folds x 4 trees


def test_cv_metrics_math():
    y_true = np.array([1, 1, 0, 0, 1], np.float32)
    y_pred = np.array([1, 0, 0, 1, 1], np.float32)
    m = metrics(y_true, y_pred)
    assert m["accuracy"] == pytest.approx(3 / 5)
    assert m["precision"] == pytest.approx(2 / 3)
    assert m["recall"] == pytest.approx(2 / 3)
    assert m["error"] == pytest.approx(2 / 5)


def test_cross_validate_runs():
    X, y = _synthetic(n=400)
    out = cross_validate("Glm", X, y, k=4)
    assert 0.5 < out["accuracy"] <= 1.0
    assert out["time_ms"] > 0


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 500), depth=st.integers(1, 5))
def test_property_forest_monotone_leaf_index(seed, depth):
    """Kernel/trainer contract: predictions are averages of leaf values selected by
    threshold comparisons — permuting sample order must not change predictions."""
    rs = np.random.RandomState(seed)
    X = rs.randn(64, 5).astype(np.float32)
    y = (rs.rand(64) > 0.5).astype(np.float32)
    params = fit_oblivious_forest(X, y, n_trees=3, depth=depth, seed=seed)
    p1 = forest_predict(params, X)
    perm = rs.permutation(64)
    p2 = forest_predict(params, X[perm])
    np.testing.assert_allclose(p1[perm], p2, rtol=1e-5, atol=1e-6)
