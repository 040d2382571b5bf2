"""The forest the serve cells serve, and the plain reference that checks it.

The trees are the benchmark's own, made from ``--seed`` and the cell's
trace: oblivious trees (one feature/threshold test per level) whose levels
test features that vary in the trace, at thresholds drawn between the
feature's 5% and 95% quantiles, and whose leaves hold the bootstrap mean of
the trace's outcome labels that reach them (the label prior where none do).
So the served model has the shape and value ranges of a fitted R.F. while
nothing the program fitted enters the reference.

The reference is the forest's definition in plain numpy, independent of
the program: walk each tree (bit ``d`` = feature > threshold, level 0 the
most significant), read the leaf, add the votes in tree order in float32,
divide by the tree count in float32 and clip to [0, 1].  The float32 tree
order is part of the stated semantics (a mean that does not depend on how
rows are batched), so the comparison is exact.

The control is the same reference computed one precision down, the step a
later change would be tempted by: features and leaf values rounded to
bfloat16, as a matrix unit at default precision reads them."""

from __future__ import annotations

import ml_dtypes
import numpy as np

# what an answer of the wrong length reads as a gap
WRONG_SHAPE = 1e9


def make_forest(rng: np.random.Generator, X: np.ndarray, y: np.ndarray, *,
                n_trees: int, depth: int) -> dict:
    """``{"feat_idx" (T, D) int32, "thresholds" (T, D) float32,
    "leaves" (T, 2^D) float32}`` from the seed's generator and the trace
    rows ``X`` with outcome labels ``y``."""
    X = np.asarray(X, np.float32)
    y = np.asarray(y, np.float32)
    varying = np.flatnonzero(X.min(axis=0) < X.max(axis=0))
    if varying.size == 0:
        raise ValueError("no feature varies in the trace")
    T, D, L = n_trees, depth, 1 << depth
    feat = rng.choice(varying, size=(T, D)).astype(np.int32)
    q = rng.uniform(0.05, 0.95, size=(T, D))
    thr = np.empty((T, D), np.float32)
    for t in range(T):
        for d in range(D):
            col = X[:, feat[t, d]]
            thr[t, d] = np.quantile(col, q[t, d])
            if thr[t, d] >= col.max():           # keep every split two-sided
                thr[t, d] = np.float32(np.median(np.unique(col)[:-1]))
    idx = leaf_index(feat, thr, X)                         # (N, T)
    w = rng.poisson(1.0, size=(T, X.shape[0])).astype(np.float64)
    prior = float(y.mean()) if y.size else 0.5
    leaves = np.full((T, L), prior, np.float64)
    for t in range(T):
        cnt = np.bincount(idx[:, t], weights=w[t], minlength=L)
        hit = np.bincount(idx[:, t], weights=w[t] * y, minlength=L)
        leaves[t] = np.where(cnt > 0, hit / np.maximum(cnt, 1e-12), prior)
    return {"feat_idx": feat, "thresholds": thr,
            "leaves": leaves.astype(np.float32)}


def leaf_index(feat: np.ndarray, thr: np.ndarray, X: np.ndarray) -> np.ndarray:
    """(N, T) leaf reached by each row in each tree."""
    T, D = feat.shape
    idx = np.zeros((X.shape[0], T), np.int64)
    for d in range(D):
        idx = 2 * idx + (X[:, feat[:, d]] > thr[None, :, d])
    return idx


def _probs(forest: dict, X: np.ndarray, leaves: np.ndarray) -> np.ndarray:
    idx = leaf_index(forest["feat_idx"], forest["thresholds"], X)
    T = idx.shape[1]
    acc = np.zeros(X.shape[0], np.float32)
    for t in range(T):
        acc += leaves[t, idx[:, t]]
    return np.clip(acc / np.float32(T), 0.0, 1.0).astype(np.float32)


def reference_probs(forest: dict, X: np.ndarray) -> np.ndarray:
    """The reference success probability of every row, float32."""
    return _probs(forest, np.asarray(X, np.float32), forest["leaves"])


def control_probs(forest: dict, X: np.ndarray) -> np.ndarray:
    """The reference with its inputs one precision down (bfloat16)."""
    bf = ml_dtypes.bfloat16
    X16 = np.asarray(X, np.float32).astype(bf).astype(np.float32)
    return _probs(forest, X16, forest["leaves"].astype(bf).astype(np.float32))


def max_gap(answers: list, rows: list, forest: dict, probs=reference_probs
            ) -> float:
    """Widest absolute gap between served answers and ``probs`` over the
    same rows, checked in blocks of requests so it fits in memory.  An
    answer of the wrong length reads ``WRONG_SHAPE``, past any gap two
    probabilities can have."""
    gap = 0.0
    block: list = []
    n = 0

    def flush():
        nonlocal gap
        if not block:
            return
        got = np.concatenate([a for a, _ in block])
        want = probs(forest, np.concatenate([x for _, x in block]))
        gap = max(gap, float(np.max(np.abs(got.astype(np.float64)
                                           - want.astype(np.float64)))))
        block.clear()

    for a, x in zip(answers, rows):
        a = np.asarray(a, np.float32).reshape(-1)
        if a.shape[0] != x.shape[0]:
            return WRONG_SHAPE
        block.append((a, x))
        n += x.shape[0]
        if n >= 65536:
            flush()
            n = 0
    flush()
    return gap
