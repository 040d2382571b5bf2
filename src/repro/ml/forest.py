"""Oblivious decision trees / forests in JAX — the training side of the ATLAS
failure predictors.

Oblivious trees (one (feature, threshold) test per level, CatBoost-style) were chosen
deliberately: inference is gather-free and maps onto the MXU (see
repro/kernels/forest.py).  Training is histogram-based and fully vectorised: all
trees (and, for cross-validation, all folds) are fitted simultaneously as a batch of
per-sample weight vectors — bootstrap resampling and fold masking are both just
weights.

The split criterion is weighted variance reduction, which for {0,1} targets is
equivalent to Gini impurity up to a monotone transform; "ctree" mode normalises the
gain by pooled variance (a t-statistic-like score), approximating conditional
inference trees' test-based selection.
"""

from __future__ import annotations

import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.spans import stage


@dataclasses.dataclass
class ForestParams:
    feat_idx: np.ndarray    # (T, D) int32
    thresholds: np.ndarray  # (T, D) float32
    leaves: np.ndarray      # (T, 2^D) float32  (mean target per leaf)


def make_bins(X: np.ndarray, n_bins: int) -> np.ndarray:
    """Per-feature candidate thresholds from quantiles: (F, Q).

    Quantiles of constant / low-cardinality features repeat, and every repeat
    is the same zero-information candidate split occupying a slot in the
    (feature, quantile) candidate grid.  Each feature row keeps only its
    distinct thresholds (ascending); the tail is padded with +inf sentinels
    whose ``x > thr`` bits are identically False — a degenerate all-right
    split with exactly zero gain, so argmax never prefers one over a real
    candidate (ties resolve to the lowest flat index, which is finite)."""
    qs = np.linspace(0.05, 0.95, n_bins)
    thr = np.quantile(X, qs, axis=0).T.astype(np.float32)      # (F, Q)
    out = np.full_like(thr, np.inf)
    for f in range(thr.shape[0]):
        uniq = np.unique(thr[f])                               # sorted, distinct
        out[f, :uniq.size] = uniq
    return out


def _exact_dot(a, b):
    """f32 matmul at full precision: the TPU's default rounds operands to
    bf16, which would change the split sums (and so the chosen splits)
    relative to a fit on the CPU."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("n_leaves", "criterion"))
def _best_split(bits, w, wy, wyy, leaf, *, n_leaves: int, criterion: str):
    """One oblivious level for a batch of trees.

    bits: (N, FQ) f32 — precomputed X[:,f] > thr[f,q] indicators.
    w/wy/wyy: (T, N) — per-tree sample weights, weight*target, weight*target^2.
    leaf: (T, N) int32 current leaf of each sample.
    Returns (gain (T, FQ), best flat candidate per tree (T,)).
    """
    L = n_leaves

    def per_tree(args):
        wt, wyt, wyyt, lt = args
        oh = jax.nn.one_hot(lt, L, dtype=jnp.float32)          # (N, L)
        stacked = jnp.stack([wt, wyt, wyyt], axis=1)           # (N, 3)
        tot = _exact_dot(oh.T, stacked)                        # (L, 3)
        lw = _exact_dot((oh * wt[:, None]).T, bits)            # (L, FQ)
        ly = _exact_dot((oh * wyt[:, None]).T, bits)
        lyy = _exact_dot((oh * wyyt[:, None]).T, bits)
        rw = tot[:, 0:1] - lw
        ry = tot[:, 1:2] - ly
        ryy = tot[:, 2:3] - lyy
        eps = 1e-9

        def sse(s_w, s_y, s_yy):
            return s_yy - s_y * s_y / jnp.maximum(s_w, eps)

        parent = sse(tot[:, 0:1], tot[:, 1:2], tot[:, 2:3])
        child = sse(lw, ly, lyy) + sse(rw, ry, ryy)
        gain_l = parent - child                                # (L, FQ)
        gain = gain_l.sum(axis=0)                              # (FQ,)
        if criterion == "ctree":
            pooled = child.sum(axis=0) / jnp.maximum(tot[:, 0].sum(), eps)
            gain = gain / jnp.sqrt(pooled + eps)
        # degenerate splits (all left / all right) get zero gain naturally
        return gain

    gains = jax.lax.map(per_tree, (w, wy, wyy, leaf))          # (T, FQ)
    best = jnp.argmax(gains, axis=1)
    return gains, best


@functools.partial(jax.jit, static_argnames=("n_leaves",))
def _leaf_values(w, wy, leaf, *, n_leaves: int):
    def per_tree(args):
        wt, wyt, lt = args
        oh = jax.nn.one_hot(lt, n_leaves, dtype=jnp.float32)
        sw = _exact_dot(oh.T, wt)
        sy = _exact_dot(oh.T, wyt)
        return sy / jnp.maximum(sw, 1e-9)
    return jax.lax.map(per_tree, (w, wy, leaf))


def fit_oblivious_forest(X: np.ndarray, y: np.ndarray, *, n_trees: int = 24,
                         depth: int = 5, n_bins: int = 8, bootstrap: bool = True,
                         criterion: str = "var", seed: int = 0,
                         sample_weight: np.ndarray | None = None,
                         fold_masks: np.ndarray | None = None) -> ForestParams:
    """Fit T oblivious trees of given depth.

    fold_masks: optional (K, N) {0,1} — trains T trees *per fold* in one batch
    (weights zeroed on the fold's test samples); returns K*T trees ordered
    fold-major.  This is how the 10-fold CV trains all folds in one shot.
    """
    N, F = X.shape
    thr = make_bins(X, n_bins)                                 # (F, Q)
    Q = thr.shape[1]
    bits_np = (X[:, :, None] > thr[None]).astype(np.float32).reshape(N, F * Q)
    bits = jnp.asarray(bits_np)

    rng = np.random.RandomState(seed)
    if fold_masks is None:
        fold_masks = np.ones((1, N), np.float32)
    K = fold_masks.shape[0]
    T = n_trees * K
    if bootstrap:
        w0 = rng.poisson(1.0, size=(T, N)).astype(np.float32)
    else:
        w0 = np.ones((T, N), np.float32)
    mask = np.repeat(fold_masks, n_trees, axis=0)              # (T, N) fold-major
    w_np = w0 * mask
    if sample_weight is not None:
        w_np = w_np * sample_weight[None, :]

    w = jnp.asarray(w_np)
    yj = jnp.asarray(y, jnp.float32)
    wy = w * yj[None]
    wyy = wy * yj[None]
    leaf = jnp.zeros((T, N), jnp.int32)

    feat_idx = np.zeros((T, depth), np.int32)
    thresholds = np.zeros((T, depth), np.float32)
    thr_flat = thr.reshape(-1)
    for d in range(depth):
        _, best = _best_split(bits, w, wy, wyy, leaf,
                              n_leaves=1 << d, criterion=criterion)
        best = np.asarray(best)
        feat_idx[:, d] = best // Q
        thresholds[:, d] = thr_flat[best]
        chosen_bits = jnp.take(bits, jnp.asarray(best), axis=1).T  # (T, N)
        leaf = leaf * 2 + chosen_bits.astype(jnp.int32)

    leaves = np.asarray(_leaf_values(w, wy, leaf, n_leaves=1 << depth))
    # empty leaves fall back to the tree prior
    prior = float(np.average(y, weights=np.maximum(w_np.sum(0), 1e-9)))
    counts = np.asarray(
        jax.vmap(lambda lt, wt: jax.ops.segment_sum(wt, lt, 1 << depth))(
            leaf, w))
    leaves = np.where(counts > 0, leaves, prior).astype(np.float32)
    return ForestParams(feat_idx=feat_idx, thresholds=thresholds, leaves=leaves)


# Below this batch size the per-call dispatch overhead of the XLA/Pallas path
# dwarfs the arithmetic; the scheduler's per-decision scoring (1-13 rows per
# call) sits firmly in this regime, so it routes to the numpy mirror.
SMALL_BATCH = 64


def _mean_over_trees(vals: np.ndarray) -> np.ndarray:
    """Mean over axis 1 with a fixed, batch-shape-independent accumulation order.

    ``np.mean`` re-associates its pairwise reduction depending on the array
    shape, so the same row can round differently inside different batches
    (observed 1-2 ulp).  The online broker memoises probabilities and must
    return bit-identical values however requests are batched, so the tree sum
    is accumulated strictly in tree order — per-row arithmetic that cannot see
    the batch it rides in."""
    acc = vals[:, 0].astype(np.float32)                        # always a copy
    for t in range(1, vals.shape[1]):
        acc += vals[:, t]
    return acc / np.float32(vals.shape[1])


def _leaf_votes_np(fi, th, lv, x: np.ndarray) -> np.ndarray:
    """Per-(row, tree) leaf values for an oblivious forest: (B, T) float32.

    Bit patterns -> leaf indices go through a float32 dot with the power-of-two
    weights (exact for 0/1 bits and D <= 24), which is a single BLAS call
    instead of an int64 broadcast-multiply-reduce — this is the broker's
    saturated-flush floor, so per-row constants matter."""
    B = x.shape[0]
    T, D = fi.shape
    g = np.take(x, fi.reshape(-1), axis=1)                      # (B, T*D)
    bits = (g > th.reshape(1, T * D).astype(np.float32))
    weights = (1 << np.arange(D - 1, -1, -1)).astype(np.float32)
    leaf_idx = (bits.reshape(B * T, D).astype(np.float32) @ weights) \
        .astype(np.intp).reshape(B, T)
    flat_idx = leaf_idx + (np.arange(T) * lv.shape[1])[None, :]
    return np.take(lv.astype(np.float32).reshape(-1), flat_idx)


def forest_predict_np(params: ForestParams, X: np.ndarray,
                      tree_slice: slice | None = None) -> np.ndarray:
    """Pure-numpy mirror of ``kernels.ref.forest_infer_ref`` for tiny batches."""
    x = np.asarray(X, np.float32)
    fi, th, lv = params.feat_idx, params.thresholds, params.leaves
    if tree_slice is not None:
        fi, th, lv = fi[tree_slice], th[tree_slice], lv[tree_slice]
    return _mean_over_trees(_leaf_votes_np(fi, th, lv, x))


# ---------------------------------------------------------------------------
# Block-diagonal grouped inference: the serving-path hot loop
# ---------------------------------------------------------------------------

# Below this many total rows a fused flush stays on the numpy block-diagonal
# pass under impl="auto"; above it the packed layout ships to the XLA/Pallas
# grouped kernel (one device pass for the whole flush).
GROUPED_KERNEL_ROWS = 512


def serving_impl() -> str:
    """Flush backend of the serving brokers: the grouped Pallas kernel when
    the default JAX backend is a TPU, the numpy mirror elsewhere.  The kernel
    reproduces the mirror's bits, so the choice moves work, not results."""
    return "pallas" if jax.default_backend() == "tpu" else "numpy"


@dataclasses.dataclass
class PackedForests:
    """Many forests packed into one padded block-diagonal tensor layout.

    All models of a flush are padded to a common (T, D): padded levels test
    feature 0 against +inf (bits identically False), padded trees have all-zero
    leaves.  A model of true depth d stores leaf ``l`` at index ``l << (D-d)``
    so the padded bit/weight arithmetic lands on exactly the original leaf
    value — votes for real trees are bit-identical to the unpadded model.

    The same layout feeds both the numpy pass (``_leaf_votes_blockdiag``) and
    the grouped Pallas kernel (``kernels.forest.forest_infer_grouped``)."""
    feat_idx: np.ndarray    # (M, T, D) int32, zero-padded
    thresholds: np.ndarray  # (M, T, D) float32, +inf-padded
    leaves: np.ndarray      # (M, T, 2^D) float32, zero-padded / shifted
    n_trees: np.ndarray     # (M,) int32 true per-model tree counts


def pack_forests(params_list) -> PackedForests:
    """Pack per-model (T_m, D_m) forests into one padded (M, T, D) block."""
    M = len(params_list)
    T = max(p.feat_idx.shape[0] for p in params_list)
    D = max(p.feat_idx.shape[1] for p in params_list)
    if D > 24:
        raise ValueError(f"depth {D} > 24 breaks exact float32 leaf indexing")
    fi = np.zeros((M, T, D), np.int32)
    th = np.full((M, T, D), np.inf, np.float32)
    lv = np.zeros((M, T, 1 << D), np.float32)
    n_trees = np.empty(M, np.int32)
    for m, p in enumerate(params_list):
        t, d = p.feat_idx.shape
        fi[m, :t, :d] = p.feat_idx
        th[m, :t, :d] = p.thresholds
        lv[m, :t][:, np.arange(1 << d) << (D - d)] = p.leaves
        n_trees[m] = t
    return PackedForests(fi, th, lv, n_trees)


# Flush-to-flush the broker scores the same model set, so the padded blocks
# are cached by model identity (strong refs in the value keep the id()s from
# being recycled while an entry is alive).  Flushes can run concurrently from
# independent brokers, so mutation is locked.
_PACK_CACHE: dict[tuple, tuple[list, PackedForests]] = {}
_PACK_CACHE_MAX = 32
_PACK_LOCK = threading.Lock()


def _packed_for(params_list) -> PackedForests:
    key = tuple(id(p) for p in params_list)
    with _PACK_LOCK:
        hit = _PACK_CACHE.get(key)
        if hit is not None and all(a is b for a, b in
                                   zip(hit[0], params_list)):
            return hit[1]
    packed = pack_forests(params_list)
    with _PACK_LOCK:
        if len(_PACK_CACHE) >= _PACK_CACHE_MAX:
            _PACK_CACHE.pop(next(iter(_PACK_CACHE)), None)
        _PACK_CACHE[key] = (list(params_list), packed)
    return packed


def _leaf_votes_blockdiag(packed: PackedForests, x: np.ndarray,
                          seg_ids: np.ndarray) -> np.ndarray:
    """Per-(row, tree) leaf values where row r reads ONLY model seg_ids[r]'s
    block: (R, T) float32.  Every step is per-row (gather, compare, exact
    power-of-two dot, gather), so votes for row r are bit-identical to
    ``_leaf_votes_np`` on r's own model — no row is scored against trees it
    doesn't belong to, which is what makes the pass O(Σ B_m x T) instead of
    O(ΣB x ΣT)."""
    M, T, D = packed.feat_idx.shape
    L = packed.leaves.shape[2]
    R = x.shape[0]
    fi = packed.feat_idx.reshape(M, T * D)
    th = packed.thresholds.reshape(M, T * D)
    g = np.take_along_axis(x, fi[seg_ids], axis=1)              # (R, T*D)
    bits = g > th[seg_ids]
    weights = (1 << np.arange(D - 1, -1, -1)).astype(np.float32)
    leaf_idx = (bits.reshape(R * T, D).astype(np.float32) @ weights) \
        .astype(np.intp).reshape(R, T)
    flat = (seg_ids[:, None] * T + np.arange(T)[None, :]) * L + leaf_idx
    return packed.leaves.reshape(-1).take(flat)


def forest_predict_grouped(groups, *, impl: str = "numpy") -> tuple[list, int]:
    """One block-diagonal inference pass over many (ForestParams, X) groups.

    The serving broker flushes every queued prediction request — possibly from
    many independently trained predictors — as a single pass: rows are stacked
    segment-by-segment (one segment per distinct model), the models' tree
    blocks are packed into one padded tensor (``pack_forests``), and each row
    is gathered / compared / leaf-indexed against ONLY its own segment's
    block.  Because the tree mean accumulates in a fixed order
    (``_mean_over_trees``) over each model's true tree count and every other
    step is per-row, each row's probability is bit-identical to
    ``forest_predict_np(its_params, its_rows)`` regardless of which other
    groups share the flush — and regardless of the padded tail.

    Returns ``(outs, n_passes)``: one score array per group and the number of
    fused passes issued — one for the whole flush (heterogeneous model shapes
    included; they pad into the same block).  Groups that reference the *same*
    ForestParams object share one segment, so a saturated flush of many
    requests against one model costs one model's worth of trees.

    impl: "numpy" (default), "auto" (numpy below ``GROUPED_KERNEL_ROWS``
    total rows, the backend's grouped kernel path above — see
    ``kernels.ops``), or an explicit kernel impl ("xla"/"pallas"/
    "interpret") to force the packed pass.  The Pallas kernel (compiled or
    interpreted) is bit-identical to the numpy mirror; the "xla" reference
    may round its tree mean differently at the last ulp.
    """
    outs: list = [None] * len(groups)
    by_params: dict[int, list[int]] = {}      # id(params) -> group indices
    params_of: dict[int, ForestParams] = {}
    counts: dict[int, int] = {}
    order: list[int] = []                     # pids in first-appearance order
    total = 0
    for i, (params, X) in enumerate(groups):
        if X.shape[0] == 0:
            outs[i] = np.zeros(0, np.float32)
            continue
        pid = id(params)
        if pid not in by_params:
            by_params[pid] = []
            params_of[pid] = params
            counts[pid] = 0
            order.append(pid)
        by_params[pid].append(i)
        counts[pid] += X.shape[0]
        total += X.shape[0]
    if not total:
        return outs, 0

    # columnar row assembly: one preallocated block, segments contiguous
    first = groups[by_params[order[0]][0]][1]
    group_span: list = [None] * len(groups)
    seg_start: dict[int, int] = {}
    if len(by_params) == 1 and len(by_params[order[0]]) == 1:
        # one model, one row block (e.g. a broker column view): use it as-is
        i = by_params[order[0]][0]
        x = np.ascontiguousarray(first, np.float32)
        group_span[i] = (0, total)
        seg_start[order[0]] = 0
    else:
        x = np.empty((total, first.shape[1]), np.float32)
        pos = 0
        for pid in order:
            seg_start[pid] = pos
            for i in by_params[pid]:
                b = groups[i][1].shape[0]
                x[pos:pos + b] = groups[i][1]
                group_span[i] = (pos, pos + b)
                pos += b

    use_kernel = impl not in ("numpy", "auto") or (
        impl == "auto" and total > GROUPED_KERNEL_ROWS)
    if use_kernel:
        from repro.kernels import ops
        packed = _packed_for([params_of[p] for p in order])
        seg_sizes = np.asarray([counts[p] for p in order], np.int32)
        kernel_impl = None if impl == "auto" else impl
        scores = np.asarray(ops.forest_infer_grouped(
            x, seg_sizes, packed.feat_idx, packed.thresholds, packed.leaves,
            packed.n_trees, impl=kernel_impl), np.float32)
        for i, span in enumerate(group_span):
            if span is not None:
                outs[i] = scores[span[0]:span[1]]
        return outs, 1

    stage("forest.numpy")
    if len(order) == 1:
        # single model: the existing numpy mirror (shared tree block over the
        # stacked rows) — same arithmetic, no per-row index plumbing
        p = params_of[order[0]]
        votes = _leaf_votes_np(p.feat_idx, p.thresholds, p.leaves, x)
        means = {order[0]: _mean_over_trees(votes)}
    else:
        packed = _packed_for([params_of[p] for p in order])
        seg_ids = np.repeat(np.arange(len(order), dtype=np.intp),
                            [counts[p] for p in order])
        votes = _leaf_votes_blockdiag(packed, x, seg_ids)      # (R, T_pad)
        means = {}
        for m, pid in enumerate(order):
            s = seg_start[pid]
            t = params_of[pid].feat_idx.shape[0]
            # fixed-order mean over the model's TRUE tree count: the padded
            # tail never enters the accumulation
            means[pid] = _mean_over_trees(votes[s:s + counts[pid], :t])
    stage("forest.unpack")
    for pid in order:
        s = seg_start[pid]
        block = means[pid]
        for i in by_params[pid]:
            gs, ge = group_span[i]
            outs[i] = block[gs - s:ge - s]
    return outs, 1


def forest_predict(params: ForestParams, X: np.ndarray, *, impl: str | None = None,
                   tree_slice: slice | None = None) -> np.ndarray:
    """Mean leaf value over trees — a probability for {0,1} targets.

    impl=None auto-routes: numpy mirror for small batches, the kernel path
    otherwise.  Pass impl="numpy"/"xla"/... to force a specific path."""
    if impl == "numpy" or (impl is None and X.shape[0] <= SMALL_BATCH):
        return forest_predict_np(params, X, tree_slice)
    from repro.kernels import ops
    fi, th, lv = params.feat_idx, params.thresholds, params.leaves
    if tree_slice is not None:
        fi, th, lv = fi[tree_slice], th[tree_slice], lv[tree_slice]
    out = ops.forest_infer(jnp.asarray(X, jnp.float32), jnp.asarray(fi),
                           jnp.asarray(th), jnp.asarray(lv), impl=impl)
    return np.asarray(out)
