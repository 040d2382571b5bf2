"""Oblivious-forest inference as a Pallas TPU kernel — the ATLAS scheduling hot path.

The paper evaluates its Random Forest per scheduling decision (~26-36 ms in R).  Our
runtime predicts outcomes for *every pending step-shard each scheduler tick*, so
inference is batched and kernelised.

TPU adaptation (this is where the Hadoop-era algorithm is rethought for the MXU):
tree traversal is gather-heavy on CPUs/GPUs; TPUs hate gathers.  For *oblivious*
trees (one (feature, threshold) test per level, as in CatBoost) the whole forest
evaluates gather-free.  A batch tile is laid out with its rows along the lanes:

  1. feature gather  ->  one-hot matmul on the MXU:  S (T*D, F) . X^T -> (T*D, Bb),
     where S[t*D+d, f] = 1 iff tree t level d tests feature f.  It runs at
     ``Precision.HIGHEST``: the f32 feature splits into three bf16 parts that
     the one-hot weight reassembles exactly, so the comparison sees the
     feature itself and not its bf16 rounding.
  2. bits            ->  compare with the (T*D, 1) threshold column (VPU).
  3. leaf lookup     ->  per tree, the D bit rows fold into an int32 leaf index
     (level 0 = MSB); ``iota == index`` gives a (2^D, Bb) one-hot, and the
     tree's leaf row contracts with it at ``HIGHEST`` — one nonzero term
     per row, so the leaf value comes out exactly.
  4. tree sum        ->  the leaf values are summed in tree order over the
     model's true tree count (VPU adds are IEEE-exact).

The mean's division by the tree count runs on the host, in float32 numpy:
the chip's f32 divide is not correctly rounded (on a v5e it missed numpy's
quotient in about 28% of 8.8M tested values), and the mean must be the
numpy mirror's (``ml.forest._mean_over_trees``) bit for bit.

Every block keeps its trailing two dimensions whole, and no value is reshaped
inside the body: Mosaic lays each one out as given.  The single-model
``forest_infer`` is the grouped kernel over one segment.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs.spans import stage


def _tile_sums(x, sel, thr, leaves_ref, n_trees, *, T: int, D: int):
    """Tree-order leaf sums for one batch tile, rows along lanes: (1, Bb).

    x (Bb, F); sel (T*D, F) one-hot; thr (T*D, 1); ``leaves_ref`` is the
    model's (1, T, 2^D) leaf block; ``n_trees`` is the true tree count, an
    int32 scalar read from SMEM.  Trees at index >= n_trees are padding and
    never enter the sum."""
    L = 1 << D
    g = jax.lax.dot_general(sel, x, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # (T*D, Bb)
    bits = (g > thr).astype(jnp.int32)
    leaf_rows = jax.lax.broadcasted_iota(jnp.int32, (L, bits.shape[1]), 0)
    acc = None
    for t in range(T):
        idx = bits[t * D:t * D + 1]
        for d in range(1, D):
            idx = idx * 2 + bits[t * D + d:t * D + d + 1]        # (1, Bb)
        hit = (leaf_rows == idx).astype(jnp.float32)             # (L, Bb)
        vote = jax.lax.dot_general(
            leaves_ref[0, t:t + 1, :], hit, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)                  # (1, Bb)
        acc = vote if acc is None else jnp.where(t < n_trees, acc + vote, acc)
    return acc


# ---------------------------------------------------------------------------
# Grouped (block-diagonal) variant: many models, one padded block layout
# ---------------------------------------------------------------------------
#
# The serving broker flushes requests from MANY independently trained forests
# at once.  The grouped kernel takes the same packed block layout the numpy
# path uses (ml.forest.pack_forests): per-model selector / threshold / leaf
# blocks stacked into one padded (M, ...) tensor, rows stacked segment-by-
# segment.  The grid walks (model-segment, batch-tile) pairs flattened into
# tiles; a scalar-prefetched tile->segment map lets each tile's BlockSpec DMA
# exactly its own model's blocks into VMEM, and a second prefetched array
# gives the tile its model's true tree count — no row is ever scored against
# trees it doesn't belong to, and no gather appears anywhere.

# Model and tile counts are padded up to these buckets, so the serving path
# compiles a handful of shapes rather than one per flush composition.
MODEL_BUCKET = 8

# Grouped passes launched through the compiled (not interpreted) kernel.  A
# broker compares it before and after a flush to see the flush reach the
# device.
n_device_passes = 0


def _tile_bucket(n_tiles: int) -> int:
    return 1 << max(n_tiles - 1, 0).bit_length()


def _grouped_kernel(seg_ref, nt_ref, x_ref, sel_ref, thr_ref, leaves_ref,
                    o_ref, *, T: int, D: int):
    n_trees = nt_ref[seg_ref[pl.program_id(0)]]
    o_ref[0] = _tile_sums(x_ref[...], sel_ref[0], thr_ref[0], leaves_ref,
                          n_trees, T=T, D=D)


@functools.partial(jax.jit, static_argnames=("D", "block_b", "interpret"))
def grouped_call(seg_of_tile, n_trees, xp, sel, thr, leaves, *, D: int,
                 block_b: int, interpret: bool):
    """The kernel launch over prepared operands (``grouped_layout`` +
    ``grouped_blocks``): xp (n_tiles * block_b, F) padded rows; returns the
    (n_tiles * block_b,) padded tree sums."""
    n_tiles = xp.shape[0] // block_b
    F = xp.shape[1]
    _, T, L = leaves.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((block_b, F), lambda i, seg, nt: (i, 0)),
            pl.BlockSpec((1, T * D, F), lambda i, seg, nt: (seg[i], 0, 0)),
            pl.BlockSpec((1, T * D, 1), lambda i, seg, nt: (seg[i], 0, 0)),
            pl.BlockSpec((1, T, L), lambda i, seg, nt: (seg[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_b), lambda i, seg, nt: (i, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, T=T, D=D),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, 1, block_b), jnp.float32),
        interpret=interpret,
    )(seg_of_tile, n_trees, xp, sel, thr, leaves)
    return out.reshape(-1)


def grouped_layout(seg_sizes, block_b: int):
    """Host-side tile layout of a grouped flush.

    Every segment is padded up to a ``block_b`` multiple so a tile never
    straddles two models, and the tile count is padded to its bucket (the
    extra tiles score zero rows of segment 0 and are dropped).  Returns
    ``(seg_of_tile, spans)``: the (n_tiles,) int32 tile->segment map and one
    ``(padded_start, start, rows)`` triple per segment, in segment order."""
    seg_sizes = np.asarray(seg_sizes, np.int64)
    tiles_per_seg = np.maximum(1, -(-seg_sizes // block_b))
    n_tiles = int(tiles_per_seg.sum())
    seg_of_tile = np.zeros(_tile_bucket(n_tiles), np.int32)
    seg_of_tile[:n_tiles] = np.repeat(
        np.arange(len(seg_sizes), dtype=np.int32), tiles_per_seg)
    padded = np.concatenate([[0], np.cumsum(tiles_per_seg)[:-1]]) * block_b
    start = np.concatenate([[0], np.cumsum(seg_sizes)[:-1]])
    return seg_of_tile, list(zip(padded.tolist(), start.tolist(),
                                 seg_sizes.tolist()))


def grouped_blocks(feat_idx, thresholds, leaves, n_trees, F: int):
    """Kernel operands for a packed (M, T, D) model block, with M padded to a
    ``MODEL_BUCKET`` multiple: selector (M, T*D, F), threshold column
    (M, T*D, 1), leaves (M, T, 2^D) and tree counts (M,) int32 (padding
    models count one tree, so no divisor is zero)."""
    M, T, D = np.shape(thresholds)
    Mp = -(-M // MODEL_BUCKET) * MODEL_BUCKET
    sel = np.zeros((Mp, T * D, F), np.float32)
    thr = np.zeros((Mp, T * D, 1), np.float32)
    lv = np.zeros((Mp, T, 1 << D), np.float32)
    nt = np.ones(Mp, np.int32)
    sel[:M] = np.asarray(feat_idx).reshape(M, T * D, 1) == np.arange(F)
    thr[:M, :, 0] = np.asarray(thresholds, np.float32).reshape(M, T * D)
    lv[:M] = leaves
    nt[:M] = np.asarray(n_trees, np.int32)
    return sel, thr, lv, nt


def forest_infer_grouped(x, seg_sizes, feat_idx, thresholds, leaves, n_trees,
                         *, block_b: int = 128, interpret: bool = False):
    """Grouped block-diagonal forest inference.

    x: (R, F) rows stacked segment-by-segment (segment m = seg_sizes[m] rows);
    feat_idx/thresholds: (M, T, D) padded model blocks; leaves: (M, T, 2^D);
    n_trees: (M,) true tree counts.  Returns (R,) mean-leaf scores where each
    row is scored only by its own model's trees."""
    global n_device_passes
    stage("forest.operands")
    x = np.asarray(x, np.float32)
    R, F = x.shape
    D = np.shape(thresholds)[2]
    seg_of_tile, spans = grouped_layout(seg_sizes, block_b)
    xp = np.zeros((seg_of_tile.size * block_b, F), np.float32)
    for dst, src, rows in spans:
        xp[dst:dst + rows] = x[src:src + rows]
    sel, thr, lv, nt = grouped_blocks(feat_idx, thresholds, leaves, n_trees, F)
    stage("forest.launch")
    out = grouped_call(seg_of_tile, nt, xp, sel, thr, lv, D=D,
                       block_b=block_b, interpret=interpret)
    stage("forest.fetch")
    out = np.asarray(out)
    stage("forest.unpack")
    n_device_passes += not interpret
    scores = np.empty(R, np.float32)
    for (dst, src, rows), n in zip(spans, nt):
        scores[src:src + rows] = out[dst:dst + rows] / np.float32(n)
    return scores


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def forest_tree_sums(x, feat_idx, thresholds, leaves, *, block_b=256,
                     interpret=False):
    """The device half of ``forest_infer``: (B,) tree-order leaf sums."""
    B, F = x.shape
    T, D = feat_idx.shape
    block_b = min(block_b, B)
    xp = jnp.pad(x.astype(jnp.float32), ((0, (-B) % block_b), (0, 0)))
    sel = jax.nn.one_hot(feat_idx.reshape(1, T * D), F, dtype=jnp.float32)
    thr = thresholds.astype(jnp.float32).reshape(1, T * D, 1)
    lv = leaves.astype(jnp.float32).reshape(1, T, 1 << D)
    seg_of_tile = jnp.zeros(xp.shape[0] // block_b, jnp.int32)
    return grouped_call(seg_of_tile, jnp.full(1, T, jnp.int32), xp, sel, thr,
                        lv, D=D, block_b=block_b, interpret=interpret)[:B]


def forest_infer(x, feat_idx, thresholds, leaves, *, block_b=256,
                 interpret=False):
    """x: (B, F) fp32; feat_idx: (T, D) int32; thresholds: (T, D); leaves: (T, 2^D).
    Returns (B,) mean-leaf margin scores (numpy): the grouped kernel over one
    model, its tree sums divided by T on the host."""
    sums = forest_tree_sums(x, feat_idx, thresholds, leaves, block_b=block_b,
                            interpret=interpret)
    return np.asarray(sums) / np.float32(np.shape(feat_idx)[0])


def warmup_grouped(n_models: int, n_trees: int, depth: int, n_features: int,
                   max_rows: int, *, block_b: int = 128) -> int:
    """Compile (and run once) the grouped kernel for every tile-count bucket
    a flush of up to ``max_rows`` rows over ``n_models`` models of shape
    (n_trees, depth) can take, so no flush of a serving run waits on the
    compiler.  Returns the number of shapes warmed."""
    sel, thr, lv, nt = grouped_blocks(
        np.zeros((n_models, n_trees, depth), np.int32),
        np.zeros((n_models, n_trees, depth), np.float32),
        np.zeros((n_models, n_trees, 1 << depth), np.float32),
        np.ones(n_models, np.int32), n_features)
    top = _tile_bucket(-(-max_rows // block_b) + n_models)
    n_tiles, n = 1, 0
    while n_tiles <= top:
        xp = np.zeros((n_tiles * block_b, n_features), np.float32)
        grouped_call(np.zeros(n_tiles, np.int32), nt, xp, sel, thr, lv,
                     D=depth, block_b=block_b,
                     interpret=False).block_until_ready()
        n_tiles *= 2
        n += 1
    return n
