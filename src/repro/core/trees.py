"""Array-based decision-tree ensembles in pure JAX.

LightGBM-style histogram trees, built level-wise with fully vectorized
``segment_sum`` histograms so training jits end-to-end.  Trees are complete
binary trees of fixed depth stored as dense arrays, so inference is
branch- and gather-free array code over every (tree, node, row) — cheap
enough to run *inside* the serving step (the paper's "Stage-0" predictions
must add <1 ms per query) and for Stage-2 to score every candidate.

Feature values are pre-binned (quantile binning) to uint8; split thresholds
are bin indices.  The binner (``fit_bins``/``apply_bins``) is part of the
model so raw features can be used at serving time.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


class TreeParams(NamedTuple):
    depth: int = 6              # number of split levels; 2**depth leaves
    n_bins: int = 64
    min_child_weight: float = 10.0
    l2: float = 1.0             # ridge term on leaf scores


class Forest(NamedTuple):
    """A stacked ensemble of complete binary trees.

    feat:   (T, depth, 2**(depth-1)) int32 — split feature per node
    thresh: (T, depth, 2**(depth-1)) int32 — split bin; go right if bin > thresh
    leaf:   (T, 2**depth) float32 — leaf scores

    Level d's nodes are ``feat[:, d, :2**d]`` (the rest is padding); node j
    at level d has children 2j and 2j + 1 at level d + 1, and the leaf index
    is the node index below the last level.  Inference reads these tables
    without a data-dependent gather: the split bins of all nodes and rows
    come from a one-hot contraction over the features (0/1 and bins < 256
    are exact in bfloat16 and each sum has one nonzero term, so it is exact
    on any backend), the descent selects by ``node == iota``, and the leaf
    value by halving the leaf table on each bit of the leaf index.
    """
    feat: jnp.ndarray
    thresh: jnp.ndarray
    leaf: jnp.ndarray


# ---------------------------------------------------------------------------
# Binning
# ---------------------------------------------------------------------------

def fit_bins(x: np.ndarray, n_bins: int) -> np.ndarray:
    """Quantile bin edges, shape (F, n_bins - 1). Host-side (numpy)."""
    qs = np.linspace(0.0, 100.0, n_bins + 1)[1:-1]
    edges = np.percentile(np.asarray(x), qs, axis=0).T.astype(np.float32)
    # strictly increasing edges keep searchsorted well-behaved on constant cols
    edges = np.maximum.accumulate(edges + 1e-9 * np.arange(edges.shape[1]), axis=1)
    return edges


@jax.jit
def apply_bins(x: jnp.ndarray, edges: jnp.ndarray) -> jnp.ndarray:
    """(n, F) raw floats -> (n, F) uint8 bin ids via vectorized searchsorted."""
    bins = jnp.sum(x[:, :, None] > edges[None, :, :], axis=-1)
    return bins.astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Level-wise histogram tree builder
# ---------------------------------------------------------------------------

def _level_histograms(xb, node, grad, weight, n_nodes, n_bins):
    """Weighted gradient/weight histograms per (node, feature, bin)."""
    n, f = xb.shape
    keys = (node[:, None].astype(jnp.int32) * f
            + jnp.arange(f, dtype=jnp.int32)[None, :]) * n_bins + xb.astype(jnp.int32)
    num_seg = n_nodes * f * n_bins
    gw = (grad * weight)[:, None] * jnp.ones((1, f), jnp.float32)
    ww = weight[:, None] * jnp.ones((1, f), jnp.float32)
    hist_g = jax.ops.segment_sum(gw.reshape(-1), keys.reshape(-1), num_segments=num_seg)
    hist_w = jax.ops.segment_sum(ww.reshape(-1), keys.reshape(-1), num_segments=num_seg)
    return (hist_g.reshape(n_nodes, f, n_bins), hist_w.reshape(n_nodes, f, n_bins))


def build_tree(xb: jnp.ndarray, target: jnp.ndarray, weight: jnp.ndarray,
               feat_mask: jnp.ndarray, params: TreeParams):
    """Fit one regression tree to `target` with variance-reduction splits.

    Args:
      xb: (n, F) uint8 binned features.
      target: (n,) regression target (pseudo-gradient for boosting).
      weight: (n,) sample weights (0 excludes a row; Poisson for bagging).
      feat_mask: (F,) bool — features eligible for splitting (attribute bagging).
    Returns:
      (feat, thresh) arrays of shape (depth, 2**(depth-1)) and the final
      (n,) leaf assignment in [0, 2**depth).
    """
    n, f = xb.shape
    d_max = params.depth
    width = 2 ** (d_max - 1)
    node = jnp.zeros((n,), jnp.int32)
    feats, threshs = [], []
    for d in range(d_max):
        n_nodes = 2 ** d
        hg, hw = _level_histograms(xb, node, target, weight, n_nodes, params.n_bins)
        cg = jnp.cumsum(hg, axis=-1)
        cw = jnp.cumsum(hw, axis=-1)
        tg = cg[..., -1:]
        tw = cw[..., -1:]
        lam = params.l2
        gain = (cg ** 2 / (cw + lam) + (tg - cg) ** 2 / (tw - cw + lam)
                - tg ** 2 / (tw + lam))
        ok = ((cw >= params.min_child_weight)
              & (tw - cw >= params.min_child_weight)
              & feat_mask[None, :, None])
        gain = jnp.where(ok, gain, NEG_INF)
        flat = gain.reshape(n_nodes, -1)
        best = jnp.argmax(flat, axis=-1)
        best_gain = jnp.take_along_axis(flat, best[:, None], axis=-1)[:, 0]
        bf = (best // params.n_bins).astype(jnp.int32)
        bb = (best % params.n_bins).astype(jnp.int32)
        # unsplittable node -> pass-through split (everything goes left)
        dead = best_gain <= NEG_INF / 2
        bf = jnp.where(dead, 0, bf)
        bb = jnp.where(dead, params.n_bins - 1, bb).astype(jnp.int32)
        fx = jnp.take_along_axis(xb.astype(jnp.int32), bf[node][:, None], axis=1)[:, 0]
        go_right = (fx > bb[node]).astype(jnp.int32)
        node = node * 2 + go_right
        pad = width - n_nodes
        feats.append(jnp.pad(bf, (0, pad)))
        threshs.append(jnp.pad(bb, (0, pad)))
    return jnp.stack(feats), jnp.stack(threshs), node


def leaf_means(leaf_id, values, weight, n_leaves, l2=1.0):
    sw = jax.ops.segment_sum(weight, leaf_id, num_segments=n_leaves)
    sv = jax.ops.segment_sum(values * weight, leaf_id, num_segments=n_leaves)
    return sv / (sw + l2)


def leaf_quantiles(leaf_id, values, weight, n_leaves, tau):
    """Exact per-leaf tau-quantile of ``values`` (weight treated as 0/1 mask).

    Rows with weight <= 0 are parked in a dummy leaf.  Implemented with one
    lexsort + prefix bookkeeping, no per-leaf loop.
    """
    n = values.shape[0]
    lid = jnp.where(weight > 0, leaf_id, n_leaves).astype(jnp.int32)
    order = jnp.lexsort((values, lid))
    s_leaf = lid[order]
    s_val = values[order]
    counts = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), lid,
                                 num_segments=n_leaves + 1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.float32),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(n, dtype=jnp.float32) - starts[s_leaf]
    target_rank = jnp.floor(tau * jnp.maximum(counts - 1.0, 0.0))
    hit = pos == target_rank[s_leaf]
    out = jnp.zeros((n_leaves + 1,), jnp.float32).at[s_leaf].add(
        jnp.where(hit, s_val, 0.0))
    return out[:n_leaves]


# ---------------------------------------------------------------------------
# Inference
# ---------------------------------------------------------------------------

def _heap_order(a: jnp.ndarray, depth: int) -> jnp.ndarray:
    """(..., depth, 2**(depth-1)) per-level node table -> (..., 2**depth - 1),
    the level-d nodes at [2**d - 1, 2**(d+1) - 1) (the padding dropped)."""
    return jnp.concatenate([a[..., d, :2 ** d] for d in range(depth)],
                           axis=-1)


@functools.partial(jax.jit, static_argnames=("depth", "reduce"))
def forest_predict_binned(forest: Forest, xb: jnp.ndarray, depth: int,
                          reduce: str = "sum") -> jnp.ndarray:
    """Predict from (n, F) pre-binned features (bins < 256).
    reduce: 'sum' (boosting) | 'mean' (bagging).

    Gather-free (see ``Forest``): one exact one-hot contraction gives every
    internal node's split bin for every row, laid out (T, node, n); level d
    reads its bit with ``node == iota`` over its 2**d nodes, and the leaf
    value comes from ``depth`` halving selects over the leaf table.  Then
    one float32 sum (or mean) over the trees.
    """
    feat = _heap_order(forest.feat, depth)                  # (T, 2^D - 1)
    thresh = _heap_order(forest.thresh, depth)
    onehot = feat[..., None] == jnp.arange(xb.shape[-1])    # (T, 2^D - 1, F)
    bins = jnp.einsum("tnf,rf->tnr", onehot.astype(jnp.bfloat16),
                      xb.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    right = bins.astype(jnp.int32) > thresh[..., None]      # (T, 2^D - 1, n)
    node = jnp.zeros((feat.shape[0], xb.shape[0]), jnp.int32)
    for d in range(depth):
        level = right[:, 2 ** d - 1:2 ** (d + 1) - 1]       # (T, 2^d, n)
        at = node[:, None] == jnp.arange(2 ** d)[:, None]
        node = node * 2 + jnp.any(at & level, axis=1).astype(jnp.int32)
    # leaf value: halve the leaf table on each bit of node, lowest first
    leaves = forest.leaf[..., None]                         # (T, 2^D, 1)
    for d in range(depth):
        bit = ((node >> d) & 1)[:, None] == 1
        leaves = jnp.where(bit, jax.lax.slice_in_dim(leaves, 1, None, 2, 1),
                           jax.lax.slice_in_dim(leaves, 0, None, 2, 1))
    leaves = leaves[:, 0].T                                 # (n, T)
    return jnp.sum(leaves, -1) if reduce == "sum" else jnp.mean(leaves, -1)


@functools.partial(jax.jit, static_argnames=("depth", "reduce"))
def forest_predict_stacked(forests: Forest, xb: jnp.ndarray, depth: int,
                           reduce: str = "sum") -> jnp.ndarray:
    """Predict M stacked ensembles in one fused on-device call.

    ``forests`` is a Forest whose arrays carry a leading (M,) model axis
    (same tree count and depth per model — stack with ``jnp.stack``);
    ``xb`` is (M, n, F) pre-binned features, one binning per model.  The
    per-model math is ``forest_predict_binned`` vmapped over the model
    axis, so the Stage-0 k/ρ/t predictors run as one array program instead
    of three dispatches.  Returns (M, n).
    """
    return jax.vmap(
        lambda f, b: forest_predict_binned(f, b, depth, reduce))(forests, xb)
