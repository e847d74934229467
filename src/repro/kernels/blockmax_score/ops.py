"""jit'd wrappers: flat postings + block survival -> Pallas masked scoring,
and the batched shard-mirror entry point used by the serving pipeline."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.blockmax_score.kernel import blockmax_score_batched
from repro.kernels.blocks import LANES, mirror_tiles, round_up
from repro.kernels.blockmax_score.ref import blockmax_score_ref


def _doc_keep(survive: jnp.ndarray, block_size: int, n_cols: int):
    """(Q, n_blocks) block survival -> (Q, n_cols) float32 0/1 doc mask."""
    keep = jnp.repeat(survive.astype(jnp.float32), block_size, axis=1)
    return keep[:, :n_cols]


@functools.partial(jax.jit, static_argnames=("tile_d", "block_size",
                                             "n_blocks", "interpret"))
def blockmax_score_tiles(tile_docs: jnp.ndarray, tile_terms: jnp.ndarray,
                         tile_scores: jnp.ndarray, qterms: jnp.ndarray,
                         survive: jnp.ndarray, *, tile_d: int,
                         block_size: int, n_blocks: int,
                         interpret: bool) -> jnp.ndarray:
    """Batched masked scoring over the shard's bucketed mirror.

    Args:
      tile_docs/tile_terms/tile_scores: (n_tiles, CAP) build-time bucketed
        shard mirror (see ``IndexShard``).
      qterms: (Q, L) query term ids with -1 in masked-out slots.
      survive: (Q, n_blocks) bool/int — per-query pruning-block survival.
    Returns:
      (Q, n_tiles, tile_d) float32 accumulator tiles; reduce with the tiled
      top-k merge (``repro.kernels.topk.topk_from_tiles``).
    """
    assert survive.shape[1] == n_blocks, (survive.shape, n_blocks)
    keep = _doc_keep(survive, block_size, tile_docs.shape[0] * tile_d)
    return blockmax_score_batched(tile_docs, tile_terms, tile_scores,
                                  qterms, keep, tile_d=tile_d,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_docs", "block_size", "tile_d",
                                             "cap", "interpret"))
def blockmax_score(docs: jnp.ndarray, scores: jnp.ndarray,
                   survive: jnp.ndarray, *, n_docs: int, block_size: int,
                   tile_d: int = 128, cap: int = 1024,
                   interpret: bool) -> jnp.ndarray:
    """Exact scoring restricted to surviving blocks.

    ``tile_d`` must be a multiple of ``block_size`` (a kernel tile covers
    whole pruning blocks).  The postings are bucketed per call and served
    as a one-query batch of the shard-mirror kernel (every live lane
    carries term 0, the query asks for term 0); lanes past ``cap`` in a
    tile fall back to an exact scatter.
    """
    assert tile_d % block_size == 0
    p = docs.shape[0]
    n_tiles = -(-n_docs // tile_d)
    nt = mirror_tiles(n_docs, tile_d)                       # kernel rows
    cap = round_up(cap, LANES)

    live = docs >= 0
    blk = jnp.where(live, docs // block_size, 0)
    keep = live & survive[blk]
    docs_m = jnp.where(keep, docs, -1)

    tile = jnp.where(keep, docs_m // tile_d, n_tiles)
    order = jnp.argsort(tile)
    tile_s = tile[order]
    docs_s = jnp.where(keep[order], docs_m[order] - tile_s * tile_d, -1)
    scores_s = scores[order]

    counts = jnp.zeros((n_tiles + 1,), jnp.int32).at[tile_s].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    pos = jnp.arange(p, dtype=jnp.int32) - starts[tile_s]
    fits = (pos < cap) & (tile_s < n_tiles)
    slot = jnp.where(fits, tile_s * cap + pos, nt * cap)
    docs_b = jnp.full((nt * cap + 1,), -1, jnp.int32
                      ).at[slot].set(jnp.where(fits, docs_s, -1))
    scores_b = jnp.zeros((nt * cap + 1,), jnp.float32
                         ).at[slot].set(jnp.where(fits, scores_s, 0.0))

    docs_b = docs_b[:-1].reshape(nt, cap)
    acc_t = blockmax_score_batched(
        docs_b, jnp.where(docs_b >= 0, 0, -1),
        scores_b[:-1].reshape(nt, cap), jnp.zeros((1, 1), jnp.int32),
        _doc_keep(survive[None, :], block_size, nt * tile_d),
        tile_d=tile_d, interpret=interpret)
    acc = acc_t.reshape(nt * tile_d)[:n_docs]

    over = keep[order] & ~fits & (tile_s < n_tiles)
    d_of = jnp.where(over, docs_m[order], 0)
    v_of = jnp.where(over, scores_s, 0.0)
    return acc.at[d_of].add(v_of)


__all__ = ["blockmax_score", "blockmax_score_ref", "blockmax_score_tiles"]
