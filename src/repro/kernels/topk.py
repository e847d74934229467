"""Tiled top-k over the accumulator tiles the serving kernels emit.

``topk_from_tiles`` replaces the full-collection ``lax.top_k`` with a
hierarchical merge: per-tile top-k over the (Q, n_tiles, tile_d)
accumulator tiles, then a top-k over the per-tile candidates.  Exactness: a
tile holds ``tile_d`` docs, so its global top-k members are within its
local top-``min(k, tile_d)``; tie-breaking (lower doc id first) is
preserved because candidates stay sorted by (tile, rank).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def topk_from_tiles(acc_tiles: jnp.ndarray, k: int,
                    n_docs: int | None = None):
    """Hierarchical top-k over (Q, n_tiles, tile_d) accumulator tiles.

    Returns (scores, doc_ids) of shape (Q, k) with doc ids global to the
    shard.  Matches ``lax.top_k`` over the flattened (Q, n_docs) accumulator
    exactly, including tie-breaking by lower doc id.  Pass ``n_docs`` when
    the tiles overhang the shard so ghost lanes can never be selected.
    """
    q, n_tiles, tile_d = acc_tiles.shape
    if n_docs is not None and n_tiles * tile_d > n_docs:
        fill = (jnp.finfo(acc_tiles.dtype).min
                if jnp.issubdtype(acc_tiles.dtype, jnp.floating)
                else jnp.iinfo(acc_tiles.dtype).min)
        gid = (jnp.arange(tile_d, dtype=jnp.int32)[None, :]
               + (jnp.arange(n_tiles, dtype=jnp.int32) * tile_d)[:, None])
        acc_tiles = jnp.where(gid[None] < n_docs, acc_tiles, fill)
    kt = min(k, tile_d)
    sc_t, idx_t = jax.lax.top_k(acc_tiles, kt)            # (Q, T, kt)
    gidx = idx_t + (jnp.arange(n_tiles, dtype=jnp.int32) * tile_d)[None, :,
                                                                   None]
    sc, pos = jax.lax.top_k(sc_t.reshape(q, n_tiles * kt), k)
    ids = jnp.take_along_axis(gidx.reshape(q, n_tiles * kt), pos, axis=1)
    return sc, ids.astype(jnp.int32)
