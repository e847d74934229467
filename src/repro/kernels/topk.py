"""Tiled top-k over the accumulator tiles the serving kernels emit.

``topk_from_tiles`` replaces the full-collection ``lax.top_k`` with a
hierarchical merge: per-tile top-k over the (Q, n_tiles, tile_d)
accumulator tiles, then a top-k over the per-tile candidates.  Exactness: a
tile holds ``tile_d`` docs, so its global top-k members are within its
local top-``min(k, tile_d)``.

Tie rule: equal scores go to the lower doc id.  ``lax.top_k`` does not
promise an order among equal keys (the TPU's reverses it on some shapes),
so integer accumulators are ranked on one packed int32 key,
``score · 2^b + (2^b − 1 − doc)``, which orders (score desc, doc asc) with
no two keys equal; the scores and doc ids are unpacked from the selected
keys.  Float accumulators keep ``lax.top_k``'s own order of equal keys.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def packed_key_bits(n_docs: int, max_score: int) -> int:
    """Bits ``b`` of the doc part of the packed (score, doc) key for doc ids
    below ``n_docs`` and scores in [0, ``max_score``]; raises when the key
    would not fit an int32."""
    b = max(int(n_docs) - 1, 1).bit_length()
    if (int(max_score) + 1) << b > 1 << 31:
        raise ValueError(
            f"packed top-k key overflows int32: scores up to {max_score} "
            f"with {n_docs} doc ids need {b} + "
            f"{int(max_score).bit_length()} bits")
    return b


def topk_from_tiles(acc_tiles: jnp.ndarray, k: int,
                    n_docs: int | None = None,
                    max_score: int | None = None):
    """Hierarchical top-k over (Q, n_tiles, tile_d) accumulator tiles.

    Returns (scores, doc_ids) of shape (Q, k) with doc ids global to the
    shard.  Pass ``n_docs`` when the tiles overhang the shard so ghost
    lanes can never be selected.  Integer tiles need ``max_score``, a
    static bound on every accumulator entry (entries are non-negative):
    they are ranked on the packed key, so equal scores go to the lower doc
    id whatever order ``lax.top_k`` gives equal keys."""
    q, n_tiles, tile_d = acc_tiles.shape
    n = n_tiles * tile_d if n_docs is None else min(n_docs, n_tiles * tile_d)
    ghosts = n < n_tiles * tile_d
    gid = (jnp.arange(tile_d, dtype=jnp.int32)[None, :]
           + (jnp.arange(n_tiles, dtype=jnp.int32) * tile_d)[:, None])
    kt = min(k, tile_d)
    if jnp.issubdtype(acc_tiles.dtype, jnp.integer):
        if max_score is None:
            raise ValueError("integer tiles need max_score")
        b = packed_key_bits(n, max_score)
        low = (1 << b) - 1
        key = acc_tiles.astype(jnp.int32) * (1 << b) + (low - gid)[None]
        if ghosts:
            # below every real key (real keys are >= 0)
            key = jnp.where(gid[None] < n, key, -1)
        key_t, _ = jax.lax.top_k(key, kt)                 # (Q, T, kt)
        key, _ = jax.lax.top_k(key_t.reshape(q, n_tiles * kt), k)
        return key >> b, (low - (key & low)).astype(jnp.int32)
    if ghosts:
        acc_tiles = jnp.where(gid[None] < n, acc_tiles,
                              jnp.finfo(acc_tiles.dtype).min)
    sc_t, idx_t = jax.lax.top_k(acc_tiles, kt)            # (Q, T, kt)
    gidx = idx_t + (jnp.arange(n_tiles, dtype=jnp.int32) * tile_d)[None, :,
                                                                   None]
    sc, pos = jax.lax.top_k(sc_t.reshape(q, n_tiles * kt), k)
    ids = jnp.take_along_axis(gidx.reshape(q, n_tiles * kt), pos, axis=1)
    return sc, ids.astype(jnp.int32)
