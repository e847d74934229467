"""Tiled top-k over the accumulator tiles the serving kernels emit.

``topk_from_tiles`` replaces the full-collection ``lax.top_k`` with a
hierarchical merge: per-tile top-k over the (Q, n_tiles, tile_d)
accumulator tiles, then a top-k over the per-tile candidates.  Exactness: a
tile holds ``tile_d`` docs, so its global top-k members are within its
local top-``min(k, tile_d)``.

Deeper than a tile (``k > tile_d``) the per-tile stage would keep every doc
and prune nothing, so the deep select takes its place (``deep_topk_select``,
one jitted function, so its ops carry the name in the device trace): the row
is padded to whole ``SPAN``-entry steps and reduced to the maxima of groups
of ``GROUP`` entries (step ``c`` holds groups ``(c, j)``, members ``c·SPAN +
s·SPAN/GROUP + j`` for ``s < GROUP``: one elementwise max of eight (8, 128)
slabs), ``lax.top_k`` picks the ``k`` groups of largest maxima, and runs
again on their ``GROUP · k`` members.  Exactness: a member of the top k
whose group was not picked would lie at or below the ``k`` picked groups'
maxima, ``k`` entries at least as large, so the picked members hold the top
k's scores (and, for the unique packed keys below, the top k itself); there
is nothing to overflow and no fallback.  A row shorter than ``GROUP · k``
keeps the tile path.

Tie rule: equal scores go to the lower doc id.  ``lax.top_k`` does not
promise an order among equal keys (the TPU's reverses it on some shapes),
so integer accumulators are ranked on one packed int32 key,
``score · 2^b + (2^b − 1 − doc)``, which orders (score desc, doc asc) with
no two keys equal; the scores and doc ids are unpacked from the selected
keys.  Float accumulators keep ``lax.top_k``'s own order of equal keys:
their score vector is exact on either path, their ids among equal scores
are whichever the sorts pick.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.blocks import LANES, SUBLANES

GROUP = SUBLANES                  # entries per deep-select group
SPAN = GROUP * SUBLANES * LANES   # entries per level step: 8 (8, 128) slabs


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


def takes_deep_select(k: int, tile_d: int, n: int) -> bool:
    """Whether ``topk_from_tiles`` ranks rows of ``n`` entries (``n_tiles ·
    tile_d``) at depth ``k`` with the deep select."""
    return k > tile_d and n >= GROUP * k


@functools.partial(jax.jit, static_argnames=("k",))
def deep_topk_select(x: jnp.ndarray, k: int):
    """Exact top-k (values, flat positions) of each row of ``x`` (Q, N),
    sorting the ``N / GROUP`` group maxima and ``GROUP · k`` members
    (module docstring); needs ``N >= GROUP · k``."""
    q, n = x.shape
    steps = -(-n // SPAN)
    if steps * SPAN > n:
        # pads rank below every real or ghost entry and are never picked
        low = (jnp.iinfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.integer)
               else -jnp.inf)
        x = jnp.pad(x, ((0, 0), (0, steps * SPAN - n)), constant_values=low)
    top = x.reshape(q, steps, GROUP, SPAN // GROUP).max(axis=2)
    _, idx = jax.lax.top_k(top.reshape(q, -1), k)
    step, pos = idx // (SPAN // GROUP), idx % (SPAN // GROUP)
    mem = (step[..., None] * SPAN + pos[..., None]
           + jnp.arange(GROUP, dtype=jnp.int32) * (SPAN // GROUP))
    mem = mem.reshape(q, -1)
    vals, at = jax.lax.top_k(jnp.take_along_axis(x, mem, axis=1), k)
    return vals, jnp.take_along_axis(mem, at, axis=1)


def topk_from_tiles(acc_tiles: jnp.ndarray, k: int,
                    n_docs: int | None = None,
                    max_score: int | None = None):
    """Hierarchical top-k over (Q, n_tiles, tile_d) accumulator tiles.

    Returns (scores, doc_ids) of shape (Q, k) with doc ids global to the
    shard.  Pass ``n_docs`` when the tiles overhang the shard so ghost
    lanes can never be selected.  Integer tiles need ``max_score``, a
    static bound on every accumulator entry (entries are non-negative):
    they are ranked on the packed key, so equal scores go to the lower doc
    id whatever order ``lax.top_k`` gives equal keys.  Past one tile's
    depth (``takes_deep_select``) the deep select ranks the whole row."""
    q, n_tiles, tile_d = acc_tiles.shape
    n = n_tiles * tile_d if n_docs is None else min(n_docs, n_tiles * tile_d)
    ghosts = n < n_tiles * tile_d
    gid = (jnp.arange(tile_d, dtype=jnp.int32)[None, :]
           + (jnp.arange(n_tiles, dtype=jnp.int32) * tile_d)[:, None])
    kt = min(k, tile_d)
    deep = takes_deep_select(k, tile_d, n_tiles * tile_d)
    if jnp.issubdtype(acc_tiles.dtype, jnp.integer):
        if max_score is None:
            raise ValueError("integer tiles need max_score")
        b = packed_key_bits(n, max_score)
        low = (1 << b) - 1
        key = acc_tiles.astype(jnp.int32) * (1 << b) + (low - gid)[None]
        if ghosts:
            # below every real key (real keys are >= 0)
            key = jnp.where(gid[None] < n, key, -1)
        if deep:
            key, _ = deep_topk_select(key.reshape(q, -1), k)
        else:
            key_t, _ = jax.lax.top_k(key, kt)             # (Q, T, kt)
            key, _ = jax.lax.top_k(key_t.reshape(q, n_tiles * kt), k)
        return key >> b, (low - (key & low)).astype(jnp.int32)
    if ghosts:
        acc_tiles = jnp.where(gid[None] < n, acc_tiles,
                              jnp.finfo(acc_tiles.dtype).min)
    if deep:
        return deep_topk_select(acc_tiles.reshape(q, -1), k)
    sc_t, idx_t = jax.lax.top_k(acc_tiles, kt)            # (Q, T, kt)
    gidx = idx_t + (jnp.arange(n_tiles, dtype=jnp.int32) * tile_d)[None, :,
                                                                   None]
    sc, pos = jax.lax.top_k(sc_t.reshape(q, n_tiles * kt), k)
    ids = jnp.take_along_axis(gidx.reshape(q, n_tiles * kt), pos, axis=1)
    return sc, ids.astype(jnp.int32)
