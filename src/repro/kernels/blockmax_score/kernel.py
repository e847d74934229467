"""Block-max pruned DAAT scoring — the TPU adaptation of BMW's skip logic.

Same bucketed one-hot-matmul layout as ``impact_accumulate`` (query block ×
tile group × lane chunk grid over the shard's build-time bucketed mirror,
term matching in-register) plus the BMW ingredient: per-(query, doc)
*survival* from the block upper bounds.  Survival rides in as a 0/1 doc
mask; a tile whose docs are pruned for every query of the query block (up
to 64 rows, ``query_rows``) skips its matmul via ``pl.when``, so the
kernel's time follows the union of the block's surviving tiles.  The
mirror's blocks are still fetched for skipped tiles; only their compute is
saved.  The SAAT kernel's grid, by contrast, is budget-bounded.

All lanes of one doc share the doc's pruning block, so masking a doc's
accumulated score equals masking its lanes.

Scores are float32 and stay so, yet the matmul is one bfloat16 MXU pass.
Each tile's score row is split into three bfloat16 parts, ``hi`` (the
score rounded to bfloat16), ``mid`` (the remainder rounded) and ``lo``
(what is left, at most 8 significant bits), whose sum is the score bit for
bit (``split_bf16``).  The one-hot is 0/1, which bfloat16 holds exactly,
so every product is exact and the MXU sums them in float32: the three
parts, stacked as ``3·QB`` rows, go through one contraction against one
one-hot, and ``(hi + mid) + lo`` per doc is the float32 sum of its
postings up to float32 rounding.  ``Precision.HIGHEST`` would compute the
same sum in several MXU passes, loading the one-hot for each.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.blocks import (TILES_PER_STEP, check_mirror, lane_chunk,
                                  pad_axis, query_rows, round_up)


def split_bf16(x: jnp.ndarray):
    """Split float32 ``x`` into three float32 arrays of bfloat16 values
    with ``(hi + mid) + lo == x`` exactly: each part rounds the remainder
    of the ones before it to bfloat16's 8 significant bits, and the
    remainders (at most 16, then 8 significant bits) are exact in float32."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    r = x - hi
    mid = r.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, mid, r - mid


def _score_kernel(qterms_ref, keep_ref, docs_ref, terms_ref, scores_ref,
                  acc_ref, *, tile_d: int):
    """One (query block, tile group, lane chunk) grid step."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    qt = qterms_ref[...]                          # (QB, L) query terms, -1 pad
    ch = docs_ref.shape[1]
    for i in range(TILES_PER_STEP):
        cols = slice(i * tile_d, (i + 1) * tile_d)
        keep = keep_ref[:, cols]                  # (QB, TILE_D) doc survival

        @pl.when(jnp.max(keep) > 0)
        def _score(i=i, cols=cols, keep=keep):
            local = docs_ref[i:i + 1, :]          # (1, CH) tile-local, -1 pad
            tterm = terms_ref[i:i + 1, :]
            hi, mid, lo = split_bf16(scores_ref[i:i + 1, :])
            match = tterm == qt[:, 0:1]
            for l in range(1, qt.shape[1]):
                match = match | (tterm == qt[:, l:l + 1])
            live = match & (local >= 0)                      # (QB, CH)
            v = jnp.concatenate([jnp.where(live, x, 0.0)
                                 for x in (hi, mid, lo)]).astype(jnp.bfloat16)
            onehot = (jax.lax.broadcasted_iota(jnp.int32, (tile_d, ch), 0)
                      == local).astype(jnp.bfloat16)
            parts = jax.lax.dot_general(
                v, onehot, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # (3·QB, TILE_D)
            qb = qt.shape[0]
            part = (parts[:qb] + parts[qb:2 * qb]) + parts[2 * qb:]
            acc_ref[:, cols] += part * keep


@functools.partial(jax.jit, static_argnames=("tile_d", "interpret"))
def blockmax_score_batched(tile_docs: jnp.ndarray, tile_terms: jnp.ndarray,
                           tile_scores: jnp.ndarray, qterms: jnp.ndarray,
                           keep: jnp.ndarray, *, tile_d: int,
                           interpret: bool) -> jnp.ndarray:
    """Batched exact scoring over the shard's bucketed postings mirror.

    Args:
      tile_docs/tile_terms/tile_scores: (n_tiles, CAP) bucketed shard mirror
        (tile-local doc ids with -1 padding; ``pack_tiles``: whole tile
        groups and lane multiples) — read in place, shared across the batch.
      qterms: (Q, L) query term ids, -1 for masked-out slots.
      keep: (Q, n_tiles · tile_d) float32 0/1 per-doc survival.
    Returns:
      (Q, n_tiles, tile_d) float32 accumulator tiles.
    """
    check_mirror(tile_docs)
    nt, cap = tile_docs.shape
    q, L = qterms.shape
    qb = query_rows(q)
    qp = round_up(q, qb)
    ch = lane_chunk(cap)
    qt = pad_axis(qterms.astype(jnp.int32), 0, qp, -1)
    kp = pad_axis(pad_axis(keep.astype(jnp.float32), 0, qp, 0.0),
                  1, nt * tile_d, 0.0)
    mirror = pl.BlockSpec((TILES_PER_STEP, ch), lambda a, t, c: (t, c))
    cols = pl.BlockSpec((qb, TILES_PER_STEP * tile_d), lambda a, t, c: (a, t))
    acc = pl.pallas_call(
        functools.partial(_score_kernel, tile_d=tile_d),
        grid=(qp // qb, nt // TILES_PER_STEP, cap // ch),
        in_specs=[pl.BlockSpec((qb, L), lambda a, t, c: (a, 0)), cols,
                  mirror, mirror, mirror],
        out_specs=cols,
        out_shape=jax.ShapeDtypeStruct((qp, nt * tile_d), jnp.float32),
        interpret=interpret,
        name="blockmax_score_batched",
    )(qt, kp, tile_docs, tile_terms, tile_scores)
    return acc[:q].reshape(q, nt, tile_d)
