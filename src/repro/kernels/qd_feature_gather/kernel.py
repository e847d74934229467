"""Stage-2 query-document feature gather straight from the doc-ordered CSR.

The LTR re-ranker needs, for every (query, candidate) pair, the per-term
exact-score aggregates {Σ score, max score, #matching terms} over the
query's postings.  Each query term's postings already lie contiguous and
doc-sorted in the CSR, so the kernel reads them in place: the CSR's
``docs``/``score`` arrays are kept as ``(rows, 128)`` tables
(``csr_blocks``), and a *block* is one ``(8, 128)`` tile of them — 1,024
consecutive postings.

The grid is (queries, steps).  Step ``s`` of query ``q`` covers one block
of one term's posting range; a query's steps walk its term slots in order
and each slot's blocks in order (``ops.csr_steps`` builds the tables).  The
block row of every step is scalar-prefetched into SMEM and the posting
``BlockSpec``'s ``index_map`` reads it — the ragged-block pattern of paged
attention — so the kernel DMAs only the blocks the batch's terms touch.
Steps past a query's last block repeat its last block index (no new DMA)
and have an empty posting range, so they skip compute under ``pl.when``.

A live step masks the tile to its term's ``[lo, hi)`` postings and matches
them against the query's candidates, held as a ``(C, 1)`` column::

    match[c, p] = doc[p] == cand[c]                  (C × 128 per tile row)
    part[c]     = Σ_p score[p] · match[c, p]         — lane reduce
    bm25 += part;  mx = max(mx, part);  cnt += any_p match[c, p]

Postings are unique (term, doc) pairs, so a step holds at most one posting
per candidate: ``part`` is that posting's score or 0 exactly, in any
reduction order.  Steps run in term order, so ``bm25`` accumulates left to
right over the query's terms, as the numpy ``qd_features`` loop does, and
the aggregates match it bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import CSR_BLOCK, LANES, SUBLANES

BLOCK = CSR_BLOCK               # postings per step: one (8, 128) tile


def _qd_gather_kernel(blk_ref, lo_ref, hi_ref, cand_ref, docs_ref,
                      score_ref, bm25_ref, mx_ref, cnt_ref, *, n_steps: int):
    """One (query, step) grid step: fold one posting block of one query
    term into the query's (C, 1) aggregates."""
    s = pl.program_id(1)
    i = pl.program_id(0) * n_steps + s
    lo, hi = lo_ref[i], hi_ref[i]          # block-local posting bounds

    @pl.when(s == 0)
    def _init():
        bm25_ref[...] = jnp.zeros(bm25_ref.shape, bm25_ref.dtype)
        mx_ref[...] = jnp.zeros(mx_ref.shape, mx_ref.dtype)
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, cnt_ref.dtype)

    @pl.when(lo < hi)
    def _fold():
        k = (jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0) * LANES
             + jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1))
        docs = jnp.where((k >= lo) & (k < hi), docs_ref[...], -1)
        score = score_ref[...]
        cand = cand_ref[...]                           # (C, 1), -1 = pad
        cand = jnp.where(cand >= 0, cand, -2)          # never a posting
        hit_sc = jnp.zeros((cand.shape[0], LANES), jnp.float32)
        hit = jnp.zeros((cand.shape[0], LANES), jnp.bool_)
        for r in range(SUBLANES):
            m = cand == docs[r:r + 1, :]               # (C, 128)
            hit_sc = jnp.where(m, score[r:r + 1, :], hit_sc)
            hit = hit | m
        part = jnp.sum(hit_sc, axis=1, keepdims=True)  # one live lane at most
        bm25_ref[...] += part
        mx_ref[...] = jnp.maximum(mx_ref[...], part)
        cnt_ref[...] += jnp.max(hit.astype(jnp.int32), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qd_feature_gather_csr(blk_docs: jnp.ndarray, blk_score: jnp.ndarray,
                          blk: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
                          cand: jnp.ndarray, *, interpret: bool):
    """Per-(query, candidate) term-score aggregates read from the CSR.

    Args:
      blk_docs: (rows, 128) int32 doc ids of the CSR, -1 past its end
        (``csr_blocks``); read in place, block by block.
      blk_score: (rows, 128) float32 exact scores, 0 past the end.
      blk: (Q, S) int32 block of each step (``csr_steps``).
      lo/hi: (Q, S) int32 block-local ``[lo, hi)`` posting bounds of each
        step; ``lo == hi`` marks a dead step.
      cand: (Q, C) int32 candidate doc ids, -1 padding; C a multiple of 8.
    Returns:
      (bm25, mx, cnt): (Q, C) float32/float32/int32 — Σ score, max score and
      match count per candidate.
    """
    q, n_steps = blk.shape
    c = cand.shape[1]
    col = pl.BlockSpec((c, 1), lambda qi, s, *_: (qi, 0))
    tile = pl.BlockSpec((SUBLANES, LANES),
                        lambda qi, s, b, *_: (b[qi * n_steps + s], 0))
    bm25, mx, cnt = pl.pallas_call(
        functools.partial(_qd_gather_kernel, n_steps=n_steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(q, n_steps),
            in_specs=[col, tile, tile],
            out_specs=[col, col, col]),
        out_shape=[
            jax.ShapeDtypeStruct((q * c, 1), jnp.float32),
            jax.ShapeDtypeStruct((q * c, 1), jnp.float32),
            jax.ShapeDtypeStruct((q * c, 1), jnp.int32),
        ],
        interpret=interpret,
        name="qd_feature_gather_lanes",
    )(blk.reshape(-1), lo.reshape(-1), hi.reshape(-1),
      cand.reshape(q * c, 1), blk_docs, blk_score)
    return bm25.reshape(q, c), mx.reshape(q, c), cnt.reshape(q, c)
