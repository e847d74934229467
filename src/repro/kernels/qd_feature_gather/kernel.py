"""Stage-2 query-document feature gather as a lane-match MXU reduction.

The LTR re-ranker needs, for every (query, candidate) pair, the per-term
exact-score aggregates {Σ score, max score, #matching terms} over the
query's postings.  A scalar per-term binary search is hostile to the TPU's
vector units, so the batched serving path compacts each query's ragged
per-term posting ranges into dense ``(Q, P)`` lanes (the same
``compact_lanes`` layout the DAAT engine uses) and this kernel reduces them
against the candidate grid:

    match = lanes_doc[p] == cand[c]            (P × C in-register compare)
    bm25  = scoresᵀ (1 × P) @ match (P × C)     — one-hot MXU matmul
    cnt   = 1ᵀ @ match
    mx    = column-max of score·match           — VPU reduce

Postings are unique (term, doc) pairs, so a candidate matches at most one
lane per query term — ``cnt`` is exactly the number of matching terms and
``mx`` the max per-term score, i.e. the aggregates ``qd_features`` needs.

The grid is (query blocks, lane tiles): each step takes ``QUERY_ROWS``
queries (a sublane group — the TPU block rule, see
``repro.kernels.blocks``), and lane tiles stream through VMEM and
accumulate into the same ``(QUERY_ROWS, C)`` output block (sequential TPU
grid ⇒ the revisited block is a safe accumulator), so VMEM per step is
O(P_TILE · C) no matter how long the query's posting lanes are.  The sum
runs at ``Precision.HIGHEST``, so scores are not rounded to bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.blocks import SUBLANES, pad_axis, round_up


QUERY_ROWS = SUBLANES


def _qd_gather_kernel(cand_ref, docs_ref, scores_ref, bm25_ref, mx_ref,
                      cnt_ref):
    """One (query block, lane-tile) grid step: reduce a lane tile into the
    block's (QUERY_ROWS, C) aggregates, one query row at a time."""
    pt = pl.program_id(1)
    for i in range(QUERY_ROWS):
        d = docs_ref[i, :]                      # (PT,) int32, -1 = dead lane
        s = scores_ref[i, :]                    # (PT,) float32
        c = cand_ref[i, :]                      # (C,) int32, -1 = pad
        match = ((d[:, None] == c[None, :])
                 & (d[:, None] >= 0) & (c[None, :] >= 0))   # (PT, C)
        mf = match.astype(jnp.float32)
        part_sum = jax.lax.dot_general(
            s[None, :], mf, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)[0]
        part_cnt = jax.lax.dot_general(
            jnp.ones((1, d.shape[0]), jnp.bfloat16),
            match.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[0]
        part_mx = jnp.max(jnp.where(match, s[:, None], 0.0), axis=0)

        @pl.when(pt == 0)
        def _init(i=i, part_sum=part_sum, part_mx=part_mx,
                  part_cnt=part_cnt):
            bm25_ref[i, :] = part_sum
            mx_ref[i, :] = part_mx
            cnt_ref[i, :] = part_cnt.astype(jnp.int32)

        @pl.when(pt > 0)
        def _accumulate(i=i, part_sum=part_sum, part_mx=part_mx,
                        part_cnt=part_cnt):
            bm25_ref[i, :] += part_sum
            mx_ref[i, :] = jnp.maximum(mx_ref[i, :], part_mx)
            cnt_ref[i, :] += part_cnt.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("p_tile", "interpret"))
def qd_feature_gather_lanes(lane_docs: jnp.ndarray, lane_scores: jnp.ndarray,
                            cand: jnp.ndarray, *, p_tile: int = 512,
                            interpret: bool):
    """Per-(query, candidate) term-score aggregates over compacted lanes.

    Args:
      lane_docs: (Q, P) int32 doc ids of the query's postings, -1 dead.
      lane_scores: (Q, P) float32 exact scores, 0 in dead lanes.
      cand: (Q, C) int32 candidate doc ids, -1 padding.
      p_tile: posting lanes per grid step (P must be a multiple).
    Returns:
      (bm25, mx, cnt): (Q, C) float32/float32/int32 — Σ score, max score and
      match count per candidate.
    """
    q, p = lane_docs.shape
    c = cand.shape[1]
    assert p % p_tile == 0, (p, p_tile)
    qp = round_up(q, QUERY_ROWS)
    # padded query rows have no live lanes and no candidates
    lane_docs = pad_axis(lane_docs, 0, qp, -1)
    lane_scores = pad_axis(lane_scores, 0, qp, 0.0)
    cand = pad_axis(cand, 0, qp, -1)
    rows = pl.BlockSpec((QUERY_ROWS, c), lambda qi, t: (qi, 0))
    lanes = pl.BlockSpec((QUERY_ROWS, p_tile), lambda qi, t: (qi, t))
    bm25, mx, cnt = pl.pallas_call(
        _qd_gather_kernel,
        grid=(qp // QUERY_ROWS, p // p_tile),
        in_specs=[rows, lanes, lanes],
        out_specs=[rows, rows, rows],
        out_shape=[
            jax.ShapeDtypeStruct((qp, c), jnp.float32),
            jax.ShapeDtypeStruct((qp, c), jnp.float32),
            jax.ShapeDtypeStruct((qp, c), jnp.int32),
        ],
        interpret=interpret,
        name="qd_feature_gather_lanes",
    )(cand, lane_docs, lane_scores)
    return bm25[:q], mx[:q], cnt[:q]
