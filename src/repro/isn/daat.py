"""DAAT (BMW-style) block-max engine — batched JAX serving path.

TPU-native adaptation of Block-Max WAND: per-block upper bounds are
accumulated from the sparse block-max structure, a phase-1 pass over the
highest-bound blocks bootstraps a rank-safe threshold τ, and the exact pass
scores only blocks with ``ub > θ·τ``.  θ = 1.0 is rank-safe; θ > 1.0 is the
paper's aggression parameter.

Serving pipeline (``daat_serve``)
---------------------------------
Queries are served as a batch, not one at a time: block bounds and the
phase-1 selection are vmapped, and the scoring hot loop dispatches through
a backend switch (see ``repro.isn.backend``):

* ``"pallas"`` / ``"interpret"`` — the block bounds come from
  ``repro.kernels.block_bounds``, which walks each query term's own
  block-max entries in place (one 1,024-entry tile a grid step) instead of
  gathering ``L × bcap`` padded lanes; the exact pass runs on
  ``repro.kernels.blockmax_score`` over the shard's **build-time bucketed
  postings mirror** (``IndexShard.tile_*``): a (query blocks, tile groups,
  lane chunks) grid where each step term-matches doc-tile buckets against a
  block of queries and reduces with a one-hot MXU matmul.  A tile pruned
  for every query of the block (up to 64 queries,
  ``repro.kernels.blocks.query_rows``) skips its matmul (``pl.when``), so
  the kernel's time follows the union of the block's surviving tiles, not
  each query's own.  The per-query ``work``/``blocks`` counters that
  ``CostModel`` charges model a per-query DAAT engine — the data-dependent
  tail of the paper's Fig. 3 that budgeted SAAT does not have; how closely
  the compiled kernel follows them is not measured.  ``interpret=True``
  runs the identical kernel program under the Pallas interpreter on CPU
  (tests).
* ``"jnp"`` — vectorized batched gather + one fused scatter over the CSR
  mirror (the block bounds too: ``_block_bounds_batched``, the reference
  the bounds kernel is held to); identical results, the portable fast
  path on CPU hosts.

Exactly **one exact-scoring pass** runs per query: the phase-1 accumulator
is kept and the exact pass only scores blocks in ``survive \\ phase1``
(the two block sets are disjoint by construction), so no posting is ever
scored twice.  The jnp backend additionally compacts the ragged per-term
posting ranges into a (Q, qcap) lane buffer before its fused scatter, so
scatter traffic tracks the batch's actual postings rather than L·max_df
padding.  On the kernel backends top-k is the tiled hierarchical merge
(per-tile top-k over the (Q, n_tiles, TILE_D) accumulator tiles, then a
merge over per-tile candidates), or past one tile's depth (k > TILE_D) the
deep select (``repro.kernels.topk``), which sorts the n_docs/8 maxima of
doc groups and the 8·k members of the k best groups; the dense jnp path
keeps XLA's native batched top-k, which is faster on CPU.

``daat_serve_laxmap`` preserves the original one-query-at-a-time
``lax.map`` + dense scatter-add reference; the parity tests and the
serving benchmark hold the batched pipeline to its output.

Caveats vs the reference: the kernel backends score *all* postings of a
matched term (the bucketed mirror has no per-term gather cap), so they
coincide with the reference only when ``cap >= max_df`` — which is how the
servers call it; duplicate query terms score once in the kernel backends
(term membership) but once per occurrence in the gather paths — query
builders emit unique terms.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.index.postings import IndexShard
from repro.isn.backend import (SegmentLists, compact_lanes,
                               map_query_blocks, merge_segments,
                               deep_select_ran, query_lane_budget,
                               resolve_backend, topk_from_tiles)
from repro.kernels.block_bounds.ops import block_bounds
from repro.kernels.blockmax_score.ops import blockmax_score_tiles


class DaatResult(NamedTuple):
    topk_docs: jnp.ndarray     # (Q, k)
    topk_scores: jnp.ndarray   # (Q, k) exact BM25
    work: jnp.ndarray          # (Q,) postings in surviving blocks
    blocks: jnp.ndarray        # (Q,) surviving blocks


def _block_bounds(shard: IndexShard, terms, mask, n_blocks: int, bcap: int):
    """Accumulate per-block upper bounds and candidate counts for a query."""
    base = shard.bm_offsets[terms]
    cnt = shard.bm_offsets[terms + 1] - base
    pos = base[:, None] + jnp.arange(bcap, dtype=jnp.int32)[None, :]
    live = (jnp.arange(bcap, dtype=jnp.int32)[None, :] < cnt[:, None]) \
        & (mask[:, None] > 0)
    pos = jnp.minimum(pos, shard.bm_block_id.shape[0] - 1)
    bid = jnp.where(live, shard.bm_block_id[pos], 0)
    bmax = jnp.where(live, shard.bm_block_max[pos], 0.0)
    bcnt = jnp.where(live, shard.bm_block_cnt[pos], 0)
    ub = jnp.zeros((n_blocks,), jnp.float32).at[bid.reshape(-1)].add(bmax.reshape(-1))
    ccnt = jnp.zeros((n_blocks,), jnp.int32).at[bid.reshape(-1)].add(bcnt.reshape(-1))
    return ub, ccnt


def _masked_score(shard: IndexShard, terms, mask, survive, n_docs: int,
                  block_size: int, cap: int):
    """Exact scoring of postings whose doc block survives pruning."""
    base = shard.offsets[terms]
    df = shard.offsets[terms + 1] - base
    pos = base[:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
    live = (jnp.arange(cap, dtype=jnp.int32)[None, :] < df[:, None]) \
        & (mask[:, None] > 0)
    pos = jnp.minimum(pos, shard.docs.shape[0] - 1)
    d = jnp.where(live, shard.docs[pos], 0)
    s = jnp.where(live, shard.score[pos], 0.0)
    keep = survive[d // block_size] & live
    s = jnp.where(keep, s, 0.0)
    d = jnp.where(keep, d, 0)
    acc = jnp.zeros((n_docs,), jnp.float32).at[d.reshape(-1)].add(s.reshape(-1))
    return acc


# ---------------------------------------------------------------------------
# batched pipeline
# ---------------------------------------------------------------------------

def _block_bounds_batched(shard: IndexShard, terms, mask, n_blocks: int,
                          bcap: int):
    """Batched block bounds: one flat scatter over the whole batch's block
    entries instead of a vmapped per-query scatter."""
    q = terms.shape[0]
    base = shard.bm_offsets[terms]                           # (Q, L)
    cnt = shard.bm_offsets[terms + 1] - base
    lanes = jnp.arange(bcap, dtype=jnp.int32)
    pos = base[..., None] + lanes[None, None, :]
    live = (lanes[None, None, :] < cnt[..., None]) & (mask[..., None] > 0)
    pos = jnp.minimum(pos, shard.bm_block_id.shape[0] - 1)
    bid = jnp.where(live, shard.bm_block_id[pos], 0)
    bmax = jnp.where(live, shard.bm_block_max[pos], 0.0)
    bcnt = jnp.where(live, shard.bm_block_cnt[pos], 0)
    flat = (jnp.arange(q, dtype=jnp.int32)[:, None, None] * n_blocks
            + bid).reshape(-1)
    ub = jnp.zeros((q * n_blocks,), jnp.float32).at[flat].add(
        bmax.reshape(-1)).reshape(q, n_blocks)
    ccnt = jnp.zeros((q * n_blocks,), jnp.int32).at[flat].add(
        bcnt.reshape(-1)).reshape(q, n_blocks)
    return ub, ccnt


def _phase1_blocks(ub, ccnt, block_size: int, k: int, n_blocks: int):
    """Rank the blocks by upper bound and keep the highest-bound prefix
    holding >= 2k candidate docs — the threshold-bootstrapping phase-1 set."""
    q = ub.shape[0]
    cand = jnp.minimum(ccnt, block_size)
    order = jnp.argsort(-ub, axis=1)
    cum = jnp.cumsum(jnp.take_along_axis(cand, order, axis=1), axis=1)
    need = jnp.minimum(
        jax.vmap(lambda c: jnp.searchsorted(c, 2 * k))(cum) + 1, n_blocks)
    rank = jnp.zeros((q, n_blocks), jnp.int32).at[
        jnp.arange(q, dtype=jnp.int32)[:, None], order].set(
        jnp.broadcast_to(jnp.arange(n_blocks, dtype=jnp.int32), (q, n_blocks)))
    return rank < need[:, None]


def _gather_compact_postings(shard: IndexShard, terms, mask, cap: int,
                             qcap: int):
    """Compact the batch's ragged per-term posting ranges into (Q, qcap)
    dense lanes and gather (doc, score) once — both scoring passes reuse
    this layout, so no posting is gathered (or scored) twice."""
    base = shard.offsets[terms]                              # (Q, L)
    df = shard.offsets[terms + 1] - base
    dfs = jnp.minimum(df, cap) * (mask > 0)
    pos, live = compact_lanes(base, dfs.astype(jnp.int32), qcap)
    pos = jnp.minimum(pos, shard.docs.shape[0] - 1)
    d = jnp.where(live, shard.docs[pos], 0)
    s = jnp.where(live, shard.score[pos], 0.0)
    return d, s, live


def _score_pass(d, s, live, survive, n_docs: int, block_size: int):
    """One masked scoring pass over the compacted lanes: mask lanes whose
    block is pruned, then one fused flat scatter into the (Q, n_docs)
    accumulator — scatter traffic tracks the batch's actual postings, not
    L·max_df padding."""
    q = d.shape[0]
    keep = jnp.take_along_axis(survive, d // block_size, axis=1) & live
    s = jnp.where(keep, s, 0.0)
    d = jnp.where(keep, d, 0)
    flat = (jnp.arange(q, dtype=jnp.int32)[:, None] * n_docs + d).reshape(-1)
    return jnp.zeros((q * n_docs,), jnp.float32).at[flat].add(
        s.reshape(-1)).reshape(q, n_docs)


def _kth_score(topk_out, k: int):
    """Extract the k-th top score behind an optimization barrier: without
    it, XLA CPU sees only one top-k column consumed and re-lowers the fast
    TopK call into a full sort (~30x slower)."""
    vals, idxs = jax.lax.optimization_barrier(topk_out)
    return vals[:, k - 1]


def _daat_batched(shard: IndexShard, terms, mask, theta, *, n_docs: int,
                  n_blocks: int, block_size: int, k: int, cap: int,
                  bcap: int, qcap: int, tile_d: int, backend: str):
    interpret = backend == "interpret"
    if backend == "jnp":
        ub, ccnt = _block_bounds_batched(shard, terms, mask, n_blocks, bcap)
    else:
        ub, ccnt = block_bounds(shard.bm_offsets, shard.bm_block_id,
                                shard.bm_block_max, shard.bm_block_cnt,
                                terms, mask, n_blocks=n_blocks, bcap=bcap,
                                interpret=interpret)
    in_p1 = _phase1_blocks(ub, ccnt, block_size, k, n_blocks)

    if backend == "jnp":
        d, s, live = _gather_compact_postings(shard, terms, mask, cap, qcap)
        acc1 = _score_pass(d, s, live, in_p1, n_docs, block_size)
        tau = _kth_score(jax.lax.top_k(acc1, k), k)
        extra = (ub >= theta[:, None] * tau[:, None]) & ~in_p1
        acc = acc1 + _score_pass(d, s, live, extra, n_docs, block_size)
        sc, ids = jax.lax.top_k(acc, k)
    else:
        qterms = jnp.where(mask > 0, terms, -1).astype(jnp.int32)
        acc1_t = blockmax_score_tiles(
            shard.tile_docs, shard.tile_terms, shard.tile_scores, qterms,
            in_p1, tile_d=tile_d, block_size=block_size, n_blocks=n_blocks,
            interpret=interpret)
        tau = _kth_score(topk_from_tiles(acc1_t, k, n_docs=n_docs), k)
        extra = (ub >= theta[:, None] * tau[:, None]) & ~in_p1
        acc_t = acc1_t + blockmax_score_tiles(
            shard.tile_docs, shard.tile_terms, shard.tile_scores, qterms,
            extra, tile_d=tile_d, block_size=block_size, n_blocks=n_blocks,
            interpret=interpret)
        sc, ids = topk_from_tiles(acc_t, k, n_docs=n_docs)

    survive = in_p1 | extra
    work = jnp.sum(jnp.where(survive, ccnt, 0), axis=1)
    blocks = jnp.sum(survive.astype(jnp.int32), axis=1)
    return ids.astype(jnp.int32), sc, work, blocks


@functools.partial(jax.jit,
                   static_argnames=("n_docs", "n_blocks", "block_size", "k",
                                    "cap", "bcap", "qcap", "tile_d",
                                    "q_block", "backend"))
def daat_serve(shard: IndexShard, terms: jnp.ndarray, mask: jnp.ndarray,
               theta: jnp.ndarray, *, n_docs: int, n_blocks: int,
               block_size: int, k: int, cap: int, bcap: int,
               qcap: int | None = None, tile_d: int = 128, q_block: int = 64,
               backend: str | None = None) -> DaatResult:
    """Serve a batch of queries with block-max pruned DAAT.

    cap: static per-term postings bound (max df in shard).
    bcap: static per-term block-entry bound.
    qcap: static per-QUERY posting-lane budget, read only by the jnp
      backend's compacted gather; must cover max_q Σ_t min(df_t, cap) over
      the batch (size it with ``repro.isn.backend.query_lane_budget``).
      None falls back to the exact worst case L·cap.  The kernel backends
      never read it: leave it None there, where a value would only add a
      compile key.
    tile_d: docs per accumulator tile (must match the shard's bucketed
      mirror when a kernel backend runs).
    q_block: queries scored concurrently; larger batches stream through in
      q_block-sized chunks so accumulator memory stays O(q_block · n_docs).
    backend: "pallas" | "interpret" | "jnp" | None (auto: pallas on TPU,
      jnp elsewhere) — see ``repro.isn.backend``.
    """
    backend = resolve_backend(backend)
    if qcap is None:
        qcap = terms.shape[1] * cap
    qcap = min(qcap, terms.shape[1] * cap)
    fn = functools.partial(_daat_batched, shard, n_docs=n_docs,
                           n_blocks=n_blocks, block_size=block_size, k=k,
                           cap=cap, bcap=bcap, qcap=qcap, tile_d=tile_d,
                           backend=backend)
    out = map_query_blocks(fn, (terms, mask, theta), (0, 0.0, 1.0), q_block)
    return DaatResult(*out)


@functools.partial(jax.jit,
                   static_argnames=("n_docs", "n_blocks", "block_size", "k",
                                    "cap", "bcap"))
def daat_serve_laxmap(shard: IndexShard, terms: jnp.ndarray,
                      mask: jnp.ndarray, theta: jnp.ndarray, *, n_docs: int,
                      n_blocks: int, block_size: int, k: int, cap: int,
                      bcap: int) -> DaatResult:
    """One-query-at-a-time reference pipeline (`lax.map` + dense scatter-add
    + full-collection top-k).  Scores every surviving posting twice (phase-1
    rescan) — kept as the parity oracle and the benchmark baseline for the
    batched pipeline."""
    def one(terms_q, mask_q, theta_q):
        ub, ccnt = _block_bounds(shard, terms_q, mask_q, n_blocks, bcap)
        # phase 1: highest-bound blocks until >= 2k candidate docs
        cand = jnp.minimum(ccnt, block_size)
        order = jnp.argsort(-ub)
        cum = jnp.cumsum(cand[order])
        need = jnp.minimum(jnp.searchsorted(cum, 2 * k) + 1, n_blocks)
        rank = jnp.zeros((n_blocks,), jnp.int32).at[order].set(
            jnp.arange(n_blocks, dtype=jnp.int32))
        in_p1 = rank < need
        acc1 = _masked_score(shard, terms_q, mask_q, in_p1, n_docs,
                             block_size, cap)
        tau = jax.lax.top_k(acc1, k)[0][k - 1]
        survive = (ub >= theta_q * tau) | in_p1
        acc = _masked_score(shard, terms_q, mask_q, survive, n_docs,
                            block_size, cap)
        sc, ids = jax.lax.top_k(acc, k)
        work = jnp.sum(jnp.where(survive, ccnt, 0))
        return ids.astype(jnp.int32), sc, work, jnp.sum(survive.astype(jnp.int32))

    ids, sc, work, blocks = jax.lax.map(lambda args: one(*args),
                                        (terms, mask, theta))
    return DaatResult(ids, sc, work, blocks)


def daat_serve_segments(segments, terms, mask, theta, *, k: int,
                        backend: str | None = None, drop=None,
                        engine=None) -> SegmentLists:
    """Serve one batch over an ordered segment list and merge the top-k.

    ``segments`` are :class:`~repro.isn.backend.Segment` s in ascending
    global-doc order: sealed shards, then the live delta, whose ``doc_lo``
    is the sealed collection size.  Each segment is scanned with its own
    static caps (a delta's capacity padding is inert: padded lanes sit past
    every term's df), and the candidates merge with ``merge_shard_topk``'s
    lower-global-doc-id tie rule; ``drop`` ((n_segments, Q) bool) masks
    lost slots out of the merge.  Only the ``jnp`` backend reads a lane
    budget: it is sized from the segment's host ``df`` (the delta has none
    and keeps the spec-static ``L * max_df``).  ``engine`` replaces the
    per-segment ``daat_serve`` (same signature).
    """
    backend = resolve_backend(backend)
    engine = engine or daat_serve
    host = terms, mask
    terms, mask = jnp.asarray(terms), jnp.asarray(mask)
    res = []
    for g in segments:
        sp = g.spec
        qcap = (query_lane_budget(g.df, *host)
                if backend == "jnp" and g.df is not None else None)
        res.append(engine(g.shard, terms, mask, theta, n_docs=sp.n_docs,
                          n_blocks=sp.n_blocks, block_size=sp.block_size,
                          k=k, cap=sp.max_df, bcap=sp.max_blocks_per_term,
                          qcap=qcap, tile_d=sp.tile_d, backend=backend))
    return SegmentLists(*merge_segments(segments, res, k, drop),
                        [r.work for r in res], [r.blocks for r in res],
                        deep_select_ran(segments, k, backend))
