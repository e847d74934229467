"""SAAT (JASS-style) anytime engine — batched JAX serving path.

Score-at-a-time traversal over the impact-ordered mirror.  The ρ budget is
resolved to a per-query impact-level cut ``lstar`` (JASS processes whole
impact segments, highest impact first, while the budget allows); every
posting whose impact reaches the cut contributes to the accumulator.

Cost is a deterministic function of ρ — the compiled program cannot touch
more than ρ_max postings (gather paths) or more than the shard's bucketed
mirror (kernel paths, whose grid is fixed by the layout), so the 200 ms
worst-case guarantee is *structural*.

Serving pipeline (``saat_serve``)
---------------------------------
Queries are served as a batch through a backend switch
(see ``repro.isn.backend``):

* ``"pallas"`` / ``"interpret"`` — the accumulation dispatches through
  ``repro.kernels.impact_accumulate`` over the shard's build-time bucketed
  postings mirror (``IndexShard.tile_*``): a (query blocks, tile groups,
  lane chunks) grid, term matching in-register, one-hot MXU matmul
  reduction shared by the block's queries.
  The level cut rides in as the per-query scalar ``lstar``.
  ``interpret=True`` runs the identical kernel program on CPU (tests).
* ``"jnp"`` — vectorized batched gather of the per-term impact-ordered
  prefixes plus one fused scatter; identical results on any host.

Top-k is the tiled hierarchical merge from ``repro.isn.backend`` (the deep
select past one tile's depth) rather than a full-collection ``lax.top_k``,
ranked on packed (score, doc) keys so equal scores go to the lower doc id
on every backend.
``saat_serve_laxmap`` preserves the original one-query-at-a-time pipeline
as parity oracle and benchmark baseline.  Accumulation is integer, so all
backends agree bit-exactly.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.index.postings import IndexShard
from repro.isn.backend import (SegmentLists, compact_lanes,
                               deep_select_ran, map_query_blocks,
                               merge_segments, resolve_backend,
                               topk_from_tiles)
from repro.kernels.impact_accumulate.ops import impact_accumulate_tiles


class SaatResult(NamedTuple):
    topk_docs: jnp.ndarray     # (Q, k) local doc ids
    topk_scores: jnp.ndarray   # (Q, k) quantized-impact scores
    work: jnp.ndarray          # (Q,) postings actually scored


def _level_cut(shard: IndexShard, terms, mask, rho):
    """Most inclusive impact level whose total postings fit the budget.

    Returns (per-term prefix lengths, total postings, the level cut itself).
    The cut is ``n_levels`` (excluding everything) when even the sparsest
    level blows the budget."""
    lc = shard.level_cum[terms] * mask[:, None].astype(jnp.int32)  # (L, 256)
    total = jnp.sum(lc, axis=0)                                    # (256,)
    ok = total <= rho
    # `total` is non-increasing in level index; first ok level = cut
    lstar = jnp.argmax(ok)
    any_ok = jnp.any(ok)
    prefix = jnp.where(any_ok, lc[:, lstar], 0)
    work = jnp.where(any_ok, total[lstar], 0)
    lstar = jnp.where(any_ok, lstar,
                      shard.level_cum.shape[1]).astype(jnp.int32)
    return prefix, work, lstar


def _accumulate(shard: IndexShard, terms, prefix, n_docs: int, cap: int):
    """Gather per-term impact-ordered prefixes and scatter-add into a dense
    accumulator (the jnp oracle of the Pallas scatter-as-matmul kernel)."""
    base = shard.offsets[terms]                                   # (L,)
    pos = base[:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
    live = jnp.arange(cap, dtype=jnp.int32)[None, :] < prefix[:, None]
    pos = jnp.minimum(pos, shard.docs_imp.shape[0] - 1)
    d = shard.docs_imp[pos]
    v = shard.imp[pos] * live.astype(jnp.int32)
    # dead lanes scatter 0 into doc 0 — harmless
    d = jnp.where(live, d, 0)
    acc = jnp.zeros((n_docs,), jnp.int32).at[d.reshape(-1)].add(v.reshape(-1))
    return acc


# ---------------------------------------------------------------------------
# batched pipeline
# ---------------------------------------------------------------------------

def _level_cut_batched(shard: IndexShard, terms, mask, rho):
    return jax.vmap(
        lambda t, m, r: _level_cut(shard, t, m, r))(terms, mask, rho)


def _accumulate_batched(shard: IndexShard, terms, prefix, n_docs: int,
                        cap: int):
    """Batched accumulation of the impact-ordered prefixes: compact the
    per-term prefixes into (Q, cap) dense lanes (the JASS budget guarantees
    Σ prefix ≤ ρ ≤ cap, so the compact buffer is exact), then one fused
    flat scatter into the (Q, n_docs) accumulator — O(Q · ρ) scatter
    traffic, the batched form of "cost tracks the budget"."""
    q = terms.shape[0]
    base = shard.offsets[terms]                              # (Q, L)
    pos, live = compact_lanes(base, prefix, cap)
    pos = jnp.minimum(pos, shard.docs_imp.shape[0] - 1)
    d = jnp.where(live, shard.docs_imp[pos], 0)
    v = jnp.where(live, shard.imp[pos], 0)
    flat = (jnp.arange(q, dtype=jnp.int32)[:, None] * n_docs + d).reshape(-1)
    return jnp.zeros((q * n_docs,), jnp.int32).at[flat].add(
        v.reshape(-1)).reshape(q, n_docs)


def _saat_batched(shard: IndexShard, terms, mask, rho, *, n_docs: int,
                  k: int, cap: int, tile_d: int, backend: str):
    prefix, work, lstar = _level_cut_batched(shard, terms, mask, rho)
    # every accumulator entry is a sum of at most L impacts
    max_score = terms.shape[1] * (shard.level_cum.shape[1] - 1)
    if backend == "jnp":
        prefix = jnp.minimum(prefix, cap)
        acc = _accumulate_batched(shard, terms, prefix, n_docs, cap)
        sc, ids = topk_from_tiles(acc[:, None, :], k, max_score=max_score)
    else:
        qterms = jnp.where(mask > 0, terms, -1).astype(jnp.int32)
        acc_t = impact_accumulate_tiles(
            shard.tile_docs, shard.tile_terms, shard.tile_imps, qterms,
            lstar, tile_d=tile_d, interpret=backend == "interpret")
        sc, ids = topk_from_tiles(acc_t, k, n_docs=n_docs,
                                  max_score=max_score)
    return ids.astype(jnp.int32), sc.astype(jnp.float32), work


@functools.partial(jax.jit, static_argnames=("n_docs", "k", "cap", "tile_d",
                                             "q_block", "backend"))
def saat_serve(shard: IndexShard, terms: jnp.ndarray, mask: jnp.ndarray,
               rho: jnp.ndarray, *, n_docs: int, k: int, cap: int,
               tile_d: int = 128, q_block: int = 64,
               backend: str | None = None) -> SaatResult:
    """Serve a batch of queries on one ISN shard.

    Args:
      terms: (Q, L) padded query term ids.
      mask: (Q, L) query term mask.
      rho: (Q,) per-query postings budgets (already capped at ρ_max by the
        Stage-0 scheduler; `cap` is the static ρ_max bound that sizes the
        gather paths, so their compiled cost is O(Q · L · cap)).
      n_docs / k / cap: static shard size, retrieval depth, per-term prefix
        cap.
      tile_d: docs per accumulator tile (must match the shard's bucketed
        mirror when a kernel backend runs).
      q_block: queries scored concurrently; larger batches stream through
        in q_block-sized chunks.
      backend: "pallas" | "interpret" | "jnp" | None (auto) — see
        ``repro.isn.backend``.
    """
    backend = resolve_backend(backend)
    fn = functools.partial(_saat_batched, shard, n_docs=n_docs, k=k, cap=cap,
                           tile_d=tile_d, backend=backend)
    out = map_query_blocks(fn, (terms, mask, rho), (0, 0.0, 0), q_block)
    return SaatResult(*out)


@functools.partial(jax.jit, static_argnames=("n_docs", "k", "cap"))
def saat_serve_laxmap(shard: IndexShard, terms: jnp.ndarray,
                      mask: jnp.ndarray, rho: jnp.ndarray, *, n_docs: int,
                      k: int, cap: int) -> SaatResult:
    """One-query-at-a-time reference pipeline (`lax.map` + dense scatter-add
    + full-collection top-k) — parity oracle and benchmark baseline."""
    def one(terms_q, mask_q, rho_q):
        prefix, work, _ = _level_cut(shard, terms_q, mask_q, rho_q)
        prefix = jnp.minimum(prefix, cap)
        acc = _accumulate(shard, terms_q, prefix, n_docs, cap)
        sc, ids = jax.lax.top_k(acc, k)
        return ids.astype(jnp.int32), sc.astype(jnp.float32), work

    ids, sc, work = jax.lax.map(lambda args: one(*args), (terms, mask, rho))
    return SaatResult(ids, sc, work)


def saat_serve_segments(segments, terms, mask, rhos, *, k: int, cap: int,
                        backend: str | None = None, drop=None,
                        engine=None) -> SegmentLists:
    """Serve one batch over an ordered segment list and merge the top-k.

    ``segments`` are :class:`~repro.isn.backend.Segment` s in ascending
    global-doc order: sealed shards, then the live delta.  ``rhos[i]`` is
    segment ``i``'s per-query postings budget: the caller splits one global
    level cut over *all* segments, so the union of the scanned prefixes is
    exactly the budgeted work.  Integer accumulation keeps the merge
    bit-exact across backends; a delta's capacity padding contributes zero
    impact and is outranked by real candidates.  ``drop`` ((n_segments, Q)
    bool) masks lost slots out of the merge.  ``engine`` replaces the
    per-segment ``saat_serve`` (same signature).
    """
    backend = resolve_backend(backend)
    engine = engine or saat_serve
    terms, mask = jnp.asarray(terms), jnp.asarray(mask)
    res = [engine(g.shard, terms, mask, rho, n_docs=g.spec.n_docs, k=k,
                  cap=cap, tile_d=g.spec.tile_d, backend=backend)
           for g, rho in zip(segments, rhos)]
    return SegmentLists(*merge_segments(segments, res, k, drop),
                        [r.work for r in res], None,
                        deep_select_ran(segments, k, backend))
