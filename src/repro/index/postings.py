"""Device-resident index shard structures for the JAX/TPU serving engines.

An ISN holds one *document shard* of the corpus in HBM, in three mirrors:

* impact-ordered arrays for SAAT (JASS) — per-term postings sorted by
  descending quantized impact, plus per-term per-level cumulative counts so
  the ρ budget resolves to per-term prefixes in O(levels);
* document-ordered arrays for DAAT (BMW) — per-term postings sorted by
  docid with exact scores, plus a *sparse* per-term block-max structure
  (term-major CSR of (block_id, block_max, block_count), padded to whole
  1,024-entry tiles that the block-bounds kernel reads in place) — dense
  (V × n_blocks) does not scale to 2M-term vocabularies;
* a **bucketed (doc-tile-major) mirror** feeding the batched Pallas serving
  kernels — every posting pre-tiled at index-build time into the
  ``(n_tiles, tile_cap)`` bucket of its ``tile_d``-doc tile, carrying
  (tile-local doc id, term id, exact score, quantized impact).  The kernels'
  one-doc-tile-per-grid-step layout is then a zero-copy view of the shard:
  one grid step loads one bucket row, matches terms against the query
  in-register, and reduces with a one-hot MXU matmul.  Pruned tiles are
  skipped via predication, so per-query HBM traffic is proportional to the
  *surviving* tiles rather than the collection size.

All fields are plain jnp arrays so a shard can be a pytree leaf under
``shard_map`` and a ShapeDtypeStruct bundle for the compile-only dry-run.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.index.builder import (InvertedIndex, block_csr,
                                 impact_order_layout, pack_tiles)
from repro.kernels.blocks import CSR_BLOCK, round_up


class IndexShardSpec(NamedTuple):
    n_docs: int            # docs in this shard
    vocab: int
    n_postings: int        # padded postings count
    n_blocks: int          # doc blocks in this shard
    n_block_entries: int   # (term, block) entries, whole 1,024-entry tiles
    n_levels: int
    block_size: int
    max_df: int            # static cap for per-term gathers
    max_blocks_per_term: int
    quant_scale: float
    tile_d: int            # docs per bucketed serving tile
    tile_cap: int          # lane-padded postings capacity per tile
    n_tiles: int           # bucketed mirror rows (whole tile groups)


class IndexShard(NamedTuple):
    """One document shard of the index mirrors (pytree of jnp arrays)."""
    # --- shared / collection stats ---
    df: jnp.ndarray            # (V,) int32
    offsets: jnp.ndarray       # (V+1,) int32 into postings arrays

    # --- impact-ordered mirror (SAAT / JASS) ---
    docs_imp: jnp.ndarray      # (P,) int32 local doc ids
    imp: jnp.ndarray           # (P,) int32 quantized impacts (from uint8)
    level_cum: jnp.ndarray     # (V, n_levels) int32: count with impact >= l

    # --- document-ordered mirror (DAAT / BMW) ---
    docs: jnp.ndarray          # (P,) int32 local doc ids (term, doc sorted)
    score: jnp.ndarray         # (P,) float32 exact BM25
    bm_offsets: jnp.ndarray    # (V+1,) int32 into block arrays
    bm_block_id: jnp.ndarray   # (PB,) int32 doc-block id
    bm_block_max: jnp.ndarray  # (PB,) float32 block upper bound (scaled)
    bm_block_cnt: jnp.ndarray  # (PB,) int32 postings in this (term, block)

    # --- bucketed doc-tile-major mirror (batched serving kernels) ---
    tile_docs: jnp.ndarray     # (n_tiles, tile_cap) int32 tile-local, -1 pad
    tile_terms: jnp.ndarray    # (n_tiles, tile_cap) int32 term ids, -1 pad
    tile_scores: jnp.ndarray   # (n_tiles, tile_cap) float32 exact BM25
    tile_imps: jnp.ndarray     # (n_tiles, tile_cap) int32 quantized impacts


def shard_ranges(n_docs: int, n_shards: int) -> list[tuple[int, int]]:
    """Contiguous doc-range partition of [0, n_docs) into n_shards shards.

    Ranges are as even as possible (first ``n_docs % n_shards`` shards get
    one extra doc) and returned in ascending order — the order the
    scatter-gather merge relies on for its doc-id tie-break.
    """
    if not 1 <= n_shards <= n_docs:
        raise ValueError(f"n_shards must be in [1, {n_docs}], got {n_shards}")
    base, extra = divmod(n_docs, n_shards)
    bounds = [0]
    for s in range(n_shards):
        bounds.append(bounds[-1] + base + (1 if s < extra else 0))
    return list(zip(bounds[:-1], bounds[1:]))


def _pad_to(arr: np.ndarray, size: int, fill) -> np.ndarray:
    """Right-pad a 1-D postings column to a static capacity.

    Pads are inert by construction: every serving gather is offsets/df
    addressed (compact lanes mask ``lane < df``), so a padded tail is never
    combined into a score.
    """
    if size < len(arr):
        raise ValueError(f"pad size {size} below array length {len(arr)}")
    out = np.full(size, fill, arr.dtype)
    out[:len(arr)] = arr
    return out


def shard_from_index(index: InvertedIndex, doc_lo: int = 0,
                     doc_hi: int | None = None,
                     tile_d: int = 128, *,
                     tile_cap: int | None = None,
                     pad_postings: int | None = None,
                     max_df: int | None = None,
                     max_blocks_per_term: int | None = None,
                     ) -> tuple[IndexShard, IndexShardSpec]:
    """Materialize the device structures for docs in [doc_lo, doc_hi).

    The keyword overrides pin *capacity* shapes and static caps instead of
    the data-derived ones, so a delta tile-set rebuilt on every ingest batch
    keeps one jit signature while it fills: ``pad_postings`` pads every
    postings column (and the sparse block-max CSR) to that length,
    ``tile_cap`` pins the bucketed mirror's lane capacity, and
    ``max_df``/``max_blocks_per_term`` pin the per-term gather caps.
    """
    doc_hi = index.n_docs if doc_hi is None else doc_hi
    n_local = doc_hi - doc_lo
    v = index.vocab
    bs = index.block_size
    if tile_d % bs:
        raise ValueError(f"tile_d={tile_d} must be a multiple of "
                         f"block_size={bs}")

    sel = (index.docs >= doc_lo) & (index.docs < doc_hi)
    term_of = np.repeat(np.arange(v), np.diff(index.offsets))
    t = term_of[sel]
    d = (index.docs[sel] - doc_lo).astype(np.int32)
    s = index.bm25_score[sel].astype(np.float32)
    im = index.impact[sel].astype(np.int32)

    df = np.bincount(t, minlength=v).astype(np.int32)
    offsets = np.zeros(v + 1, np.int64)
    np.cumsum(df, out=offsets[1:])

    # postings already (term, doc)-sorted; within-shard selection keeps order
    docs = d
    score = s

    # impact-ordered: per-term sort by impact desc (a shard covering the
    # whole index has exactly the index's own layout)
    if n_local == index.n_docs:
        docs_imp = index.docs_imp.astype(np.int32)
        imp = index.imp_sorted.astype(np.int32)
        level_cum = index.level_cum
    else:
        order, level_cum = impact_order_layout(t, d, im, v)
        docs_imp = d[order]
        imp = im[order]

    # sparse block-max: a shard over the whole index takes the index's own
    # CSR, a doc-range shard groups its postings; the block maxima are the
    # exact float scores either way
    if n_local == index.n_docs:
        bm_offsets, b_id, b_cnt = index.bm_offsets, index.bm_block, \
            index.bm_count
        start = np.r_[0, np.cumsum(b_cnt)[:-1]].astype(np.int64)
    else:
        bm_offsets, b_id, b_cnt, start = block_csr(t, d, bs, v)
    b_max = (np.maximum.reduceat(s, start).astype(np.float32) if len(d)
             else np.zeros(0, np.float32))
    bm_df = np.diff(bm_offsets)

    if pad_postings is not None:
        docs = _pad_to(docs, pad_postings, 0)
        score = _pad_to(score, pad_postings, 0.0)
        docs_imp = _pad_to(docs_imp, pad_postings, 0)
        imp = _pad_to(imp, pad_postings, 0)
        b_id = _pad_to(b_id, pad_postings, 0)
        b_max = _pad_to(b_max, pad_postings, 0.0)
        b_cnt = _pad_to(b_cnt, pad_postings, 0)
    # whole 1,024-entry tiles: the BMW block-bounds kernel views the CSR's
    # columns as (rows, 128) tables in place
    n_bm = round_up(max(len(b_id), 1), CSR_BLOCK)
    b_id = _pad_to(b_id, n_bm, 0)
    b_max = _pad_to(b_max, n_bm, 0.0)
    b_cnt = _pad_to(b_cnt, n_bm, 0)

    # bucketed doc-tile-major mirror for the batched serving kernels
    tile_docs, tile_terms, (tile_scores, tile_imps), tcap = \
        pack_tiles(
            d, t, [(s, 0.0, np.float32), (im, 0, np.int32)], n_local, tile_d,
            tile_cap=tile_cap)

    n_blocks = (n_local + bs - 1) // bs
    spec = IndexShardSpec(
        n_docs=n_local, vocab=v, n_postings=len(docs), n_blocks=n_blocks,
        n_block_entries=len(b_id), n_levels=256, block_size=bs,
        max_df=(max_df if max_df is not None
                else int(df.max()) if len(df) else 1),
        max_blocks_per_term=(max_blocks_per_term
                             if max_blocks_per_term is not None
                             else int(bm_df.max()) if len(bm_df) else 1),
        quant_scale=index.quant_scale,
        tile_d=tile_d, tile_cap=tcap, n_tiles=tile_docs.shape[0])

    shard = IndexShard(
        df=jnp.asarray(df),
        offsets=jnp.asarray(offsets, jnp.int32),
        docs_imp=jnp.asarray(docs_imp),
        imp=jnp.asarray(imp, jnp.int32),
        level_cum=jnp.asarray(level_cum, jnp.int32),
        docs=jnp.asarray(docs),
        score=jnp.asarray(score),
        bm_offsets=jnp.asarray(bm_offsets, jnp.int32),
        bm_block_id=jnp.asarray(b_id),
        bm_block_max=jnp.asarray(b_max),
        bm_block_cnt=jnp.asarray(b_cnt),
        tile_docs=jnp.asarray(tile_docs),
        tile_terms=jnp.asarray(tile_terms),
        tile_scores=jnp.asarray(tile_scores),
        tile_imps=jnp.asarray(tile_imps),
    )
    return shard, spec


def shard_specs(spec: IndexShardSpec) -> IndexShard:
    """ShapeDtypeStruct stand-ins with the same pytree structure — used by the
    multi-pod dry-run so no index is ever materialized."""
    sds = jax.ShapeDtypeStruct
    v, p, pb = spec.vocab, spec.n_postings, spec.n_block_entries
    nt, tc = spec.n_tiles, spec.tile_cap
    return IndexShard(
        df=sds((v,), jnp.int32),
        offsets=sds((v + 1,), jnp.int32),
        docs_imp=sds((p,), jnp.int32),
        imp=sds((p,), jnp.int32),
        level_cum=sds((v, spec.n_levels), jnp.int32),
        docs=sds((p,), jnp.int32),
        score=sds((p,), jnp.float32),
        bm_offsets=sds((v + 1,), jnp.int32),
        bm_block_id=sds((pb,), jnp.int32),
        bm_block_max=sds((pb,), jnp.float32),
        bm_block_cnt=sds((pb,), jnp.int32),
        tile_docs=sds((nt, tc), jnp.int32),
        tile_terms=sds((nt, tc), jnp.int32),
        tile_scores=sds((nt, tc), jnp.float32),
        tile_imps=sds((nt, tc), jnp.int32),
    )
