"""Index construction: document-ordered (block-max) and impact-ordered
(quantized, JASS-style) layouts plus the Stage-0 per-term statistics table.

Mirrors the paper's setup: one corpus, two physical index layouts serving as
"index mirrors" on different ISN replicas — a BMW-style block-max index for
rank-safe DAAT and an ATIRE/JASS-style impact-ordered index for anytime SAAT.

The assembly core is shared between two producers:

* ``build_index`` — the sealed from-scratch build (stoplist derived from the
  corpus, collection statistics computed over the postings being indexed);
* the live **delta tile-set** (``index/delta.py``) — an append-only segment
  over freshly fed documents, scored with the *frozen* statistics of the
  sealed index (``CollectionStats``) so live results converge bit-exactly to
  the post-merge rebuild once the delta is folded in.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.index import scoring
from repro.index.corpus import Corpus
from repro.kernels.blocks import LANE_CHUNKS, mirror_tiles


@dataclass
class InvertedIndex:
    # collection stats
    n_docs: int
    vocab: int
    avg_dl: float
    total_tokens: float
    doclen: np.ndarray             # (N,)
    df: np.ndarray                 # (V,)
    cf: np.ndarray                 # (V,)

    # document-ordered CSR (sorted by term, doc)
    offsets: np.ndarray            # (V+1,)
    docs: np.ndarray               # (P,)
    tf: np.ndarray                 # (P,)
    bm25_score: np.ndarray         # (P,) float32 exact scores
    impact: np.ndarray             # (P,) uint8 quantized bm25
    quant_scale: float             # impact -> score scale (score≈imp/255*scale)

    # block-max structure (document-ordered): a term-major CSR over the
    # (term, block) pairs that hold postings, blocks ascending in a term
    block_size: int
    n_blocks: int
    bm_offsets: np.ndarray         # (V+1,) int64 into the block arrays
    bm_block: np.ndarray           # (E,) int32 doc-block id
    bm_max: np.ndarray             # (E,) uint8 largest impact in the block
    bm_count: np.ndarray           # (E,) int32 postings in the block

    # impact-ordered layout (per-term descending impact)
    docs_imp: np.ndarray           # (P,)
    imp_sorted: np.ndarray         # (P,) uint8
    level_cum: np.ndarray          # (V, 256) int32: #postings with impact >= l

    # stage-0 features
    term_stats: np.ndarray         # (V, 36) float32

    # term ids dropped at build time (the stop_k most frequent); retained so
    # a live delta segment applies the same stoplist to incoming feed docs
    stoplist: np.ndarray = None    # (S,) int64

    @property
    def n_postings(self) -> int:
        return self.docs.shape[0]


@dataclass(frozen=True)
class CollectionStats:
    """Collection-level quantities that price a posting.

    A live delta segment scores its postings with the *sealed* index's stats
    (frozen at seal time) rather than its own — otherwise per-posting scores
    would drift as the delta grows and live results could never match the
    post-merge rebuild posting-for-posting.
    """
    n_docs: int
    avg_dl: float
    total_tokens: float
    df: np.ndarray                 # (V,) float64
    cf: np.ndarray                 # (V,) float64
    quant_scale: float             # frozen impact quantization scale


def frozen_stats(index: InvertedIndex) -> CollectionStats:
    """Snapshot the scoring statistics of a sealed index."""
    return CollectionStats(
        n_docs=index.n_docs, avg_dl=index.avg_dl,
        total_tokens=index.total_tokens,
        df=np.asarray(index.df, np.float64),
        cf=np.asarray(index.cf, np.float64),
        quant_scale=index.quant_scale)


def _per_term_stats(term_ids, scores, offsets, df, vocab):
    """{max, amean, gmean, hmean, median, std} per term for one sim column."""
    eps = 1e-3
    nz = np.maximum(df.astype(np.float64), 1.0)
    shifted = scores - scores.min() + eps

    s1 = np.bincount(term_ids, weights=shifted, minlength=vocab)
    s2 = np.bincount(term_ids, weights=shifted ** 2, minlength=vocab)
    slog = np.bincount(term_ids, weights=np.log(shifted), minlength=vocab)
    sinv = np.bincount(term_ids, weights=1.0 / shifted, minlength=vocab)

    amean = s1 / nz
    gmean = np.exp(slog / nz)
    hmean = nz / np.maximum(sinv, 1e-12)
    std = np.sqrt(np.maximum(s2 / nz - amean ** 2, 0.0))

    # max + median from a per-term sort.  shifted > 0, so the bits of its
    # float32 rounding order like the value: one in-place sort of int64
    # (term, value) keys yields every term's ascending values.  Rounding to
    # float32 first is exact here — the order statistics commute with a
    # monotone rounding, and the table is float32.
    keys = term_ids.astype(np.int64)
    keys <<= 32
    keys |= shifted.astype(np.float32).view(np.int32)
    del shifted
    keys.sort()

    def value_at(i):
        return (keys[i] & 0xFFFFFFFF).astype(np.uint32).view(np.float32)

    has = df > 0
    last = np.maximum(offsets[1:] - 1, 0)
    mx = np.where(has, value_at(np.minimum(last, len(keys) - 1)), 0.0)
    mid = offsets[:-1] + np.maximum((df - 1) // 2, 0)
    med = np.where(has, value_at(np.minimum(mid, len(keys) - 1)), 0.0)

    cols = np.stack([mx, amean, gmean, hmean, med, std], axis=1)
    return np.where(has[:, None], cols, 0.0).astype(np.float32)


def pack_tiles(docs: np.ndarray, terms: np.ndarray,
               values: list[tuple[np.ndarray, float, np.dtype]],
               n_docs: int, tile_d: int,
               lane_multiple: int = 128,
               tile_cap: int | None = None):
    """Pre-tile postings into ``(n_tiles, cap)`` doc-local buckets.

    This is the build-time half of the serving kernels' one-doc-tile-per-
    grid-step layout: every posting lands in the bucket of its ``tile_d``-doc
    tile, doc ids are rebased to be tile-local, and each bucket is padded to
    a common lane-aligned ``cap`` so the whole structure is a dense
    ``(n_tiles, cap)`` array the kernels can view with zero per-query copies.
    ``n_tiles`` is rounded up to whole kernel tile groups with dead tiles
    (doc -1), so the kernels never pad the mirror at serve time.

    The one tiling helper shared by the sealed build, the append-only delta
    tile-set, and the merge re-tile.

    Args:
      docs: (P,) doc ids local to the shard.
      terms: (P,) term id of each posting.
      values: per-posting payload columns as (array, fill, dtype) tuples
        (e.g. exact scores, quantized impacts).
      n_docs: shard size (defines the tile count, ``mirror_tiles``).
      tile_d: docs per tile; must match the kernels' accumulator tile.
      lane_multiple: pad cap to a multiple of this (TPU lane width); a
        data-derived cap above the widest kernel lane chunk
        (``repro.kernels.blocks.LANE_CHUNKS``) pads to a multiple of that.
      tile_cap: pin the lane capacity to this static value instead of the
        data-derived one — the delta tile-set passes its postings capacity so
        every rebuild keeps a single jit signature as documents stream in.

    Returns:
      (tile_docs, tile_terms, bucketed_values, cap) where ``tile_docs`` is
      (n_tiles, cap) int32 tile-local doc ids with -1 padding, ``tile_terms``
      is (n_tiles, cap) int32 with -1 padding, and ``bucketed_values`` is a
      list of (n_tiles, cap) arrays in ``values`` order.
    """
    n_tiles = mirror_tiles(n_docs, tile_d)
    p = len(docs)
    tile = (docs // tile_d).astype(np.int64)
    counts = np.bincount(tile, minlength=n_tiles)
    cap = max(int(counts.max()) if p else 0, 1)
    cap = -(-cap // lane_multiple) * lane_multiple
    if tile_cap is not None:
        if tile_cap < cap:
            raise ValueError(f"tile_cap={tile_cap} below required cap={cap}")
        cap = tile_cap
    elif cap > LANE_CHUNKS[0]:
        # whole chunks: the kernels stream the lane axis in chunks of the
        # widest width that divides cap (repro.kernels.blocks.lane_chunk)
        cap = -(-cap // LANE_CHUNKS[0]) * LANE_CHUNKS[0]

    # stable keeps (term, doc) order in-tile; 16-bit keys take numpy's
    # radix sort
    key = tile.astype(np.int16) if n_tiles <= np.iinfo(np.int16).max else tile
    order = np.argsort(key, kind="stable")
    tsort = tile[order]
    starts = np.zeros(n_tiles + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    slot = tsort * cap + (np.arange(p, dtype=np.int64) - starts[tsort])

    tile_docs = np.full(n_tiles * cap, -1, np.int32)
    tile_docs[slot] = (docs[order] - tsort * tile_d).astype(np.int32)
    tile_terms = np.full(n_tiles * cap, -1, np.int32)
    tile_terms[slot] = terms[order].astype(np.int32)
    bucketed = []
    for arr, fill, dtype in values:
        b = np.full(n_tiles * cap, fill, dtype)
        b[slot] = arr[order].astype(dtype)
        bucketed.append(b.reshape(n_tiles, cap))
    return (tile_docs.reshape(n_tiles, cap), tile_terms.reshape(n_tiles, cap),
            bucketed, cap)


def impact_order_layout(term: np.ndarray, doc: np.ndarray,
                        impact: np.ndarray, vocab: int):
    """Impact-ordered mirror layout shared by the monolithic build and the
    per-shard slicer: the per-term impact-descending (doc-ascending within a
    level) permutation plus the (V, 256) cumulative level table
    ``level_cum[t, l] = # postings of t with impact >= l``."""
    # one int64 key (term, impact desc, doc) == the 3-key lexsort order
    key = ((term.astype(np.int64) << 40)
           | ((255 - impact.astype(np.int64)) << 32) | doc.astype(np.int64))
    order = np.argsort(key, kind="stable")
    lvl = np.bincount(term.astype(np.int64) * 256 + impact,
                      minlength=vocab * 256).reshape(vocab, 256)
    level_cum = np.flip(np.cumsum(np.flip(lvl, axis=1), axis=1),
                        axis=1).astype(np.int32)
    return order, level_cum


def block_csr(term: np.ndarray, doc: np.ndarray, block_size: int,
              n_terms: int):
    """Term-major CSR over the (term, doc-block) pairs that hold postings,
    for (term, doc)-sorted postings (so each pair's postings are
    contiguous): ``(offsets (V+1,) int64, block ids (E,) int32, postings per
    pair (E,) int32, first posting of each pair (E,))``; a per-posting
    value's block maxima are ``np.maximum.reduceat(value, first)``."""
    p = len(term)
    blk = (doc // block_size).astype(np.int64)
    first = np.flatnonzero(np.r_[p > 0, (term[1:] != term[:-1])
                                 | (blk[1:] != blk[:-1])])
    offsets = np.zeros(n_terms + 1, np.int64)
    np.cumsum(np.bincount(term[first], minlength=n_terms), out=offsets[1:])
    return (offsets, blk[first].astype(np.int32),
            np.diff(np.r_[first, p]).astype(np.int32), first)


def assemble_index(term: np.ndarray, doc: np.ndarray, tf: np.ndarray,
                   doclen: np.ndarray, vocab: int, *,
                   block_size: int = 64, n_levels: int = 255,
                   stoplist: np.ndarray | None = None,
                   frozen: CollectionStats | None = None) -> InvertedIndex:
    """Assemble every index mirror from prepared postings.

    ``term``/``doc``/``tf`` must already be stoplist-filtered and
    (term, doc)-sorted; ``tf`` float64. With ``frozen`` set, per-posting
    scores and impact quantization use those sealed collection statistics
    instead of the combined ones — the live-delta discipline. Structural
    quantities (df, offsets, layouts) always describe the postings given.
    """
    n, v = len(doclen), vocab
    p = len(term)

    df = np.bincount(term, minlength=v).astype(np.int64)
    cf = np.bincount(term, weights=tf, minlength=v)
    offsets = np.zeros(v + 1, np.int64)
    np.cumsum(df, out=offsets[1:])

    doclen_f = doclen.astype(np.float64)
    dl = doclen_f[doc]
    if frozen is None:
        score_n = n
        avg_dl = float(doclen_f.mean())
        total_tokens = float(doclen_f.sum())
        df_p = df[term].astype(np.float64)
        cf_p = cf[term]
        smax = None
    else:
        score_n = frozen.n_docs
        avg_dl = frozen.avg_dl
        total_tokens = frozen.total_tokens
        df_p = frozen.df[term]
        cf_p = frozen.cf[term]
        smax = frozen.quant_scale

    sims = scoring.all_similarity_scores(tf, df_p, cf_p, dl, score_n, avg_dl,
                                         total_tokens)  # (P, 6)
    del dl, df_p, cf_p
    bm25_sc = sims[:, 1].astype(np.float32)
    impact, qmax = scoring.quantize_impacts(bm25_sc, n_levels, smax=smax)

    # ---- block-max structure: CSR over the (term, block) pairs ----
    n_blocks = (n + block_size - 1) // block_size
    bm_offsets, bm_block, bm_count, start = block_csr(term, doc, block_size,
                                                      v)
    bm_max = (np.maximum.reduceat(impact, start) if p
              else np.zeros(0, np.uint8))
    del start

    # ---- impact-ordered layout ----
    order, level_cum = impact_order_layout(term, doc, impact, v)
    docs_imp = doc[order]
    imp_sorted = impact[order]

    # ---- stage-0 term statistics table ----
    if p:
        stats = [
            _per_term_stats(term, sims[:, s].astype(np.float64), offsets,
                            df, v)
            for s in range(sims.shape[1])
        ]
        # layout: (V, 6 sims * 6 stats), sim-major to match feature_names()
        term_stats = np.concatenate(stats, axis=1)
    else:
        term_stats = np.zeros((v, 36), np.float32)

    if stoplist is None:
        stoplist = np.zeros(0, np.int64)

    return InvertedIndex(
        n_docs=n, vocab=v, avg_dl=avg_dl, total_tokens=total_tokens,
        doclen=doclen, df=df.astype(np.int32), cf=cf.astype(np.float32),
        offsets=offsets, docs=doc, tf=tf.astype(np.int32),
        bm25_score=bm25_sc, impact=impact, quant_scale=qmax,
        block_size=block_size, n_blocks=n_blocks,
        bm_offsets=bm_offsets, bm_block=bm_block, bm_max=bm_max,
        bm_count=bm_count,
        docs_imp=docs_imp, imp_sorted=imp_sorted, level_cum=level_cum,
        term_stats=term_stats,
        stoplist=np.asarray(stoplist, np.int64),
    )


def build_index(corpus: Corpus, block_size: int = 64,
                n_levels: int = 255, stop_k: int = 64) -> InvertedIndex:
    term = corpus.postings_term
    doc = corpus.postings_doc
    tf = corpus.postings_tf.astype(np.float64)

    stoplist = np.zeros(0, np.int64)
    if stop_k > 0:
        # stop the collection (paper: Indri stoplist): drop the stop_k most
        # frequent terms from the index entirely
        cf_all = np.bincount(term, weights=tf, minlength=corpus.vocab)
        stoplist = np.argsort(-cf_all)[:stop_k]
        keep = ~np.isin(term, stoplist)
        term, doc, tf = term[keep], doc[keep], tf[keep]

    return assemble_index(term, doc, tf, corpus.doclen, corpus.vocab,
                          block_size=block_size, n_levels=n_levels,
                          stoplist=stoplist)
