"""Later-stage re-ranking (stages 1+ of the cascade).

The paper's effectiveness story is about how many candidates the first
stage must pass on; this module is the consumer: query-document features
(BM25 decomposition + topical affinity) and a GBRT point-wise LTR model
trained from reference-list labels — plus the cascade driver that chains
stage-0 prediction → candidate generation → re-ranking.

Two implementations of the feature extractor coexist:

* ``qd_features`` — the original per-query numpy loop (one CSR
  ``searchsorted`` per query term).  Kept as the parity oracle for
  ``rerank_loop``.
* ``qd_features_batched`` — the serving path: one array program over the
  whole ``(Q, C)`` candidate grid.  The per-term exact scores come from a
  branch-free CSR binary search over *all* query terms at once (``"jnp"``
  backend — the portable CPU fast path) or from the ``qd_feature_gather``
  Pallas kernel, which reads each query term's posting range block by
  block straight from the CSR (``"pallas"`` / ``"interpret"`` backends —
  the TPU path, same backend switch as the Stage-1 engines).
  Transcendentals are precomputed host-side into gather tables
  (``Stage2Arrays.log1p_doclen``), so on either backend the batched
  features match the numpy loop bit for bit.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gbrt
from repro.kernels.qd_feature_gather.ops import (csr_blocks,
                                                 qd_feature_gather,
                                                 step_budget)

N_LTR_FEATURES = 8


def qd_features(index, corpus, terms_row, mask_row, topic, doc_ids):
    """Per-(query, doc) LTR features for a candidate list."""
    t = terms_row[mask_row > 0]
    feats = np.zeros((len(doc_ids), N_LTR_FEATURES), np.float32)
    dl = index.doclen[doc_ids].astype(np.float32)
    feats[:, 0] = np.log1p(dl)
    # per-term exact scores via CSR binary search
    bm25 = np.zeros(len(doc_ids), np.float32)
    n_match = np.zeros(len(doc_ids), np.float32)
    mx = np.zeros(len(doc_ids), np.float32)
    for tt in t:
        lo, hi = index.offsets[tt], index.offsets[tt + 1]
        if hi <= lo:
            continue                      # term absent from this shard
        seg = index.docs[lo:hi]
        pos = np.searchsorted(seg, doc_ids)
        pos = np.minimum(pos, hi - lo - 1)
        hit = seg[pos] == doc_ids
        sc = np.where(hit, index.bm25_score[lo:hi][pos], 0.0)
        bm25 += sc
        mx = np.maximum(mx, sc)
        n_match += hit
    feats[:, 1] = bm25
    feats[:, 2] = mx
    feats[:, 3] = n_match / max(len(t), 1)
    feats[:, 4] = bm25 / np.maximum(dl, 1.0)
    feats[:, 5] = corpus.doc_topics[doc_ids, topic]
    feats[:, 6] = corpus.doc_topics[doc_ids].max(axis=1)
    feats[:, 7] = len(t)
    return feats


# ---------------------------------------------------------------------------
# batched (Q, C) candidate-grid featurization
# ---------------------------------------------------------------------------

class Stage2Arrays(NamedTuple):
    """Device-resident inputs of the batched Stage-2 featurizer."""
    offsets: jnp.ndarray       # (V+1,) int32 — doc-ordered CSR
    docs: jnp.ndarray          # (P,) int32, doc-sorted within each term
    score: jnp.ndarray         # (P,) float32 exact BM25
    blk_docs: jnp.ndarray      # (rows, 128) int32 — docs in kernel blocks
    blk_score: jnp.ndarray     # (rows, 128) float32 — score, same blocks
    doclen: jnp.ndarray        # (N,) float32
    log1p_doclen: jnp.ndarray  # (N,) float32 — np.log1p table (exactness)
    doc_topics: jnp.ndarray    # (N, K) float32
    doc_topics_max: jnp.ndarray  # (N,) float32 — row max, precomputed


def stage2_arrays(index, corpus) -> Stage2Arrays:
    """Materialize the Stage-2 gather tables from the index + corpus."""
    dl32 = index.doclen.astype(np.float32)
    blk_docs, blk_score = csr_blocks(index.docs, index.bm25_score)
    return Stage2Arrays(
        offsets=jnp.asarray(index.offsets, jnp.int32),
        docs=jnp.asarray(index.docs, jnp.int32),
        score=jnp.asarray(index.bm25_score, jnp.float32),
        blk_docs=blk_docs,
        blk_score=blk_score,
        doclen=jnp.asarray(dl32),
        log1p_doclen=jnp.asarray(np.log1p(dl32)),
        doc_topics=jnp.asarray(corpus.doc_topics, jnp.float32),
        doc_topics_max=jnp.asarray(corpus.doc_topics.max(axis=1)
                                   .astype(np.float32)),
    )


def csr_search_iters(max_df: int) -> int:
    """Bisection steps that exhaust a posting range of ``max_df`` entries."""
    return max(1, int(np.ceil(np.log2(max(max_df, 2)))) + 1)


def _csr_term_stats(offsets, docs, score, terms, tmask, cand, cmask,
                    n_iter: int):
    """(Σ score, max score, match count) per (query, candidate) via a
    branch-free CSR binary search over all query terms at once.

    Each of the ``n_iter`` unrolled steps halves every (q, l, c) search
    range with pure gathers — no Python loop over queries or terms.  The
    final per-term reduction is unrolled left-to-right over the (≤ L) term
    slots, matching the numpy loop's accumulation order bit-for-bit.
    """
    q, l_dim = terms.shape
    c_dim = cand.shape[1]
    p = docs.shape[0]
    lo = offsets[terms][:, :, None]                    # (Q, L, 1)
    hi = offsets[terms + 1][:, :, None]
    tgt = cand[:, None, :]                             # (Q, 1, C)
    lo_b = jnp.broadcast_to(lo, (q, l_dim, c_dim))
    hi_b = jnp.broadcast_to(hi, (q, l_dim, c_dim))
    for _ in range(n_iter):
        active = lo_b < hi_b
        mid = (lo_b + hi_b) // 2
        v = docs[jnp.minimum(mid, p - 1)]
        go_right = (v < tgt) & active
        lo_b = jnp.where(go_right, mid + 1, lo_b)
        hi_b = jnp.where(active & ~go_right, mid, hi_b)
    pos = jnp.minimum(lo_b, p - 1)
    hit = ((lo_b < hi) & (docs[pos] == tgt)
           & tmask[:, :, None] & cmask[:, None, :])
    sc = jnp.where(hit, score[pos], 0.0)               # (Q, L, C)
    # left-to-right over term slots: dead slots add an exact 0.0
    bm25, mx, nm = sc[:, 0], sc[:, 0], hit[:, 0].astype(jnp.float32)
    for l in range(1, l_dim):
        bm25 = bm25 + sc[:, l]
        mx = jnp.maximum(mx, sc[:, l])
        nm = nm + hit[:, l].astype(jnp.float32)
    return bm25, mx, nm


@functools.partial(jax.jit, static_argnames=("n_iter", "backend", "qcap"))
def qd_features_batched(arrs: Stage2Arrays, terms: jnp.ndarray,
                        mask: jnp.ndarray, topics: jnp.ndarray,
                        cand: jnp.ndarray, *, n_iter: int,
                        backend: str = "jnp",
                        qcap: int | None = None) -> jnp.ndarray:
    """LTR features for the whole (Q, C) candidate grid in one call.

    Args:
      arrs: ``stage2_arrays`` gather tables.
      terms/mask: (Q, L) padded query terms.
      topics: (Q,) query topic ids.
      cand: (Q, C) candidate doc ids, -1 padding (padded rows yield garbage
        features — mask downstream, as ``rerank_batched`` does).
      n_iter: static bisection depth (``csr_search_iters(max_df)``).
      backend: "jnp" (CSR binary search) or "interpret"/"pallas"
        (``qd_feature_gather`` kernel over the CSR's posting blocks); both
        are bit-identical to the numpy loop.
      qcap: kernel backends only — a static bound on the batch's per-query
        posting total (``query_lane_budget``); it fixes the kernel's
        ``step_budget``, so the program compiles per (width, ``qcap``).
    Returns:
      (Q, C, 8) float32 feature grid.
    """
    tmask = mask > 0
    cmask = cand >= 0
    c_safe = jnp.maximum(cand, 0)
    if backend == "jnp":
        bm25, mx, nm = _csr_term_stats(arrs.offsets, arrs.docs, arrs.score,
                                       terms, tmask, cand, cmask, n_iter)
    else:
        if qcap is None:
            raise ValueError("kernel backends need a static qcap lane budget")
        lo = arrs.offsets[terms]
        hi = jnp.where(tmask, arrs.offsets[terms + 1], lo)
        bm25, mx, cnt = qd_feature_gather(
            arrs.blk_docs, arrs.blk_score, lo, hi, cand,
            n_steps=step_budget(qcap, terms.shape[1]),
            interpret=backend == "interpret")
        nm = cnt.astype(jnp.float32)
    dl = arrs.doclen[c_safe]                           # (Q, C)
    n_terms = jnp.sum(tmask.astype(jnp.float32), axis=1)
    feats = jnp.stack([
        arrs.log1p_doclen[c_safe],
        bm25,
        mx,
        nm / jnp.maximum(n_terms, 1.0)[:, None],
        bm25 / jnp.maximum(dl, 1.0),
        arrs.doc_topics[c_safe, topics[:, None]],
        arrs.doc_topics_max[c_safe],
        jnp.broadcast_to(n_terms[:, None], c_safe.shape),
    ], axis=-1)
    return feats.astype(jnp.float32)


@dataclass
class LTRModel:
    model: object

    def score(self, feats: np.ndarray) -> np.ndarray:
        return np.asarray(gbrt.predict(self.model, feats))


def train_ltr(feats: np.ndarray, gains: np.ndarray,
              n_trees: int = 48) -> LTRModel:
    m = gbrt.fit(feats, gains.astype(np.float32),
                 gbrt.GBRTParams(n_trees=n_trees, depth=4, loss="l2",
                                 learning_rate=0.2))
    return LTRModel(m)


def ltr_training_set(index, corpus, ql, ref_lists, rows,
                     n_pos: int = 24, n_neg: int = 24, seed: int = 0):
    """(features, gains) pairs from reference lists: graded gains for the
    top reference docs, zero for random negatives."""
    rng = np.random.RandomState(seed)
    feats, gains = [], []
    for q in rows:
        pos = ref_lists[q][:n_pos]
        neg = rng.randint(0, index.n_docs, n_neg)
        docs = np.concatenate([pos, neg]).astype(np.int64)
        g = np.concatenate([1.0 / np.log2(np.arange(len(pos)) + 2),
                            np.zeros(len(neg))])
        feats.append(qd_features(index, corpus, ql.terms[q], ql.mask[q],
                                 ql.topic[q], docs))
        gains.append(g)
    return np.concatenate(feats), np.concatenate(gains).astype(np.float32)
