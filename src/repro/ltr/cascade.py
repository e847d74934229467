"""Multi-stage cascade driver: Stage-0 predict → Stage-1 candidates (hybrid
ISN) → Stage-2 LTR re-rank → final top-t.

``rerank_batched`` is the serving path: array programs over the whole
(Q, C) candidate grid — batched featurization (``qd_features_batched``),
one GBRT inference over all (query, candidate) rows (gather-free: every
split bin of every row from one one-hot contraction, then selects by node
index, see ``core.trees.forest_predict_binned``), and a masked ``top_k``
selection whose tie-breaking (lower candidate rank first) matches the
stable argsort of the loop.  ``rerank_loop`` keeps the original
one-query-at-a-time driver as the parity oracle; on the ``"jnp"`` backend
the batched path reproduces it bit-for-bit
(``tests/test_cascade_pipeline.py``, ``benchmarks/bench_hybrid.py``).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gbrt
from repro.ltr.ranker import (LTRModel, Stage2Arrays, csr_search_iters,
                              qd_features, qd_features_batched)
from repro.serving.telemetry.spans import fetch


@dataclass
class CascadeResult:
    final: np.ndarray           # (Q, t) doc ids
    candidates_used: np.ndarray # (Q,) candidate count entering stage 2


def rerank_loop(index, corpus, ql, rows, candidate_lists, k_per_query,
                ltr: LTRModel, t_final: int = 10) -> CascadeResult:
    """One-query-at-a-time cascade (per-term CSR searchsorted + one GBRT
    dispatch per query) — the parity oracle and benchmark baseline for
    ``rerank_batched``."""
    out = np.zeros((len(rows), t_final), np.int64)
    used = np.zeros(len(rows), np.int64)
    for i, q in enumerate(rows):
        k = int(k_per_query[i])
        cand = candidate_lists[i][:k]
        cand = cand[cand >= 0]
        used[i] = len(cand)
        if len(cand) == 0:
            continue
        f = qd_features(index, corpus, ql.terms[q], ql.mask[q],
                        ql.topic[q], cand)
        sc = ltr.score(f)
        order = np.argsort(-sc, kind="stable")[:t_final]
        picks = cand[order]
        out[i, :len(picks)] = picks
        if len(picks) < t_final:
            out[i, len(picks):] = -1
    return CascadeResult(final=out, candidates_used=used)


def rerank_batched(arrs: Stage2Arrays, ltr: LTRModel, terms, mask, topics,
                   cand, k_per_query, *, t_final: int = 10, n_iter: int,
                   backend: str = "jnp", qcap: int | None = None,
                   lane_need: int | None = None) -> CascadeResult:
    """Batched Stage-2: re-rank every query's candidate grid in one array
    program.

    Args:
      arrs: ``stage2_arrays`` gather tables.
      terms/mask/topics: the (Q, L)/(Q,) query batch.
      cand: (Q, C) candidate doc ids (-1 padding), e.g. the Stage-1 top-k.
      k_per_query: (Q,) per-query candidate budgets (the Stage-0 P_k
        prediction, clamped); only the first k columns of each row enter
        the re-ranker.
      lane_need: kernel backends only — the batch's max per-query posting
        total, if the caller already knows it (a ``query_lane_budget``
        result qualifies: it bounds the total by construction).  When
        omitted it is re-derived from ``arrs.offsets``, which costs a
        device-to-host copy of the offsets table per call.
      n_iter / backend / qcap: see ``qd_features_batched``.
    """
    q, c = np.shape(cand)
    if backend != "jnp":
        # the kernel's step budget covers qcap postings a query and drops
        # blocks past it — refuse rather than return wrong features (size
        # qcap with query_lane_budget)
        if lane_need is None:
            off = np.asarray(arrs.offsets)
            t_np = np.asarray(terms)
            df = off[t_np + 1] - off[t_np]
            lane_need = int((df * (np.asarray(mask) > 0)).sum(axis=1).max())
        if qcap is None or qcap < lane_need:
            raise ValueError(
                f"qcap={qcap} does not cover the batch's per-query posting "
                f"total ({lane_need}); size it with "
                f"repro.isn.backend.query_lane_budget")
    terms = jnp.asarray(terms)
    mask = jnp.asarray(mask)
    cand_j = jnp.asarray(cand, jnp.int32)
    feats = qd_features_batched(arrs, terms, mask,
                                jnp.asarray(topics, jnp.int32), cand_j,
                                n_iter=n_iter, backend=backend, qcap=qcap)
    sc = gbrt.predict(ltr.model, feats.reshape(q * c, -1)).reshape(q, c)
    valid = (cand_j >= 0) & (jnp.arange(c, dtype=jnp.int32)[None, :]
                             < jnp.asarray(k_per_query, jnp.int32)[:, None])
    sc = jnp.where(valid, sc, -jnp.inf)
    kk = min(t_final, c)
    top_sc, order = jax.lax.top_k(sc, kk)
    picks = jnp.take_along_axis(cand_j, order, axis=1)
    picks = jnp.where(jnp.isfinite(top_sc), picks, -1)
    used = jnp.sum(valid, axis=1)
    final = jnp.where(used[:, None] > 0, picks, 0)
    if kk < t_final:
        final = jnp.pad(final, ((0, 0), (0, t_final - kk)),
                        constant_values=-1)
        final = jnp.where(used[:, None] > 0, final, 0)
    final, used = fetch(final, used)
    return CascadeResult(final=final.astype(np.int64),
                         candidates_used=used.astype(np.int64))
