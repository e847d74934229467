"""The dense Stage-1 engine: sharded query×doc similarity top-k.

A first-class second modality next to the lexical DAAT/SAAT engines, built
to slot into the existing deployment shape unchanged:

* the embedding matrix is partitioned by the **same contiguous doc ranges**
  as the inverted index (``shard_ranges``), per-shard results carry global
  doc ids, and the multi-shard merge is the existing ``merge_shard_topk``
  — its (score, global doc id) sort keeps the lower-global-doc-id
  tie-break, and ``drop`` masks (fault loss / partial coverage)
  degrade a dense query exactly like a lexical one;
* per-shard cost is **shape-static** — every query scores every doc tile,
  so ``CostModel.dense_time(n_tiles)`` is exact from the spec alone, which
  is what makes the dense route's contribution to ``worst_case_us``
  analytic (no df tables, no per-query work counters).

``serve`` is bit-identical to the numpy brute-force oracle on every
backend thanks to grid-quantized embeddings (``repro.dense.embeddings``).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.dense.embeddings import embed_queries
from repro.isn.backend import merge_shard_topk
from repro.kernels.dense_topk.ops import dense_topk
from repro.kernels.dense_topk.ref import dense_topk_oracle
from repro.serving.telemetry.spans import DISPATCH, fetch, span

SCORE_FILL = float(np.finfo(np.float32).min)


class DenseEngine:
    """Doc-range-sharded dense retrieval over a quantized embedding matrix.

    Args:
      doc_emb: (n_docs, d) float32 grid-quantized doc embeddings.
      term_table: (vocab, d) float32 grid-quantized per-term vectors
        (queries embed as the quantized mean of their active terms).
      ranges: the deployment's ``shard_ranges`` output — the SAME doc-range
        partitioning the lexical shards use.
      tile_d: docs per kernel grid tile (lane-width multiple).
      backend: ``pallas | interpret | jnp`` kernel switch.
    """

    def __init__(self, doc_emb: np.ndarray, term_table: np.ndarray,
                 ranges, *, tile_d: int = 512, backend: str | None = None):
        self.doc_emb = np.asarray(doc_emb, np.float32)
        self.term_table = np.asarray(term_table, np.float32)
        self.tile_d = int(tile_d)
        self.backend = backend if backend is not None else "jnp"
        self.d = self.doc_emb.shape[1]
        self.doc_lo = [lo for lo, _ in ranges]
        self.shard_emb = [jnp.asarray(self.doc_emb[lo:hi])
                          for lo, hi in ranges]
        self.shard_docs = [hi - lo for lo, hi in ranges]
        # live delta segment (capacity-padded, appended above the ranges)
        self.delta_emb = None
        self.delta_live = 0
        self.delta_lo = 0

    @property
    def n_shards(self) -> int:
        return len(self.shard_emb)

    def n_tiles(self, s: int) -> int:
        """Kernel grid tiles of shard ``s`` — the shape-static work unit."""
        return -(-self.shard_docs[s] // self.tile_d)

    def max_tiles(self) -> int:
        """Largest per-shard tile count: the scatter-gather bound's term."""
        return max(self.n_tiles(s) for s in range(self.n_shards))

    def embed(self, terms: np.ndarray, mask: np.ndarray) -> np.ndarray:
        """(Q, d) quantized query embeddings (row-independent)."""
        return embed_queries(self.term_table, terms, mask)

    def set_delta(self, emb: np.ndarray, n_live: int, doc_lo: int) -> None:
        """Attach/refresh the live delta segment.

        ``emb`` is the capacity-padded (cap, d) quantized matrix (rows
        >= ``n_live`` are ghosts), ``doc_lo`` the global id of delta doc 0.
        The shape is the fixed delta capacity so the kernel signature never
        changes as documents stream in.
        """
        self.delta_emb = jnp.asarray(np.asarray(emb, np.float32))
        self.delta_live = int(n_live)
        self.delta_lo = int(doc_lo)

    def clear_delta(self) -> None:
        self.delta_emb = None
        self.delta_live = 0
        self.delta_lo = 0

    def delta_tiles(self) -> int:
        """Kernel grid tiles the delta scan adds to every query's cost."""
        if self.delta_emb is None:
            return 0
        return -(-int(self.delta_emb.shape[0]) // self.tile_d)

    def serve(self, q_emb: np.ndarray, k: int, drop=None):
        """Scatter-gather dense top-k: (ids, scores), each (Q, k).

        Ids are global; ``drop`` ((n_shards, Q) bool) excludes lost /
        never-requested shard responses exactly like the lexical merge
        (surviving-shard merge, ``-1`` padding).  Requires
        ``k <= min(shard docs)`` — the deployment invariant ``SearchSystem``
        already enforces for the lexical grid.
        """
        sc_list, id_list = [], []
        with span(DISPATCH):
            for s in range(self.n_shards):
                sc, ids = dense_topk(jnp.asarray(q_emb), self.shard_emb[s],
                                     k, tile_d=self.tile_d,
                                     backend=self.backend)
                sc_list.append(sc)
                id_list.append(ids + self.doc_lo[s])
        if self.n_shards == 1 and self.delta_emb is None:
            ids, sc = fetch(id_list[0], sc_list[0])
            ids = ids.astype(np.int64)
            if drop is not None and drop[0].any():
                ids[drop[0]] = -1
                sc[drop[0]] = SCORE_FILL
            return ids, sc
        if self.delta_emb is not None:
            # Rank the WHOLE delta segment (its capacity is small and
            # static), then mask ghost rows explicitly: a ghost's zero
            # vector scores 0, which would outrank genuinely negative live
            # scores, and requesting only k could let ghosts displace live
            # docs from the candidate list. A full ranking plus post-mask
            # makes padding provably inert.
            cap = int(self.delta_emb.shape[0])
            with span(DISPATCH):
                dsc, dids = dense_topk(jnp.asarray(q_emb), self.delta_emb,
                                       cap, tile_d=self.tile_d,
                                       backend=self.backend)
            dsc, dids = fetch(dsc, dids)
            dsc = dsc.copy()
            ghost = dids >= self.delta_live
            dsc[ghost] = SCORE_FILL
            dids = np.where(ghost, -1, dids + self.delta_lo)
            sc_list.append(dsc)
            id_list.append(dids)
            if drop is not None:
                drop = np.concatenate(
                    [np.asarray(drop),
                     np.zeros((1, np.asarray(drop).shape[1]), bool)])
        with span(DISPATCH):
            merged = merge_shard_topk(sc_list, id_list, k, drop=drop)
        ids, sc = fetch(*merged)
        return ids.astype(np.int64), sc

    def oracle(self, q_emb: np.ndarray, k: int):
        """Brute-force ground truth over the unsharded matrix: (ids,
        scores) — what ``serve`` must match bit for bit."""
        sc, ids = dense_topk_oracle(q_emb, self.doc_emb, k)
        return ids, sc
