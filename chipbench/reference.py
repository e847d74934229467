"""Plain reference of the served cascade, in numpy, from the raw collection.

It imports nothing of the program and reads nothing the program made: it
rebuilds the stoplist, document frequencies and BM25 (k1 0.9, b 0.4, scores
stored in float32 as the configuration states) from the generated postings,
quantises impacts the ATIRE way (``ceil(score / max * 255)``), and evaluates
the seed-made forests with its own tree walk.  For a query it gives:

* the Stage-0 route and postings budget (Algorithm 2 over the three
  predictors, k and ρ clamped as the scheduler does);
* the JASS/SAAT list: every posting whose impact reaches the most inclusive
  impact level whose postings fit the budget, integer-summed, top k with
  ties to the lower doc id;
* the exhaustive BM25 scores the rank-safe BMW/DAAT list must top;
* the Stage-2 forest score of each candidate.

``precision="bfloat16"`` gives the control: per-posting scores, impacts and
forest inputs rounded to bfloat16, the step below the float32 the
configuration states.
"""

from __future__ import annotations

import numpy as np

N_LEVELS = 255
K1, B = 0.9, 0.4
# Stage-0 feature columns the seed-made forests split on (the program's
# 147-feature layout: query length, log1p of summed and of least df)
F_N_TERMS, F_LOG_SUM_DF, F_LOG_MIN_DF = 144, 145, 146
N_STAGE0_FEATURES = 147
STAGE0_SPLIT_FEATURES = (F_N_TERMS, F_LOG_SUM_DF, F_LOG_MIN_DF)
# Stage-2 feature columns: log1p doc length, max per-term BM25, topic
# affinity, the doc's strongest topic, query length
LTR_SPLIT_FEATURES = (0, 2, 5, 6, 7)
N_LTR_FEATURES = 8
FAR = 1.0e6                       # edges no feature value reaches
ULP_GUARD = 8                     # float32 ulps a log1p may differ by


def _round(x: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float32":
        return x.astype(np.float32)
    import ml_dtypes
    return x.astype(np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def topk_ties(acc: np.ndarray, k: int) -> np.ndarray:
    """Indices of the k largest entries, ties to the lower index."""
    n = len(acc)
    k = min(k, n)
    kth = np.partition(acc, n - k)[n - k]
    cand = np.flatnonzero(acc >= kth)
    return cand[np.lexsort((cand, -acc[cand]))][:k]


def descend(feat: np.ndarray, thresh: np.ndarray, xb: np.ndarray):
    """Leaf index of each row of binned features in one complete tree
    (``feat``/``thresh`` are (depth, 2**(depth-1))); right when bin >
    threshold."""
    node = np.zeros(len(xb), np.int64)
    rows = np.arange(len(xb))
    for d in range(feat.shape[0]):
        f = feat[d, node]
        node = node * 2 + (xb[rows, f] > thresh[d, node])
    return node


def forest_score(parts, xb: np.ndarray) -> np.ndarray:
    """Boosted sum in float64: base + Σ leaves."""
    feat, thresh, leaf, base = parts
    out = np.full(len(xb), float(base))
    for t in range(feat.shape[0]):
        out += leaf[t][descend(feat[t], thresh[t], xb)].astype(np.float64)
    return out


def _snap_edges(values: np.ndarray, n_edges: int, snap) -> tuple:
    """Quantile edges through ``snap``, deduplicated, padded with
    unreachable edges; returns (edges float32, count of real edges)."""
    qs = np.percentile(values, np.linspace(0, 100, n_edges + 2)[1:-1])
    real = np.unique(snap(qs).astype(np.float32))[:n_edges]
    pad = FAR + np.arange(n_edges - len(real), dtype=np.float32)
    return np.concatenate([real, pad]).astype(np.float32), len(real)


def _log_edge(q):
    """log1p(m + 1/2) for integer m: a log1p of an integer count sits
    between two such edges."""
    return np.log1p(np.floor(np.maximum(q, 0.0)) + 0.5)


def _half_edge(q):
    return np.floor(q) + 0.5


def bins_exact(x: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """The program's binning (count of edges strictly below) in float32."""
    return np.sum(x.astype(np.float32)[:, :, None] > edges[None], axis=-1)


def ltr_edges(f: np.ndarray, n_bins: int):
    edges = np.empty((N_LTR_FEATURES, n_bins - 1), np.float32)
    useful = np.zeros(N_LTR_FEATURES, np.int64)
    for j in range(N_LTR_FEATURES):
        snap = _half_edge if j == 7 else (lambda q: q)
        edges[j], useful[j] = _snap_edges(f[:, j], n_bins - 1, snap)
    return edges, useful


class Reference:
    """Collection statistics and BM25 over the raw generated postings."""

    def __init__(self, corpus, stop_k: int, precision: str = "float32"):
        self.precision = precision
        term = corpus.postings_term
        tf = corpus.postings_tf.astype(np.float64)
        v = corpus.params.vocab
        self.vocab = v
        self.n_docs = len(corpus.doclen)
        cf_all = np.bincount(term, weights=tf, minlength=v)
        self.stoplist = np.argsort(-cf_all)[:stop_k]
        keep = ~np.isin(term, self.stoplist)
        self.term = term[keep]
        self.doc = corpus.postings_doc[keep]
        self.tf = tf[keep]
        self.df = np.bincount(self.term, minlength=v).astype(np.int64)
        self.offsets = np.zeros(v + 1, np.int64)
        np.cumsum(self.df, out=self.offsets[1:])
        self.doclen = corpus.doclen
        self.doclen_f = corpus.doclen.astype(np.float64)
        self.avg_dl = float(self.doclen_f.mean())
        self.doc_topics = corpus.doc_topics
        self.doc_topics_max = corpus.doc_topics.max(axis=1).astype(np.float32)
        self._smax = None

    # ---- BM25 and impacts -------------------------------------------------

    def _bm25(self, lo: int, hi: int) -> np.ndarray:
        tf = self.tf[lo:hi]
        df = self.df[self.term[lo:hi]].astype(np.float64)
        dl = self.doclen_f[self.doc[lo:hi]]
        idf = np.log(1.0 + (self.n_docs - df + 0.5) / (df + 0.5))
        norm = tf + K1 * (1.0 - B + B * dl / self.avg_dl)
        return idf * tf * (K1 + 1.0) / norm

    def scores(self, t: int) -> np.ndarray:
        """Stored per-posting scores of term t in the reference precision."""
        return _round(self._bm25(self.offsets[t], self.offsets[t + 1]),
                      self.precision)

    def smax(self) -> float:
        """Largest stored score in the collection (the impact scale)."""
        if self._smax is None:
            step = 1 << 22
            self._smax = float(max(
                _round(self._bm25(lo, min(lo + step, len(self.tf))),
                       self.precision).max()
                for lo in range(0, len(self.tf), step)))
        return self._smax

    def impacts(self, t: int) -> np.ndarray:
        s = self.scores(t)
        q = np.ceil(s / self.smax() * N_LEVELS).astype(np.int32)
        return np.clip(q, 1, N_LEVELS)

    def docs(self, t: int) -> np.ndarray:
        return self.doc[self.offsets[t]:self.offsets[t + 1]]

    # ---- Stage-0 ----------------------------------------------------------

    def _query_counts(self, terms, mask):
        live = mask > 0
        df = self.df[terms] * live
        n_terms = live.sum(axis=1)
        min_df = np.where(live, self.df[terms], np.iinfo(np.int64).max)
        return n_terms, df.sum(axis=1), np.where(n_terms > 0,
                                                 min_df.min(axis=1), 0)

    def stage0_features(self, terms, mask) -> np.ndarray:
        """(Q, 147) with the split columns filled in float64."""
        n_terms, sum_df, min_df = self._query_counts(terms, mask)
        x = np.zeros((len(terms), N_STAGE0_FEATURES))
        x[:, F_N_TERMS] = np.maximum(n_terms, 1)
        x[:, F_LOG_SUM_DF] = np.log1p(sum_df)
        x[:, F_LOG_MIN_DF] = np.log1p(min_df)
        return x

    @staticmethod
    def stage0_edges(x: np.ndarray, n_bins: int):
        edges = np.tile(FAR + np.arange(n_bins - 1, dtype=np.float32),
                        (N_STAGE0_FEATURES, 1))
        useful = np.zeros(N_STAGE0_FEATURES, np.int64)
        edges[F_N_TERMS], useful[F_N_TERMS] = _snap_edges(
            x[:, F_N_TERMS], n_bins - 1, _half_edge)
        for j in (F_LOG_SUM_DF, F_LOG_MIN_DF):
            edges[j], useful[j] = _snap_edges(np.expm1(x[:, j]), n_bins - 1,
                                              _log_edge)
        return edges, useful

    def stage0_bins(self, terms, mask, edges):
        """(binned features, ambiguous rows): a log feature within a few
        float32 ulps of an edge may bin either way on the device."""
        x = self.stage0_features(terms, mask)
        if self.precision != "float32":
            x = _round(x, self.precision).astype(np.float64)
        xb = np.zeros(x.shape, np.int64)
        amb = np.zeros(len(x), bool)
        for j in STAGE0_SPLIT_FEATURES:
            e = edges[j].astype(np.float64)
            xb[:, j] = np.sum(x[:, j, None] > e[None], axis=1)
            if j != F_N_TERMS:
                guard = ULP_GUARD * np.spacing(edges[j]).astype(np.float64)
                amb |= np.any(np.abs(x[:, j, None] - e[None]) <= guard[None],
                              axis=1)
        return xb, amb

    def route(self, parts: dict, edges, terms, mask, sched: dict):
        """Reference Stage-0 decisions for a batch: (is_jass, k, rho_lo,
        rho_hi, ambiguous).  ``rho_lo``/``rho_hi`` bracket ρ by a relative
        1e-4, the float32 rounding room of the device's predictions."""
        xb, amb = self.stage0_bins(terms, mask, edges)
        pk, pr, pt = (np.expm1(forest_score(parts[n], xb))
                      for n in ("k", "rho", "t"))
        t_k, t_time = sched["t_k"], sched["t_time"]
        is_jass = (pk > t_k) | (pt > t_time)
        amb |= (np.abs(pk - t_k) <= 1e-4 * t_k) | (
            np.abs(pt - t_time) <= 1e-4 * t_time)
        k = np.clip(np.round(pk), 10, 16384).astype(np.int64)

        def rho(scale):
            return np.clip(np.round(pr * scale), sched["rho_min"],
                           sched["rho_max"]).astype(np.int64)
        return is_jass, k, rho(1 - 1e-4), rho(1 + 1e-4), amb

    # ---- Stage-1 ----------------------------------------------------------

    def jass_list(self, terms_row, mask_row, rho: int, k: int):
        """(ids, scores) of the budgeted impact-ordered traversal."""
        ts = [int(t) for t, m in zip(terms_row, mask_row) if m > 0]
        imps = [self.impacts(t) for t in ts]
        counts = np.zeros(N_LEVELS + 2, np.int64)
        for im in imps:
            counts[:N_LEVELS + 1] += np.bincount(im, minlength=N_LEVELS + 1)
        total = np.cumsum(counts[::-1])[::-1]           # postings >= level
        ok = np.flatnonzero(total[:N_LEVELS + 1] <= rho)
        lstar = int(ok[0]) if len(ok) else N_LEVELS + 1
        acc = np.zeros(self.n_docs)
        for t, im in zip(ts, imps):
            live = im >= lstar
            acc += np.bincount(self.docs(t)[live], weights=im[live],
                               minlength=self.n_docs)
        ids = topk_ties(acc, k)
        self.last_cut = (lstar, int(total[lstar]) if lstar <= N_LEVELS
                         else 0)
        return ids, acc[ids]

    def bm25_acc(self, terms_row, mask_row) -> np.ndarray:
        """Exhaustive per-doc BM25 of one query (float64 sums of the stored
        per-posting scores)."""
        acc = np.zeros(self.n_docs)
        for t, m in zip(terms_row, mask_row):
            if m > 0:
                acc += np.bincount(self.docs(int(t)),
                                   weights=self.scores(int(t)),
                                   minlength=self.n_docs)
        return acc

    # ---- Stage-2 ----------------------------------------------------------

    def ltr_features(self, terms_row, mask_row, topic: int, docs):
        """(C, 8) features of ``docs`` for one query (float64)."""
        docs = np.asarray(docs, np.int64)
        f = np.zeros((len(docs), N_LTR_FEATURES))
        dl32 = self.doclen[docs].astype(np.float32)
        f[:, 0] = np.log1p(dl32)
        n_t = 0
        for t, m in zip(terms_row, mask_row):
            if m <= 0:
                continue
            n_t += 1
            seg = self.docs(int(t))
            if len(seg) == 0:
                continue
            pos = np.minimum(np.searchsorted(seg, docs), len(seg) - 1)
            hit = seg[pos] == docs
            sc = np.where(hit, self.scores(int(t))[pos], 0.0)
            f[:, 1] += sc
            f[:, 2] = np.maximum(f[:, 2], sc)
            f[:, 3] += hit
        f[:, 3] /= max(n_t, 1)
        f[:, 4] = f[:, 1] / np.maximum(dl32, 1.0)
        f[:, 5] = self.doc_topics[docs, topic]
        f[:, 6] = self.doc_topics_max[docs]
        f[:, 7] = n_t
        return f

    def ltr_scores(self, parts, edges, terms_row, mask_row, topic, docs):
        f = self.ltr_features(terms_row, mask_row, topic, docs)
        return forest_score(parts, bins_exact(_round(f, self.precision),
                                              edges))
