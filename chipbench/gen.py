"""Seeded inputs of one run: the collection, the query stream and the
Stage-0 / Stage-2 forests.

Everything here is made from ``--seed`` by the benchmark itself, as the
weights of a served model would be: the program under test only receives
the generated collection (``repro.index.corpus.Corpus``), the query rows and
the forests in its own ``GBRTModel`` layout.

The collection follows the repository's synthetic generator in form
(log-normal document lengths, a Zipf background vocabulary, a sparse
Dirichlet topic mixture per document whose topical tokens are drawn from a
topic-permuted Zipf, doc ids clustered by dominant topic) at the sizes the
configuration gives: its vocabulary is the source collection's, and its
document lengths are set so that a document holds the source's postings.
It is drawn fully vectorised: per-document topic counts come from
sequential binomials instead of a per-token 32-way Gumbel draw, the Zipf
tail is inverted in closed form, and the (term, doc) aggregation sorts in
term-range buckets on a thread pool.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference as R

SORT_BUCKETS = 64
SORT_THREADS = 8


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per input stream; any non-negative seed."""
    return np.random.default_rng([int(stream), int(seed)])


HEAD_RANKS = 1 << 20


class Zipf:
    """Zipf(``a``) over ranks 0..``vocab``-1 of a web-sized vocabulary:
    the first ``HEAD_RANKS`` ranks by their exact cumulative sum, the tail
    by the integral of x^-a, so that no vocabulary-sized table is made."""

    def __init__(self, vocab: int, a: float):
        self.vocab, self.a = int(vocab), float(a)
        head = min(HEAD_RANKS, self.vocab)
        p = np.arange(1, head + 1, dtype=np.float64) ** -self.a
        self._edge = head + 0.5
        tail = (self._edge ** (1 - self.a)
                - (self.vocab + 0.5) ** (1 - self.a)) / (self.a - 1)
        self.z = p.sum() + tail
        self._cdf = np.cumsum(p) / self.z
        self._head = head

    def prob(self, rank: np.ndarray) -> np.ndarray:
        return (np.asarray(rank, np.float64) + 1) ** -self.a / self.z

    def draw(self, u: np.ndarray) -> np.ndarray:
        """Ranks (int64) for uniforms ``u``."""
        out = np.searchsorted(self._cdf, u).astype(np.int64)
        tail = u > self._cdf[-1]
        if tail.any():
            m = (u[tail] - self._cdf[-1]) * self.z * (self.a - 1)
            x = (self._edge ** (1 - self.a) - m) ** (1 / (1 - self.a)) - 0.5
            out[tail] = np.floor(x).astype(np.int64)
        return np.clip(out, 0, self.vocab - 1)


def topic_maps(rng, vocab: int, k: int) -> np.ndarray:
    """(k, 2) multiplier and offset of each topic's bijection of the ranks,
    r -> (m * r + b) mod vocab, with m prime to vocab."""
    out = np.zeros((k, 2), np.int64)
    for t in range(k):
        m = int(rng.integers(vocab // 3, vocab))
        while np.gcd(m, vocab) != 1:
            m += 1
        out[t] = m, int(rng.integers(0, vocab))
    return out


def _draw(cdf: np.ndarray, u: np.ndarray) -> np.ndarray:
    return np.minimum(np.searchsorted(cdf, u), len(cdf) - 1).astype(np.int32)


def _multinomial_rows(rng, n: np.ndarray, p: np.ndarray) -> np.ndarray:
    """(N, K) counts: row i is Multinomial(n[i], p[i]) by sequential
    conditional binomials, vectorised over rows."""
    out = np.zeros(p.shape, np.int64)
    left = n.astype(np.int64).copy()
    rest = np.ones(len(n))
    for k in range(p.shape[1] - 1):
        q = np.clip(p[:, k] / np.maximum(rest, 1e-12), 0.0, 1.0)
        out[:, k] = rng.binomial(left, q)
        left -= out[:, k]
        rest -= p[:, k]
    out[:, -1] = left
    return out


def _sorted_unique_counts(key: np.ndarray, hi_bits: int):
    """Sorted unique int64 keys and their counts; sorts ``SORT_BUCKETS``
    key ranges (split on the top bits) in parallel threads."""
    shift = max(hi_bits - int(np.log2(SORT_BUCKETS)), 0)
    bucket = (key >> shift).astype(np.int16)
    order = np.argsort(bucket, kind="stable")
    key = key[order]
    del order
    bounds = np.r_[0, np.cumsum(np.bincount(bucket,
                                            minlength=SORT_BUCKETS))]
    with ThreadPoolExecutor(SORT_THREADS) as pool:
        list(pool.map(lambda b: key[bounds[b]:bounds[b + 1]].sort(),
                      range(SORT_BUCKETS)))
    first = np.empty(len(key), bool)
    first[:1] = True
    np.not_equal(key[1:], key[:-1], out=first[1:])
    starts = np.flatnonzero(first)
    counts = np.diff(np.r_[starts, len(key)])
    return key[starts], counts


def make_corpus(c: dict, seed: int):
    """A ``Corpus`` with the configuration's statistics, from the seed.

    Terms are ranks of a Zipf over the source's whole vocabulary
    (``vocab``); the shard's own vocabulary is the ranks its documents
    hold, numbered in rank order, as a shard's term dictionary would be.
    ``topic_perm`` maps each shard term to its topical image where the
    shard holds that image, and to itself where it does not: the query
    generator reads it, the program does not."""
    from repro.index.corpus import Corpus, CorpusParams

    rng = rng_for(seed, 1)
    n, v, k = int(c["n_docs"]), int(c["vocab"]), int(c["n_topics"])
    doclen = np.maximum(rng.lognormal(np.log(c["doclen_median"]),
                                      c["doclen_sigma"], n),
                        c["min_doclen"]).astype(np.int64)
    gam = rng.gamma(c["topic_alpha"], size=(n, k)).astype(np.float32) + 1e-8
    doc_topics = gam / gam.sum(axis=1, keepdims=True)
    del gam
    zipf = Zipf(v, c["zipf_a"])
    maps = topic_maps(rng, v, k)

    n_top = rng.binomial(doclen, c["topical_fraction"])
    n_bg = doclen - n_top
    bg_doc = np.repeat(np.arange(n, dtype=np.int32), n_bg)
    bg_term = zipf.draw(rng.random(len(bg_doc)))
    per_topic = _multinomial_rows(rng, n_top, doc_topics.astype(np.float64))
    slot = np.repeat(np.arange(n * k, dtype=np.int64), per_topic.ravel())
    del per_topic
    top_doc = (slot // k).astype(np.int32)
    tk = slot % k
    del slot
    top_term = (maps[tk, 0] * zipf.draw(rng.random(len(tk)))
                + maps[tk, 1]) % v
    del tk

    # doc ids clustered by dominant topic (URL-style reordering)
    order = np.argsort(np.argmax(doc_topics, axis=1), kind="stable")
    inv = np.empty(n, np.int64)
    inv[order] = np.arange(n)
    key = np.concatenate([bg_term, top_term]) * n
    key += inv[np.concatenate([bg_doc, top_doc])]
    del bg_doc, bg_term, top_doc, top_term
    uniq, counts = _sorted_unique_counts(key, int(np.ceil(np.log2(v * n))))
    del key
    rank = uniq // n
    new = np.r_[True, rank[1:] != rank[:-1]]
    term = (np.cumsum(new) - 1).astype(np.int32)
    ranks = rank[new]
    del rank, new
    vs = len(ranks)
    topic_perm = np.empty((k, vs), np.int32)

    def image(t):
        img = (maps[t, 0] * ranks + maps[t, 1]) % v
        order = np.argsort(img)
        pos = np.minimum(np.searchsorted(ranks, img[order]), vs - 1)
        hit = ranks[pos] == img[order]
        topic_perm[t, order] = np.where(hit, pos, order)
    with ThreadPoolExecutor(SORT_THREADS) as pool:
        list(pool.map(image, range(k)))
    params = CorpusParams(n_docs=n, vocab=vs,
                          avg_doclen=int(c["doclen_median"]),
                          zipf_a=float(c["zipf_a"]), n_topics=k,
                          topical_fraction=float(c["topical_fraction"]),
                          seed=int(seed) % (1 << 31))
    return Corpus(params, doclen[order].astype(np.int32), term,
                  (uniq % n).astype(np.int32), counts.astype(np.int32),
                  doc_topics[order], topic_perm,
                  zipf.prob(ranks).astype(np.float32))


def query_lengths(rng, q: dict, n: int) -> np.ndarray:
    """Terms per query: uniform on [min, max] (``uniform``) or one plus a
    Poisson of the given mean minus one, clipped to [min, max]
    (``poisson``)."""
    lo, hi = int(q["min_terms"]), int(q["max_terms"])
    if q["lengths"] == "uniform":
        return rng.integers(lo, hi + 1, size=n)
    if q["lengths"] == "poisson":
        return np.clip(1 + rng.poisson(q["mean_terms"] - 1.0, size=n), lo, hi)
    raise ValueError(f"unknown query length law {q['lengths']!r}")


def make_queries(corpus, q: dict, n: int, seed: int, stream: int,
                 slots: int = 8):
    """``n`` distinct-intent queries: terms drawn from the vocabulary's Zipf
    raised to ``popularity_exponent`` (stopped ranks excluded), a
    ``topical_share`` of them through the query topic's permutation."""
    from repro.index.corpus import QueryLog

    rng = rng_for(seed, stream)
    lengths = query_lengths(rng, q, n)
    topic = rng.integers(0, corpus.params.n_topics, size=n).astype(np.int32)
    probs = corpus.zipf_probs.astype(np.float64) ** q["popularity_exponent"]
    probs[:q["stop_k"]] = 0.0
    cdf = np.cumsum(probs / probs.sum())
    draws = _draw(cdf, rng.random((n, slots)))
    topical = rng.random((n, slots)) < q["topical_share"]
    draws = np.where(topical, corpus.topic_perm[topic[:, None], draws], draws)
    terms = np.zeros((n, slots), np.int32)
    mask = np.zeros((n, slots), np.float32)
    for i in range(n):
        t = np.unique(draws[i, :lengths[i]])
        terms[i, :len(t)] = t
        mask[i, :len(t)] = 1.0
    return QueryLog(terms, mask, topic, mask.sum(axis=1).astype(np.int32))


# ---------------------------------------------------------------------------
# forests: weights made from the seed, in the program's GBRTModel layout
# ---------------------------------------------------------------------------

def _boost(xb: np.ndarray, y: np.ndarray, feats, useful: np.ndarray,
           n_trees: int, depth: int, lr: float, rng):
    """Gradient boosting on the L2 loss with random (feature, bin) splits:
    split features are drawn from ``feats`` and split bins uniformly among
    the feature's real edges; each leaf takes ``lr`` times the mean residual
    of the rows it holds.  Returns (feat, thresh, leaf, base) as the
    program's ``Forest`` arrays."""
    half = 2 ** (depth - 1)
    feat = rng.choice(np.asarray(feats), size=(n_trees, depth, half))
    thresh = np.floor(rng.random(feat.shape)
                      * np.maximum(useful[feat], 1)).astype(np.int64)
    leaf = np.zeros((n_trees, 2 ** depth))
    base = float(np.mean(y))
    f = np.full(len(y), base)
    for t in range(n_trees):
        node = R.descend(feat[t], thresh[t], xb)
        res = y - f
        s = np.bincount(node, weights=res, minlength=2 ** depth)
        c = np.bincount(node, minlength=2 ** depth)
        leaf[t] = lr * s / np.maximum(c, 1)
        f += leaf[t][node]
    return (feat.astype(np.int32), thresh.astype(np.int32),
            leaf.astype(np.float32), np.float32(base))


def _model(parts, edges, n_trees, depth, n_bins, loss, tau, lr):
    import jax.numpy as jnp

    from repro.core import gbrt
    from repro.core.trees import Forest

    feat, thresh, leaf, base = parts
    params = gbrt.GBRTParams(n_trees=n_trees, depth=depth, n_bins=n_bins,
                             loss=loss, tau=tau, learning_rate=lr)
    return gbrt.GBRTModel(Forest(jnp.asarray(feat), jnp.asarray(thresh),
                                 jnp.asarray(leaf)),
                          jnp.asarray(base), jnp.asarray(edges), params)


def stage0_models(ref: "R.Reference", train, cfg: dict, s0, seed: int):
    """The k, ρ and t predictors (``Stage0Spec`` shapes) and the routing
    thresholds they imply (the program's ``calibrate`` rule: t_k at the
    60th percentile of predicted k, t_time at the 75th percentile of
    predicted time, capped at 0.75 of the budget)."""
    rng = rng_for(seed, 3)
    n_bins = 64
    x = ref.stage0_features(train.terms, train.mask)
    edges, useful = ref.stage0_edges(x, n_bins)
    xb, _ = ref.stage0_bins(train.terms, train.mask, edges)
    eff = np.exp(x[:, R.F_LOG_SUM_DF]) - 1.0
    t = cfg["stage0"]
    models, parts = {}, {}
    for name in ("k", "rho", "t"):
        y = np.log1p(eff * t["scale"][name]
                     * np.exp(rng.standard_normal(len(eff)) * t["noise"]))
        parts[name] = _boost(xb, y, R.STAGE0_SPLIT_FEATURES, useful,
                             s0.n_trees, s0.depth, t["learning_rate"], rng)
        tau = {"k": s0.tau_k, "rho": s0.tau_rho, "t": s0.tau_t}[name]
        models[name] = _model(parts[name], edges, s0.n_trees, s0.depth,
                              n_bins, "quantile", tau, t["learning_rate"])
    return models, parts, edges


def ltr_model(ref: "R.Reference", train, s2, cfg: dict, seed: int):
    """The Stage-2 point-wise forest (``train_ltr`` shapes: depth 4, 64
    bins) with pseudo-gains ``topic affinity + 0.2 * BM25 sum`` over random
    (query, doc) pairs, as the program's label-free ``fit`` does."""
    rng = rng_for(seed, 4)
    t = cfg["stage2"]
    rows = np.arange(min(len(train.terms), t["train_queries"]))
    docs = rng.integers(0, ref.n_docs, size=(len(rows), t["docs_per_query"]))
    f = np.concatenate([ref.ltr_features(train.terms[q], train.mask[q],
                                         int(train.topic[q]), docs[i])
                        for i, q in enumerate(rows)])
    gain = f[:, 5] + 0.2 * f[:, 1]
    edges, useful = R.ltr_edges(f, 64)
    xb = R.bins_exact(f.astype(np.float32), edges)
    parts = _boost(xb, gain, R.LTR_SPLIT_FEATURES, useful, s2.ltr_trees, 4,
                   t["learning_rate"], rng)
    from repro.ltr.ranker import LTRModel
    return LTRModel(_model(parts, edges, s2.ltr_trees, 4, 64, "l2", 0.5,
                           t["learning_rate"])), parts, edges
