"""The plain reference against the program on a small collection: the
same stoplist, document frequencies, float32 BM25 and impacts bit for bit,
the same JASS lists, and the generator's invariants."""

import numpy as np
import pytest

import gen
import reference as R

SMALL = {"n_docs": 4096, "vocab": 4096, "doclen_median": 60,
         "doclen_sigma": 0.6, "min_doclen": 8, "zipf_a": 1.1,
         "n_topics": 16, "topic_alpha": 0.08, "topical_fraction": 0.35}
QUERIES = {"lengths": "uniform", "min_terms": 2, "max_terms": 5,
           "popularity_exponent": 0.65, "topical_share": 0.5, "stop_k": 8}


@pytest.fixture(scope="module")
def small():
    from repro.index.builder import build_index
    corpus = gen.make_corpus(SMALL, 2**33 + 5)
    return corpus, build_index(corpus, stop_k=8), R.Reference(corpus, 8)


def test_generator_invariants(small):
    corpus, _, _ = small
    key = corpus.postings_term.astype(np.int64) * 4096 + corpus.postings_doc
    assert corpus.postings_term.max() == corpus.vocab - 1
    assert np.all(np.diff(key) > 0)         # (term, doc) sorted, unique
    assert corpus.postings_tf.min() >= 1
    assert corpus.doclen.min() >= 8
    # tokens per doc add up to the drawn document lengths
    per_doc = np.bincount(corpus.postings_doc, weights=corpus.postings_tf,
                          minlength=4096)
    assert np.array_equal(per_doc, corpus.doclen)
    again = gen.make_corpus(SMALL, 2**33 + 5)
    assert np.array_equal(again.postings_doc, corpus.postings_doc)


def test_scores_and_impacts_match_the_index(small):
    corpus, index, ref = small
    assert sorted(ref.stoplist) == sorted(index.stoplist)
    assert np.array_equal(ref.df, index.df)
    assert ref.smax() == index.quant_scale
    for t in range(0, corpus.vocab, 5):
        lo, hi = index.offsets[t], index.offsets[t + 1]
        assert np.array_equal(ref.docs(t), index.docs[lo:hi])
        assert np.array_equal(ref.scores(t), index.bm25_score[lo:hi])
        assert np.array_equal(ref.impacts(t), index.impact[lo:hi])


def test_jass_list_matches_the_program(small):
    import jax.numpy as jnp

    from repro.index.postings import shard_from_index
    from repro.isn.saat import saat_serve

    corpus, index, ref = small
    ql = gen.make_queries(corpus, QUERIES, 16, 9, stream=6)
    shard, sp = shard_from_index(index)
    rho = np.array([5, 200, 800, 3000, 10**6] * 4)[:16]
    res = saat_serve(shard, jnp.asarray(ql.terms), jnp.asarray(ql.mask),
                     jnp.asarray(rho), n_docs=sp.n_docs, k=32,
                     cap=1 << 18, backend="jnp")
    for i in range(16):
        ids, sc = ref.jass_list(ql.terms[i], ql.mask[i], int(rho[i]), 32)
        assert np.array_equal(np.asarray(res.topk_docs[i]), ids)
        assert np.array_equal(np.asarray(res.topk_scores[i]), sc)


def test_list_gap():
    import run
    acc = np.array([5.0, 4.0, 3.0, 2.0])
    assert run.list_gap(acc, np.array([0, 1])) == 0.0
    assert run.list_gap(acc, np.array([0, 2])) == pytest.approx(0.2)
    assert run.list_gap(acc, np.array([0, 0])) == float("inf")


def test_every_seed_offers_the_same_arrivals_in_another_order():
    import traffic
    a = traffic.poisson_due_ms(6.0, 51, 20090101, 1)
    b = traffic.poisson_due_ms(6.0, 51, 20090101, 2)
    assert len(a) == len(b) == 306
    assert a[-1] < 51e3 and np.all(np.diff(a) > 0)
    assert not np.array_equal(a, b)
    gaps = [np.sort(np.diff(np.r_[0.0, x, 51e3])) for x in (a, b)]
    assert np.allclose(gaps[0], gaps[1])


def test_zipf_over_a_web_vocabulary():
    z = gen.Zipf(92_094_694, 1.15)
    draws = z.draw(gen.rng_for(3, 0).random(1 << 20))
    assert draws.min() == 0 and draws.max() < 92_094_694
    assert draws.max() >= gen.HEAD_RANKS          # the tail is reached
    for r in range(4):
        assert np.mean(draws == r) == pytest.approx(z.prob(r), rel=0.02)
    tail = np.mean(draws >= gen.HEAD_RANKS)
    want = 1.0 - z.prob(np.arange(gen.HEAD_RANKS)).sum()
    assert tail == pytest.approx(want, rel=0.05)


def test_topic_maps_are_bijections():
    maps = gen.topic_maps(gen.rng_for(1, 0), 1000, 4)
    r = np.arange(1000)
    for m, b in maps:
        assert len(np.unique((m * r + b) % 1000)) == 1000


def test_shard_vocabulary_is_numbered_in_rank_order(small):
    corpus, _, _ = small
    assert np.all(np.diff(corpus.zipf_probs) <= 0)
    assert corpus.topic_perm.shape == (16, corpus.vocab)
    assert corpus.topic_perm.min() >= 0
    assert corpus.topic_perm.max() < corpus.vocab
