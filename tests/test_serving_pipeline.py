"""Parity suite for the batched kernel-backed serving pipeline.

The batched ``daat_serve`` / ``saat_serve`` (jnp fast path AND the
interpret-mode Pallas kernel path over the bucketed shard mirror) must
reproduce the original one-query-at-a-time ``lax.map`` + dense scatter-add
reference, across θ aggression settings and ρ budgets; DAAT must run
exactly one exact-scoring pass per query (phase-1 accumulator reused).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.index.builder import pack_tiles
from repro.index.postings import shard_from_index
from repro.isn import daat
from repro.isn.backend import (compact_lanes, query_lane_budget,
                               resolve_backend, tiled_topk, topk_from_tiles)
from repro.isn.daat import daat_serve, daat_serve_laxmap
from repro.isn.saat import saat_serve, saat_serve_laxmap


@pytest.fixture(scope="module")
def shard(small_collection):
    corpus, index, ql = small_collection
    s, spec = shard_from_index(index)
    return corpus, index, ql, s, spec


# ---------------------------------------------------------------------------
# batched jnp pipeline vs lax.map reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rho", [256, 2048, 8192])
def test_saat_batched_matches_laxmap(shard, rho):
    corpus, index, ql, s, spec = shard
    terms, mask = jnp.asarray(ql.terms), jnp.asarray(ql.mask)
    rho_v = jnp.full(96, rho, jnp.int32)
    a = saat_serve(s, terms, mask, rho_v, n_docs=spec.n_docs, k=30, cap=rho,
                   backend="jnp")
    b = saat_serve_laxmap(s, terms, mask, rho_v, n_docs=spec.n_docs, k=30,
                          cap=rho)
    # integer accumulation: all paths agree bit-exactly
    np.testing.assert_array_equal(np.asarray(a.topk_docs),
                                  np.asarray(b.topk_docs))
    np.testing.assert_array_equal(np.asarray(a.topk_scores),
                                  np.asarray(b.topk_scores))
    np.testing.assert_array_equal(np.asarray(a.work), np.asarray(b.work))


@pytest.mark.parametrize("theta", [1.0, 1.2])
def test_daat_batched_matches_laxmap(shard, theta):
    corpus, index, ql, s, spec = shard
    terms, mask = jnp.asarray(ql.terms), jnp.asarray(ql.mask)
    qcap = query_lane_budget(index.df, ql.terms, ql.mask)
    kw = dict(n_docs=spec.n_docs, n_blocks=spec.n_blocks,
              block_size=spec.block_size, k=20, cap=spec.max_df,
              bcap=spec.max_blocks_per_term)
    a = daat_serve(s, terms, mask, jnp.full(96, theta), qcap=qcap,
                   backend="jnp", **kw)
    b = daat_serve_laxmap(s, terms, mask, jnp.full(96, theta), **kw)
    np.testing.assert_array_equal(np.asarray(a.work), np.asarray(b.work))
    np.testing.assert_array_equal(np.asarray(a.blocks), np.asarray(b.blocks))
    np.testing.assert_allclose(np.asarray(a.topk_scores),
                               np.asarray(b.topk_scores), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(a.topk_docs),
                                  np.asarray(b.topk_docs))


def test_daat_batched_chunked_q_block(shard):
    """Streaming a large batch through q_block-sized chunks is exact."""
    corpus, index, ql, s, spec = shard
    terms, mask = jnp.asarray(ql.terms), jnp.asarray(ql.mask)
    kw = dict(n_docs=spec.n_docs, n_blocks=spec.n_blocks,
              block_size=spec.block_size, k=20, cap=spec.max_df,
              bcap=spec.max_blocks_per_term)
    a = daat_serve(s, terms, mask, jnp.ones(96), q_block=40, backend="jnp",
                   **kw)
    b = daat_serve_laxmap(s, terms, mask, jnp.ones(96), **kw)
    np.testing.assert_array_equal(np.asarray(a.topk_docs),
                                  np.asarray(b.topk_docs))
    np.testing.assert_array_equal(np.asarray(a.work), np.asarray(b.work))


# ---------------------------------------------------------------------------
# interpret-mode kernel backend (the Pallas program itself) vs reference
# ---------------------------------------------------------------------------

def test_saat_kernel_backend_matches_laxmap(shard):
    corpus, index, ql, s, spec = shard
    q, rho = 8, 2048
    terms, mask = jnp.asarray(ql.terms[:q]), jnp.asarray(ql.mask[:q])
    rho_v = jnp.full(q, rho, jnp.int32)
    a = saat_serve(s, terms, mask, rho_v, n_docs=spec.n_docs, k=30, cap=rho,
                   tile_d=spec.tile_d, backend="interpret")
    b = saat_serve_laxmap(s, terms, mask, rho_v, n_docs=spec.n_docs, k=30,
                          cap=rho)
    np.testing.assert_array_equal(np.asarray(a.topk_docs),
                                  np.asarray(b.topk_docs))
    np.testing.assert_array_equal(np.asarray(a.topk_scores),
                                  np.asarray(b.topk_scores))
    np.testing.assert_array_equal(np.asarray(a.work), np.asarray(b.work))


@pytest.mark.parametrize("theta", [1.0, 1.2])
def test_daat_kernel_backend_matches_laxmap(shard, theta):
    corpus, index, ql, s, spec = shard
    q = 8
    terms, mask = jnp.asarray(ql.terms[:q]), jnp.asarray(ql.mask[:q])
    kw = dict(n_docs=spec.n_docs, n_blocks=spec.n_blocks,
              block_size=spec.block_size, k=20, cap=spec.max_df,
              bcap=spec.max_blocks_per_term)
    a = daat_serve(s, terms, mask, jnp.full(q, theta), tile_d=spec.tile_d,
                   backend="interpret", **kw)
    b = daat_serve_laxmap(s, terms, mask, jnp.full(q, theta), **kw)
    np.testing.assert_array_equal(np.asarray(a.work), np.asarray(b.work))
    np.testing.assert_array_equal(np.asarray(a.blocks), np.asarray(b.blocks))
    np.testing.assert_allclose(np.asarray(a.topk_scores),
                               np.asarray(b.topk_scores), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(a.topk_docs),
                                  np.asarray(b.topk_docs))


# ---------------------------------------------------------------------------
# the one-exact-pass property
# ---------------------------------------------------------------------------

def test_daat_single_exact_scoring_pass(shard, monkeypatch):
    """daat_serve runs exactly one exact-scoring pass per query: phase-1
    scores its blocks once, the exact pass scores only the *disjoint*
    remainder, and the phase-1 accumulator is reused (summed), never
    recomputed."""
    corpus, index, ql, s, spec = shard
    q = 8
    terms, mask = jnp.asarray(ql.terms[:q]), jnp.asarray(ql.mask[:q])

    calls = []
    orig = daat._score_pass

    def spy(d, sc, live, survive, n_docs, block_size):
        calls.append(np.asarray(survive))
        return orig(d, sc, live, survive, n_docs, block_size)

    monkeypatch.setattr(daat, "_score_pass", spy)
    # call the eager core directly so the spy sees concrete block masks
    daat._daat_batched(s, terms, mask, jnp.ones(q), n_docs=spec.n_docs,
                       n_blocks=spec.n_blocks, block_size=spec.block_size,
                       k=20, cap=spec.max_df, bcap=spec.max_blocks_per_term,
                       qcap=8 * spec.max_df, tile_d=spec.tile_d,
                       backend="jnp")
    assert len(calls) == 2, "exactly phase-1 + one exact pass"
    in_p1, extra = calls
    assert not np.any(in_p1 & extra), \
        "exact pass must not rescore phase-1 blocks"


# ---------------------------------------------------------------------------
# batched kernels over a synthetic bucketed mirror
# ---------------------------------------------------------------------------

def _synthetic_bucketed(seed, n_docs=600, vocab=48, p=4000, tile_d=128):
    rng = np.random.RandomState(seed)
    pairs = rng.permutation(n_docs * vocab)[:p]      # unique (term, doc)
    terms = (pairs // n_docs).astype(np.int32)
    docs = (pairs % n_docs).astype(np.int32)
    scores = (rng.random_sample(p) * 6).astype(np.float32)
    imps = rng.randint(1, 256, p).astype(np.int32)
    td, tt, (ts, ti), cap = pack_tiles(
        docs, terms, [(scores, 0.0, np.float32), (imps, 0, np.int32)],
        n_docs, tile_d)
    return rng, terms, docs, scores, imps, td, tt, ts, ti


def test_blockmax_batched_kernel_matches_numpy():
    from repro.kernels.blockmax_score.ops import blockmax_score_tiles
    n_docs, bs, tile_d, q, L = 600, 64, 128, 5, 8
    rng, terms, docs, scores, imps, td, tt, ts, ti = _synthetic_bucketed(
        1, n_docs=n_docs, tile_d=tile_d)
    qterms = np.full((q, L), -1, np.int32)
    for i in range(q):
        qterms[i, :5] = rng.choice(48, 5, replace=False)
    n_blocks = -(-n_docs // bs)
    survive = rng.random_sample((q, n_blocks)) < 0.4
    acc_t = blockmax_score_tiles(
        jnp.asarray(td), jnp.asarray(tt), jnp.asarray(ts),
        jnp.asarray(qterms), jnp.asarray(survive), tile_d=tile_d,
        block_size=bs, n_blocks=n_blocks, interpret=True)
    acc = np.asarray(acc_t).reshape(q, -1)[:, :n_docs]
    for i in range(q):
        keep = np.isin(terms, qterms[i][qterms[i] >= 0]) \
            & survive[i][docs // bs]
        ref = np.zeros(n_docs, np.float32)
        np.add.at(ref, docs[keep], scores[keep])
        np.testing.assert_allclose(acc[i], ref, atol=1e-4)


def test_impact_batched_kernel_matches_numpy():
    from repro.kernels.impact_accumulate.ops import impact_accumulate_tiles
    n_docs, tile_d, q, L = 600, 128, 5, 8
    rng, terms, docs, scores, imps, td, tt, ts, ti = _synthetic_bucketed(
        2, n_docs=n_docs, tile_d=tile_d)
    qterms = np.full((q, L), -1, np.int32)
    for i in range(q):
        qterms[i, :6] = rng.choice(48, 6, replace=False)
    lstar = rng.randint(0, 256, q).astype(np.int32)
    acc_t = impact_accumulate_tiles(
        jnp.asarray(td), jnp.asarray(tt), jnp.asarray(ti),
        jnp.asarray(qterms), jnp.asarray(lstar), tile_d=tile_d,
        interpret=True)
    acc = np.asarray(acc_t).reshape(q, -1)[:, :n_docs]
    for i in range(q):
        keep = np.isin(terms, qterms[i][qterms[i] >= 0]) \
            & (imps >= lstar[i])
        ref = np.zeros(n_docs, np.int64)
        np.add.at(ref, docs[keep], imps[keep])
        np.testing.assert_array_equal(acc[i], ref)


def test_bucketed_mirror_is_lossless(shard):
    """The build-time (n_tiles, cap) mirror holds exactly the CSR postings:
    same (term, doc, score, impact) multiset, doc ids rebased per tile."""
    corpus, index, ql, s, spec = shard
    td = np.asarray(s.tile_docs)
    tt = np.asarray(s.tile_terms)
    ts = np.asarray(s.tile_scores)
    ti = np.asarray(s.tile_imps)
    live = td >= 0
    gdoc = td + (np.arange(spec.n_tiles) * spec.tile_d)[:, None]
    term_of = np.repeat(np.arange(spec.vocab),
                        np.diff(np.asarray(s.offsets)))
    assert int(live.sum()) == spec.n_postings
    # scores against the doc-ordered mirror
    got = sorted(zip(tt[live], gdoc[live], ts[live]))
    want = sorted(zip(term_of, np.asarray(s.docs), np.asarray(s.score)))
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64), rtol=1e-6)
    # impacts against the impact-ordered mirror (same (term, doc) multiset)
    got_i = sorted(zip(tt[live], gdoc[live], ti[live]))
    want_i = sorted(zip(term_of, np.asarray(s.docs_imp), np.asarray(s.imp)))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_unaligned_shard_mirror_is_never_padded(small_collection):
    """A shard whose tile count is not a whole kernel tile group gets dead
    tiles at build time, so the serving kernels read the resident mirror
    as it is: the traced call pads nothing, an unaligned mirror is refused
    rather than copied, and the dead tiles never reach a top-k."""
    from repro.kernels.blockmax_score.ops import blockmax_score_tiles
    from repro.kernels.blocks import TILES_PER_STEP
    from repro.kernels.impact_accumulate.ops import impact_accumulate_tiles
    corpus, index, ql = small_collection
    tile_d, n_real = 128, 6
    s, spec = shard_from_index(index, 0, (n_real - 1) * tile_d + 7, tile_d)
    assert spec.n_tiles == s.tile_docs.shape[0] == TILES_PER_STEP
    assert (np.asarray(s.tile_docs)[n_real:] == -1).all()

    q = 8
    qt = jnp.where(jnp.asarray(ql.mask[:q]) > 0, jnp.asarray(ql.terms[:q]),
                   -1).astype(jnp.int32)
    jaxpr = jax.make_jaxpr(lambda td, tt, ti, qt, cut: impact_accumulate_tiles(
        td, tt, ti, qt, cut, tile_d=tile_d, interpret=True))(
        s.tile_docs, s.tile_terms, s.tile_imps, qt, jnp.zeros(q, jnp.int32))
    assert " pad[" not in str(jaxpr)
    with pytest.raises(ValueError, match="pack_tiles"):
        impact_accumulate_tiles(s.tile_docs[:n_real], s.tile_terms[:n_real],
                                s.tile_imps[:n_real], qt,
                                jnp.zeros(q, jnp.int32), tile_d=tile_d,
                                interpret=True)
    with pytest.raises(ValueError, match="pack_tiles"):
        blockmax_score_tiles(s.tile_docs[:n_real], s.tile_terms[:n_real],
                             s.tile_scores[:n_real], qt,
                             jnp.ones((q, spec.n_blocks), bool),
                             tile_d=tile_d, block_size=spec.block_size,
                             n_blocks=spec.n_blocks, interpret=True)

    terms, mask = jnp.asarray(ql.terms[:q]), jnp.asarray(ql.mask[:q])
    kw = dict(n_docs=spec.n_docs, n_blocks=spec.n_blocks,
              block_size=spec.block_size, k=20, cap=spec.max_df,
              bcap=spec.max_blocks_per_term)
    a, b = (daat_serve(s, terms, mask, jnp.ones(q), backend=be, **kw)
            for be in ("interpret", "jnp"))
    assert int(np.asarray(a.topk_docs).max()) < spec.n_docs
    np.testing.assert_array_equal(np.asarray(a.topk_docs),
                                  np.asarray(b.topk_docs))
    rho = jnp.full(q, 2048, jnp.int32)
    a, b = (saat_serve(s, terms, mask, rho, n_docs=spec.n_docs, k=20,
                       cap=2048, backend=be) for be in ("interpret", "jnp"))
    assert int(np.asarray(a.topk_docs).max()) < spec.n_docs
    np.testing.assert_array_equal(np.asarray(a.topk_docs),
                                  np.asarray(b.topk_docs))


# ---------------------------------------------------------------------------
# backend plumbing
# ---------------------------------------------------------------------------

def test_tiled_topk_matches_dense_topk_with_ties():
    rng = np.random.RandomState(7)
    # small integer range forces heavy ties — the merge must keep lax.top_k's
    # lower-index tie-break
    acc_i = jnp.asarray(rng.randint(0, 7, (16, 1000)), jnp.int32)
    acc_f = acc_i.astype(jnp.float32)
    for acc in (acc_i, acc_f):
        sc, ids = tiled_topk(acc, 25, tile_d=128, max_score=6)
        sc_r, ids_r = jax.lax.top_k(acc, 25)
        np.testing.assert_array_equal(np.asarray(sc), np.asarray(sc_r))
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_r))


def test_topk_from_tiles_masks_ghost_docs():
    # 2 tiles of 4 docs but only 6 real docs; ghosts must never surface
    acc = jnp.zeros((1, 2, 4), jnp.float32)
    sc, ids = topk_from_tiles(acc, 8, n_docs=6)
    assert set(np.asarray(ids[0, :6])) == set(range(6))
    assert np.all(np.asarray(sc[0, 6:]) < 0)


def test_compact_lanes_concatenates_prefixes():
    base = jnp.asarray([[0, 10, 40], [5, 7, 90]], jnp.int32)
    dfs = jnp.asarray([[3, 0, 2], [1, 1, 1]], jnp.int32)
    pos, live = compact_lanes(base, dfs, 6)
    np.testing.assert_array_equal(
        np.asarray(pos)[np.asarray(live)],
        np.asarray([0, 1, 2, 40, 41, 5, 7, 90]))
    np.testing.assert_array_equal(np.asarray(live).sum(axis=1),
                                  np.asarray([5, 3]))


def test_query_lane_budget_covers_batch(shard):
    corpus, index, ql, s, spec = shard
    qcap = query_lane_budget(index.df, ql.terms, ql.mask)
    eff = index.df[ql.terms] * (ql.mask > 0)
    assert qcap >= int(eff.sum(axis=1).max())
    assert qcap % 1024 == 0 or qcap == 256


def test_resolve_backend():
    assert resolve_backend("jnp") == "jnp"
    assert resolve_backend(None) in ("pallas", "jnp")
    with pytest.raises(ValueError):
        resolve_backend("cuda")
