"""Parity suite for the batched end-to-end cascade pipeline.

The unified array-program cascade (fused Stage-0, batched Stage-2 LTR
re-rank, per-stage latency accounting) must reproduce the per-query
reference paths: the numpy ``qd_features`` loop and the ``rerank_loop``
cascade driver.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gbrt
from repro.ltr import cascade, ranker
from repro.serving.scheduler import SchedulerConfig


@pytest.fixture(scope="module")
def stage2(small_collection):
    corpus, index, ql = small_collection
    arrs = ranker.stage2_arrays(index, corpus)
    n_iter = ranker.csr_search_iters(int(index.df.max()))
    rng = np.random.RandomState(7)
    c = 48
    cand = np.sort(rng.choice(index.n_docs, (96, c)), axis=1).astype(np.int64)
    cand[0, 40:] = -1                       # ragged padding
    cand[3] = -1                            # fully empty candidate list
    return corpus, index, ql, arrs, n_iter, cand


@pytest.fixture(scope="module")
def ltr_model(stage2):
    corpus, index, ql, arrs, n_iter, cand = stage2
    rng = np.random.RandomState(11)
    feats = []
    for q in range(24):
        sel = cand[q][cand[q] >= 0]
        feats.append(ranker.qd_features(index, corpus, ql.terms[q],
                                        ql.mask[q], ql.topic[q], sel))
    feats = np.concatenate(feats)
    gains = (feats[:, 5] + 0.2 * feats[:, 1]
             + 0.05 * rng.randn(len(feats))).astype(np.float32)
    return ranker.train_ltr(feats, gains, n_trees=24)


# ---------------------------------------------------------------------------
# batched featurization vs the per-query numpy loop — exact
# ---------------------------------------------------------------------------

def test_qd_features_batched_matches_loop_exactly(stage2):
    corpus, index, ql, arrs, n_iter, cand = stage2
    feats = np.asarray(ranker.qd_features_batched(
        arrs, jnp.asarray(ql.terms), jnp.asarray(ql.mask),
        jnp.asarray(ql.topic), jnp.asarray(cand, jnp.int32), n_iter=n_iter))
    assert feats.shape == (96, cand.shape[1], ranker.N_LTR_FEATURES)
    for q in range(96):
        sel = cand[q] >= 0
        if not sel.any():
            continue
        ref = ranker.qd_features(index, corpus, ql.terms[q], ql.mask[q],
                                 ql.topic[q], cand[q][sel])
        np.testing.assert_array_equal(feats[q][sel], ref)


def test_rerank_batched_matches_loop_exactly(stage2, ltr_model):
    corpus, index, ql, arrs, n_iter, cand = stage2
    rng = np.random.RandomState(3)
    k_per_query = rng.randint(0, cand.shape[1] + 16, 96)
    k_per_query[5] = 0                      # k = 0 edge case
    a = cascade.rerank_batched(arrs, ltr_model, ql.terms, ql.mask, ql.topic,
                               cand, k_per_query, t_final=10, n_iter=n_iter)
    b = cascade.rerank_loop(index, corpus, ql, np.arange(96), cand,
                            k_per_query, ltr_model, t_final=10)
    np.testing.assert_array_equal(a.final, b.final)
    np.testing.assert_array_equal(a.candidates_used, b.candidates_used)


def test_rerank_batched_empty_candidates(stage2, ltr_model):
    """A query with no candidates yields the loop's zero row and used == 0."""
    corpus, index, ql, arrs, n_iter, cand = stage2
    k = np.full(96, cand.shape[1])
    res = cascade.rerank_batched(arrs, ltr_model, ql.terms, ql.mask,
                                 ql.topic, cand, k, t_final=10, n_iter=n_iter)
    assert res.candidates_used[3] == 0
    np.testing.assert_array_equal(res.final[3], np.zeros(10, np.int64))
    # short candidate lists pad the tail of the final list with -1
    res_short = cascade.rerank_batched(arrs, ltr_model, ql.terms, ql.mask,
                                       ql.topic, cand,
                                       np.full(96, 4), t_final=10,
                                       n_iter=n_iter)
    assert np.all(res_short.final[1, 4:] == -1)
    assert np.all(res_short.final[1, :4] >= 0)


# ---------------------------------------------------------------------------
# the qd_feature_gather kernel (interpret mode = the kernel program on CPU)
# ---------------------------------------------------------------------------

def _block_csr():
    """A hand-made doc-ordered CSR whose term ranges sit on and across the
    kernel's 1,024-posting block edges: (index, corpus) as ``qd_features``
    and ``stage2_arrays`` read them."""
    from types import SimpleNamespace
    rng = np.random.RandomState(2)
    n_docs = 4096
    # t0 absent, t1 df 1, t2 ends on the 1,024 edge, t3 crosses 2,048,
    # t4 and t5 share t3's last block, t6 spans three blocks, t7 df 3
    offsets = np.array([0, 0, 1, 1024, 2100, 2110, 2130, 5000, 5003])
    docs = np.concatenate([
        np.sort(rng.choice(n_docs, hi - lo, replace=False))
        for lo, hi in zip(offsets[:-1], offsets[1:])]).astype(np.int64)
    index = SimpleNamespace(
        offsets=offsets, docs=docs,
        bm25_score=(rng.random_sample(len(docs)) * 4 + 0.1)
        .astype(np.float32),
        doclen=rng.randint(8, 400, n_docs).astype(np.int32),
        df=np.diff(offsets).astype(np.int32))
    corpus = SimpleNamespace(
        doc_topics=rng.dirichlet(np.full(4, 0.3), n_docs).astype(np.float32))
    return index, corpus


# (Q, per-query term slots, whether each query's candidates carry -1 pads);
# each slot list is padded to 4 slots with masked term 0
_CSR_CASES = {
    "absent_term": (4, [[0, 1], [0], [0, 7, 0], [2, 0]], False),
    "df_one": (3, [[1], [1, 7], [7, 1, 2]], False),
    "range_ends_on_block_edge": (2, [[2], [2, 3]], False),
    "range_crosses_block_edge": (3, [[3], [1, 3], [3, 6]], False),
    "terms_share_block": (3, [[4, 5], [3, 4, 5], [5, 4, 3, 6]], False),
    "all_slots_masked": (3, [[], [4], []], False),
    "candidate_padding": (4, [[3, 4], [6], [2, 5, 7], [1]], True),
    "q_not_multiple_of_8": (11, [[t % 8, (3 * t + 1) % 8] for t in range(11)],
                            False),
}


@pytest.mark.parametrize("case", list(_CSR_CASES))
def test_qd_feature_gather_kernel_matches_loop(case):
    """The CSR-block kernel (interpret mode = the kernel program on CPU)
    gives the numpy ``qd_features`` loop's features bit for bit."""
    from repro.isn.backend import query_lane_budget
    index, corpus = _block_csr()
    q, slots, pad = _CSR_CASES[case]
    rng = np.random.RandomState(len(case))
    terms = np.zeros((q, 4), np.int32)
    mask = np.zeros((q, 4), np.float32)
    for i, row in enumerate(slots):
        terms[i, :len(row)] = row
        mask[i, :len(row)] = 1.0
    c = 20                                   # not a sublane multiple
    cand = rng.randint(0, 4096, (q, c))
    for i, row in enumerate(slots):          # plant hits in every range
        for j, t in enumerate(row):
            lo, hi = index.offsets[t], index.offsets[t + 1]
            if hi > lo:
                cand[i, 2 * j] = index.docs[rng.randint(lo, hi)]
                cand[i, 2 * j + 1] = index.docs[hi - 1]
    if pad:
        cand[:, c - 5:] = -1
        cand[0, :] = -1
    topics = rng.randint(0, 4, q).astype(np.int32)
    arrs = ranker.stage2_arrays(index, corpus)
    feats = np.asarray(ranker.qd_features_batched(
        arrs, jnp.asarray(terms), jnp.asarray(mask), jnp.asarray(topics),
        jnp.asarray(cand, jnp.int32),
        n_iter=ranker.csr_search_iters(int(index.df.max())),
        backend="interpret",
        qcap=query_lane_budget(index.df, terms, mask)))
    hits = 0
    for i in range(q):
        sel = cand[i] >= 0
        if not sel.any():
            continue
        ref = ranker.qd_features(index, corpus, terms[i], mask[i],
                                 topics[i], cand[i][sel])
        np.testing.assert_array_equal(feats[i][sel], ref)
        hits += int((ref[:, 2] > 0).sum())
    assert hits > 0 or case == "all_slots_masked"


@pytest.mark.parametrize("seed", range(4))
def test_step_budget_covers_lane_budget(seed):
    """Every batch that ``query_lane_budget`` sizes needs at most
    ``step_budget(qcap, L)`` steps, with ranges placed at block edges
    (where a range spans the most blocks); the live steps read each
    posting once, in term order, and dead steps re-read the last block."""
    import jax

    from repro.isn.backend import query_lane_budget
    from repro.kernels.qd_feature_gather.kernel import BLOCK
    from repro.kernels.qd_feature_gather.ops import csr_steps, step_budget
    steps = jax.jit(csr_steps, static_argnums=2)
    rng = np.random.RandomState(seed)
    q = 8
    for _ in range(150):
        n_slots = int(rng.choice([1, 3, 8]))
        df = np.where(rng.random_sample((q, n_slots)) < 0.2, 0,
                      rng.choice([1, 2, 1023, 1024, 1025, 3000],
                                 (q, n_slots)))
        mask = rng.random_sample((q, n_slots)) < 0.8
        lo = (rng.randint(1, 40, (q, n_slots)) * BLOCK
              + rng.choice([-1, 0, 1, -1023], (q, n_slots)))
        hi = np.where(mask, lo + df, lo)
        # query_lane_budget reads df through term ids: one id per slot
        qcap = query_lane_budget((hi - lo).reshape(-1),
                                 np.arange(q * n_slots).reshape(q, n_slots),
                                 np.ones((q, n_slots)))
        blk, s_lo, s_hi = (np.asarray(a) for a in steps(
            jnp.asarray(lo), jnp.asarray(hi), 64))
        live = s_lo < s_hi
        assert live.sum(axis=1).max() <= step_budget(qcap, n_slots) <= 64
        for i in range(q):
            n = int(live[i].sum())
            assert live[i, :n].all()
            got = np.concatenate([np.arange(b * BLOCK + a, b * BLOCK + z)
                                  for b, a, z in zip(blk[i, :n], s_lo[i, :n],
                                                     s_hi[i, :n])] + [[]])
            want = np.concatenate([np.arange(a, z) for a, z
                                   in zip(lo[i], hi[i])] + [[]])
            np.testing.assert_array_equal(got, want)
            if n:
                assert np.all(blk[i, n:] == blk[i, n - 1])


def test_rerank_batched_refuses_short_lane_budget(stage2, ltr_model):
    """A kernel-backend call whose qcap is under the batch's per-query
    posting total is refused, not served with dropped postings."""
    from repro.isn.backend import query_lane_budget
    corpus, index, ql, arrs, n_iter, cand = stage2
    need = int((index.df[ql.terms[:8]] * (ql.mask[:8] > 0)).sum(axis=1).max())
    assert query_lane_budget(index.df, ql.terms[:8], ql.mask[:8]) >= need
    with pytest.raises(ValueError, match="does not cover"):
        cascade.rerank_batched(arrs, ltr_model, ql.terms[:8], ql.mask[:8],
                               ql.topic[:8], cand[:8], np.full(8, 10),
                               n_iter=n_iter, backend="interpret",
                               qcap=need - 1)


def test_qd_features_interpret_backend_matches_jnp(stage2):
    """The kernel-backed featurizer agrees with the CSR binary-search path
    bit for bit, sums included."""
    corpus, index, ql, arrs, n_iter, cand = stage2
    q = 8
    terms = jnp.asarray(ql.terms[:q])
    mask = jnp.asarray(ql.mask[:q])
    topics = jnp.asarray(ql.topic[:q])
    cd = jnp.asarray(cand[:q], jnp.int32)
    from repro.isn.backend import query_lane_budget
    qcap = query_lane_budget(index.df, ql.terms[:q], ql.mask[:q])
    a = np.asarray(ranker.qd_features_batched(arrs, terms, mask, topics, cd,
                                              n_iter=n_iter,
                                              backend="interpret", qcap=qcap))
    b = np.asarray(ranker.qd_features_batched(arrs, terms, mask, topics, cd,
                                              n_iter=n_iter, backend="jnp"))
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# end-to-end: a one-shard system against the per-query references
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stage0_models(small_collection):
    corpus, index, ql = small_collection
    from repro.core import features as F
    x = np.asarray(F.extract(jnp.asarray(index.term_stats),
                             jnp.asarray(index.df),
                             jnp.asarray(ql.terms), jnp.asarray(ql.mask)))
    rng = np.random.RandomState(5)
    # cheap pseudo-labels: routing only needs plausible heavy-tailed targets
    eff_df = index.df[ql.terms] * (ql.mask > 0)
    base = eff_df.sum(axis=1).astype(np.float64)
    models = {}
    for name, scale, tau in (("k", 0.05, 0.55), ("rho", 0.5, 0.45),
                             ("t", 0.002, 0.5)):
        y = base * scale * np.exp(rng.randn(len(base)) * 0.3)
        models[name] = gbrt.fit(x, np.log1p(y.astype(np.float32)),
                                gbrt.GBRTParams(n_trees=24, depth=4,
                                                loss="quantile", tau=tau))
    return x, models


def test_pipeline_stage0_matches_per_model(small_collection, stage0_models,
                                           one_shard_system):
    corpus, index, ql = small_collection
    x, models = stage0_models
    cfg = SchedulerConfig(budget=100.0)
    pipe = one_shard_system(index, models, cfg)
    assert pipe._stacked is not None, "same-shaped ensembles must stack"
    pk, pr, pt = pipe.stage0(ql.terms, ql.mask)
    for name, got in (("k", pk), ("rho", pr), ("t", pt)):
        want = np.expm1(np.asarray(gbrt.predict(models[name],
                                                jnp.asarray(x))))
        np.testing.assert_array_equal(got, want)


def test_pipeline_full_cascade_matches_loop(small_collection, stage0_models,
                                            ltr_model, one_shard_system):
    """End-to-end: the pipeline's Stage-2 output equals running rerank_loop
    over the served Stage-1 candidates, and the cascade latency decomposes
    into the per-stage accounts."""
    corpus, index, ql = small_collection
    x, models = stage0_models
    cfg = SchedulerConfig(budget=100.0, rho_max=1 << 14)
    pipe = one_shard_system(index, models, cfg, corpus=corpus, ltr=ltr_model,
                            k_serve=64, t_final=10)
    res = pipe.serve(ql.terms, ql.mask, ql.topic)
    assert res.final is not None and res.final.shape == (96, 10)

    routed = pipe.sched.route(*pipe.stage0(ql.terms, ql.mask))
    k2 = np.minimum(routed.k, 64)
    ref = cascade.rerank_loop(index, corpus, ql, np.arange(96),
                              res.topk, k2, ltr_model, t_final=10)
    np.testing.assert_array_equal(res.final, ref.final)
    np.testing.assert_array_equal(res.candidates_used, ref.candidates_used)

    total = (res.stage_latency["stage0"] + res.stage_latency["stage1"]
             + res.stage_latency["stage2"])
    np.testing.assert_allclose(res.latency, total)
    assert set(res.stats["stages"]) == {"stage0", "stage1", "stage2"}
    # stage-2 cost follows the candidate count
    np.testing.assert_allclose(
        res.stage_latency["stage2"],
        pipe.cost.ltr_time(res.candidates_used))


def test_cascade_budget_reserves_stage2(small_collection, stage0_models,
                                        ltr_model, one_shard_system):
    """With an LTR model attached, the scheduler enforces Stage-1 against
    budget - Stage-0 prediction cost - worst-case Stage-2 cost, so the
    late-hedge guarantee covers the cascade; without an LTR model only the
    (unconditional) Stage-0 cost is reserved."""
    corpus, index, ql = small_collection
    x, models = stage0_models
    cfg = SchedulerConfig(budget=30.0, rho_max=1 << 14)
    pipe = one_shard_system(index, models, cfg, corpus=corpus, ltr=ltr_model,
                            k_serve=64)
    reserve = float(pipe.cost.ltr_time(np.asarray(64)))
    assert pipe.sched.cfg.budget == pytest.approx(
        30.0 - pipe.cost.predict_us - reserve)
    assert pipe.budget == 30.0                 # reporting uses the full budget
    plain = one_shard_system(index, models, cfg)
    assert plain.sched.cfg.budget == pytest.approx(
        30.0 - plain.cost.predict_us)
