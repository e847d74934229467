"""End-to-end behaviour of the paper's system on a small collection:
labels → Stage-0 predictors → hybrid routing → budget guarantee +
effectiveness parity (the paper's Tables 3/4 in miniature)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import features as F
from repro.core import gbrt
from repro.core.labels import LabelConfig, generate_labels
from repro.core.reference import rbp_weights
from repro.isn import oracle
from repro.serving.latency import CostModel
from repro.serving.scheduler import SchedulerConfig


@pytest.fixture(scope="module")
def pipeline(small_collection):
    corpus, index, ql = small_collection
    labels = generate_labels(index, corpus, ql,
                             LabelConfig(max_k=1024, batch=96,
                                         rho_grid=(256, 512, 1024, 2048,
                                                   4096, 8192, 16384)))
    x = np.asarray(F.extract(jnp.asarray(index.term_stats),
                             jnp.asarray(index.df),
                             jnp.asarray(ql.terms), jnp.asarray(ql.mask)))
    return corpus, index, ql, labels, x


def test_labels_sane(pipeline):
    corpus, index, ql, labels, x = pipeline
    assert labels.oracle_k.min() >= 1
    assert labels.oracle_rho.min() >= 256
    assert np.isfinite(labels.t_bmw).all()
    # skew: heavy-tailed k distribution (mean > median), paper Fig. 2
    k = labels.oracle_k[labels.keep]
    assert k.mean() >= np.median(k)


def test_oracle_k_achieves_eps(pipeline):
    """Re-ranking the top-oracle_k candidates recovers the reference list up
    to the MED target (the defining property of the label)."""
    corpus, index, ql, labels, x = pipeline
    cfg = LabelConfig(max_k=1024)
    rows = np.arange(24)
    acc, _ = oracle.exhaustive_scores(index, ql.terms, ql.mask, rows)
    ranks = oracle.ranks_of(acc, labels.ref_lists[rows], cfg.max_k)
    w = np.asarray(rbp_weights(cfg.ref_depth, cfg.rbp_p))
    for i, q in enumerate(rows):
        if not labels.keep[q] or labels.oracle_k[q] >= cfg.max_k:
            continue
        med = w[ranks[i] >= labels.oracle_k[q]].sum()
        assert med <= cfg.eps + 1e-9


def test_end_to_end_budget_guarantee(pipeline, one_shard_system):
    """The hybrid system must keep (almost) every query under budget while a
    fixed exhaustive BMW system does not — the paper's headline claim."""
    corpus, index, ql, labels, x = pipeline
    keep = labels.keep
    models = {}
    for name, y, tau in (("k", labels.oracle_k, 0.55),
                         ("rho", labels.oracle_rho, 0.45),
                         ("t", labels.t_bmw, 0.5)):
        models[name] = gbrt.fit(x[keep], np.log1p(y[keep].astype(np.float32)),
                                gbrt.GBRTParams(n_trees=24, depth=4,
                                                loss="quantile", tau=tau))
    cost = CostModel.paper_scale()
    budget = float(np.percentile(labels.t_bmw[keep], 85))
    cfg = SchedulerConfig(algorithm=2, budget=budget, rho_max=1 << 14,
                          t_time=budget * 0.6, t_k=float(
                              np.median(labels.oracle_k[keep])))
    server = one_shard_system(index, models, cfg, cost=cost)
    res = server.serve(ql.terms, ql.mask)
    frac_over_hybrid = np.mean(res.latency > budget)
    frac_over_bmw = np.mean(labels.t_bmw > budget)
    assert frac_over_hybrid < frac_over_bmw
    assert frac_over_hybrid <= 0.05
    # both pools actually used
    assert res.stats["jass"] > 0 and res.stats["bmw"] > 0


def test_features_finite_and_shaped(pipeline):
    corpus, index, ql, labels, x = pipeline
    assert x.shape == (len(ql.terms), F.N_FEATURES)
    assert np.isfinite(x).all()
    names = F.feature_names()
    assert len(names) == F.N_FEATURES == 147
