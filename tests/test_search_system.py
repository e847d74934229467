"""SearchSystem / CascadeSpec suite: spec JSON round-trip, the preset
registry, multi-shard scatter-gather parity vs the single-shard pipeline,
and replica-pool integration.
"""

import dataclasses
import json

import numpy as np
import pytest

from repro.configs.cascade_presets import PRESETS, get_preset
from repro.serving.spec import (BackendSpec, CascadeSpec, DeploySpec,
                                IndexSpec, RoutingSpec, Stage0Spec,
                                Stage2Spec)
from repro.serving.system import build_system


# ---------------------------------------------------------------------------
# spec serialization + validation
# ---------------------------------------------------------------------------

def test_spec_json_round_trip():
    spec = CascadeSpec(
        index=IndexSpec(block_size=32, stop_k=8, tile_d=64),
        stage0=Stage0Spec(n_trees=24, depth=4, tau_k=0.6),
        routing=RoutingSpec(algorithm=1, budget=88.5, rho_max=1 << 15,
                            enable_hedging=False, calibrate=True),
        stage2=Stage2Spec(enabled=False, k_serve=96, t_final=7),
        backend=BackendSpec(backend="jnp", cost="v5e_shard"),
        deploy=DeploySpec(n_shards=3, replicas=4, jass_fraction=0.25,
                          rebalance_every=2, seed=9),
        name="round_trip",
    )
    again = CascadeSpec.from_json(spec.to_json())
    assert again == spec
    # the wire format is JSON-plain and versioned
    d = json.loads(spec.to_json())
    assert d["version"] == 1
    assert d["deploy"]["n_shards"] == 3


def test_spec_validation_rejects_bad_values():
    with pytest.raises(ValueError):
        CascadeSpec(routing=RoutingSpec(algorithm=3)).validate()
    with pytest.raises(ValueError):
        CascadeSpec(deploy=DeploySpec(n_shards=0)).validate()
    with pytest.raises(ValueError):
        CascadeSpec(backend=BackendSpec(backend="cuda")).validate()
    with pytest.raises(ValueError):
        CascadeSpec(index=IndexSpec(block_size=48, tile_d=128)).validate()


def test_preset_registry_complete():
    assert set(PRESETS) == {"paper_200ms", "throughput", "quality",
                            "stage1_only", "fault_tolerant", "cached",
                            "live_ingest", "hybrid_fusion",
                            "msmarco_passage"}
    for name in PRESETS:
        spec = get_preset(name)
        assert spec.name == name
        assert spec == CascadeSpec.from_json(spec.to_json())
    assert get_preset("stage1_only").stage2.enabled is False
    assert get_preset("throughput").routing.enable_hedging is False
    assert (get_preset("quality").stage2.k_serve
            > get_preset("throughput").stage2.k_serve)
    marco = get_preset("msmarco_passage")
    assert (marco.stage2.k_serve, marco.stage2.t_final) == (1000, 10)
    assert marco.routing.algorithm == 2 and marco.routing.budget == 200.0
    assert marco.online.max_batch == 32
    with pytest.raises(ValueError):
        get_preset("no_such_preset")
    # overrides replace whole nodes and re-validate
    spec = get_preset("paper_200ms", deploy=DeploySpec(n_shards=4))
    assert spec.deploy.n_shards == 4


# ---------------------------------------------------------------------------
# system construction + multi-shard parity
# ---------------------------------------------------------------------------

def _spec(n_shards, t_k=150.0, t_time=18.0, replicas=2, **kw):
    return CascadeSpec(
        routing=RoutingSpec(budget=100.0, rho_max=1 << 14, t_k=t_k,
                            t_time=t_time),
        stage2=Stage2Spec(enabled=True, k_serve=64, t_final=10),
        backend=BackendSpec(backend="jnp"),
        deploy=DeploySpec(n_shards=n_shards, replicas=replicas, **kw),
        name=f"test_{n_shards}shard",
    )


@pytest.fixture(scope="module")
def fitted(small_collection):
    """A fitted single-shard system plus the calibrated routing thresholds
    every sharded comparison system reuses (identical routing is what makes
    the parity bit-exact)."""
    corpus, index, ql = small_collection
    spec = dataclasses.replace(
        _spec(1), routing=RoutingSpec(budget=100.0, rho_max=1 << 14,
                                      calibrate=True))
    system = build_system(spec, index, corpus=corpus)
    system.fit(ql, None, seed=5)
    thresholds = (system._base_cfg.t_k, system._base_cfg.t_time)
    return corpus, index, ql, system, thresholds


def test_build_system_from_corpus_matches_index(small_collection):
    """Building from the corpus reproduces the prebuilt index layout."""
    corpus, index, ql = small_collection
    spec = dataclasses.replace(
        _spec(1), index=IndexSpec(stop_k=8), stage2=Stage2Spec(enabled=False))
    system = build_system(spec, corpus)
    assert system.index.n_docs == index.n_docs
    np.testing.assert_array_equal(system.index.df, index.df)
    with pytest.raises(TypeError):
        build_system(spec, "not a corpus")


def test_fit_trains_all_stages(fitted):
    corpus, index, ql, system, _ = fitted
    assert set(system.models) == {"k", "rho", "t"}
    assert system._stacked is not None
    assert system.ltr is not None
    pk, pr, pt = system.stage0(ql.terms, ql.mask)
    assert pk.shape == (len(ql.terms),) and np.isfinite(pk).all()


@pytest.mark.parametrize("n_shards", [1, 3])
def test_multi_shard_topk_parity(fitted, n_shards):
    """n-shard scatter-gather == single-shard top-k, final lists and
    candidate counts, bit for bit on the jnp backend (documented merge
    tie-break: lower global doc id on score ties)."""
    corpus, index, ql, system, (tk, tt) = fitted
    sharded = build_system(_spec(n_shards, tk, tt), index, corpus=corpus,
                           models=system.models, ltr=system.ltr)
    assert sharded.n_shards == n_shards
    assert sum(sp.n_docs for sp in sharded.shard_specs) == index.n_docs
    a = system.serve(ql.terms, ql.mask, ql.topic)
    b = sharded.serve(ql.terms, ql.mask, ql.topic)
    # both pools must be exercised for this to mean anything
    assert b.stats["jass"] > 0 and b.stats["bmw"] > 0
    np.testing.assert_array_equal(a.topk, b.topk)
    np.testing.assert_array_equal(a.final, b.final)
    np.testing.assert_array_equal(a.candidates_used, b.candidates_used)


def test_multi_shard_tail_is_scatter_gather_max(fitted):
    """Sharding must not increase any query's modeled Stage-1 time, and the
    slowest query must strictly improve (the max-over-shards tail)."""
    corpus, index, ql, system, (tk, tt) = fitted
    sharded = build_system(_spec(3, tk, tt), index, corpus=corpus,
                           models=system.models, ltr=system.ltr)
    a = system.serve(ql.terms, ql.mask, ql.topic)
    b = sharded.serve(ql.terms, ql.mask, ql.topic)
    assert np.all(b.stage_latency["stage1"]
                  <= a.stage_latency["stage1"] + 1e-9)
    assert b.stage_latency["stage1"].max() < a.stage_latency["stage1"].max()


def test_spec_round_trip_builds_identical_system(fitted):
    """build_system(from_json(to_json(spec))) serves bit-identical results."""
    corpus, index, ql, system, _ = fitted
    spec2 = CascadeSpec.from_json(system.cascade_spec.to_json())
    system2 = build_system(spec2, index, corpus=corpus,
                           models=system.models, ltr=system.ltr)
    a = system.serve(ql.terms, ql.mask, ql.topic)
    b = system2.serve(ql.terms, ql.mask, ql.topic)
    np.testing.assert_array_equal(a.topk, b.topk)
    np.testing.assert_array_equal(a.final, b.final)
    np.testing.assert_allclose(a.latency, b.latency)


def test_k_serve_must_fit_smallest_shard(small_collection):
    corpus, index, ql = small_collection
    spec = dataclasses.replace(
        _spec(64), stage2=Stage2Spec(enabled=True, k_serve=128))
    with pytest.raises(ValueError, match="smallest shard"):
        build_system(spec, index, corpus=corpus)


# ---------------------------------------------------------------------------
# replica pool integration
# ---------------------------------------------------------------------------

def test_pool_fed_by_serving_and_stats_surface(fitted, small_collection):
    corpus, index, ql = small_collection
    _, _, _, system, (tk, tt) = fitted
    sharded = build_system(_spec(3, tk, tt, rebalance_every=1), index,
                           corpus=corpus, models=system.models,
                           ltr=system.ltr)
    res = sharded.serve(ql.terms, ql.mask, ql.topic)
    st = sharded.stats()
    pool = st["pool"]
    # every query occupied one replica of every partition, and observed
    # latencies fed the EWMA estimates back
    assert pool["served"] >= len(ql.terms) * 3
    assert pool["max_inflight"] == 0                 # all completed
    assert any(v is not None for v in pool["ewma_latency"].values())
    assert res.stats["pool"]["served"] == pool["served"]
    assert st["n_shards"] == 3 and len(st["shard_docs"]) == 3
    assert st["batches"] == 1
    assert "last_batch" in st and "p99" in st["last_batch"]


def test_rebalance_exercised_by_cascade_run(fitted, small_collection):
    """With a JASS/BMW-skewed routing mix, serving itself re-splits the
    mirror ratio toward the observed mix (not only tests/test_replicas)."""
    corpus, index, ql = small_collection
    _, _, _, system, _ = fitted
    spec = dataclasses.replace(
        _spec(2, replicas=4, rebalance_every=1),
        routing=RoutingSpec(budget=100.0, rho_max=1 << 14, t_k=0.0,
                            t_time=0.0))   # pred_k > 0 routes all to JASS
    sharded = build_system(spec, index, corpus=corpus, models=system.models,
                           ltr=system.ltr)
    assert sharded.pool.stats()["jass_fraction"] == 0.5
    res = sharded.serve(ql.terms, ql.mask, ql.topic)
    assert res.stats["jass"] == len(ql.terms)
    # observed mix 100% JASS -> split clipped to the 0.8 ceiling = 3/4
    assert sharded.pool.stats()["jass_fraction"] == pytest.approx(0.75)
