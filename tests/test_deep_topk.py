"""Stage-1 deeper than one accumulator tile: the deep top-k select against
numpy's exact top-k, and a served ``SearchSystem`` at ``k_serve`` above
``tile_d`` against the numpy oracles under Algorithm 2.

Integer tiles must give numpy's (score desc, doc id asc) list id for id,
planted ties included; float tiles the same score vector.  The clustered
inputs put the whole top k into a few of the select's groups, the case a
group-maxima select must not lose."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.index.builder import build_index
from repro.index.corpus import CorpusParams, build_corpus, build_queries
from repro.isn import oracle
from repro.isn.backend import deep_select_ran
from repro.kernels.topk import (GROUP, SPAN, deep_topk_select,
                                takes_deep_select, topk_from_tiles)
from repro.serving.spec import (BackendSpec, CascadeSpec, DeploySpec,
                                OnlineSpec, RoutingSpec, Stage2Spec)
from repro.serving.system import build_system

TILE_D = 128


def lower_id_topk(acc: np.ndarray, k: int):
    ids = np.lexsort((np.broadcast_to(np.arange(acc.shape[1]), acc.shape),
                      -acc), axis=1)[:, :k]
    return ids, np.take_along_axis(acc, ids, axis=1)


def _tiles(acc: np.ndarray):
    q, n = acc.shape
    t = -(-n // TILE_D)
    t += (-t) % 8
    out = np.zeros((q, t * TILE_D), acc.dtype)
    out[:, :n] = acc
    return jnp.asarray(out.reshape(q, t, TILE_D))


def _draw(kind: str, n: int, k: int, dtype, seed: int) -> np.ndarray:
    rng = np.random.RandomState(seed)
    if kind == "ties":
        # scores 0..9: every list is mostly ties
        acc = rng.randint(0, 10, (3, n))
    else:
        # clustered: one 1,024-doc range (one tile group) outranks the
        # rest, so the top k share few groups of every level
        acc = rng.randint(0, 4, (3, n))
        lo = int(rng.randint(0, n // 1024)) * 1024
        acc[:, lo:lo + 1024] += 50 + rng.randint(0, 3, (3, 1024))
    return acc.astype(dtype)


@pytest.mark.parametrize("kind", ["ties", "clustered"])
@pytest.mark.parametrize("n,k", [(1 << 14, TILE_D + 1), (1 << 14, 300),
                                 (1 << 14, 1000), ((1 << 14) - 37, 1000),
                                 (1 << 16, 300)])
def test_deep_select_int_keys_exact(kind, n, k):
    acc = _draw(kind, n, k, np.int32, n + k)
    sc, ids = topk_from_tiles(_tiles(acc), k, n_docs=n, max_score=60)
    want_ids, want_sc = lower_id_topk(acc, k)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    np.testing.assert_array_equal(np.asarray(sc), want_sc)


@pytest.mark.parametrize("kind", ["ties", "clustered"])
@pytest.mark.parametrize("n,k", [(1 << 14, TILE_D + 1), (1 << 14, 300),
                                 (1 << 14, 1000), ((1 << 14) - 37, 1000)])
def test_deep_select_float_scores_exact(kind, n, k):
    acc = _draw(kind, n, k, np.float32, n + 2 * k) + np.float32(0.25)
    sc, ids = topk_from_tiles(_tiles(acc), k, n_docs=n)
    ref, _ = jax.lax.top_k(jnp.asarray(acc), k)
    np.testing.assert_array_equal(np.asarray(sc), np.asarray(ref))
    ids = np.asarray(ids)
    assert ids.max() < n and all(len(set(r)) == k for r in ids.tolist())
    np.testing.assert_array_equal(np.take_along_axis(acc, ids, axis=1),
                                  np.asarray(sc))


@pytest.mark.parametrize("n", [SPAN, SPAN + 37, 3 * SPAN - 1])
def test_deep_topk_select_pads_a_ragged_row(n):
    """Rows of any length from ``GROUP · k`` up: the pad to whole level
    steps is never selected, and the (value, position) pairs are the
    exact top k of distinct keys, negative keys included."""
    k = n // GROUP
    rng = np.random.RandomState(n)
    x = np.stack([rng.permutation(n) - n // 2 for _ in range(3)])
    vals, pos = deep_topk_select(jnp.asarray(x, jnp.int32), k)
    want = np.argsort(-x, axis=1)[:, :k]
    np.testing.assert_array_equal(np.asarray(pos), want)
    np.testing.assert_array_equal(np.asarray(vals),
                                  np.take_along_axis(x, want, axis=1))


class _Spec:
    def __init__(self, n_tiles, tile_d=TILE_D):
        self.spec = type("spec", (), {"n_tiles": n_tiles, "tile_d": tile_d})


@pytest.mark.parametrize("backend", ["pallas", "interpret", "jnp"])
@pytest.mark.parametrize("k,n_tiles,deep", [
    (TILE_D, 64, False),             # within one tile
    (TILE_D + 1, 64, True),
    (1000, 62, False),               # a row below GROUP * k entries
    (1000, 63, True),
])
def test_deep_select_decision(k, n_tiles, deep, backend):
    """The engines report the deep select exactly where ``topk_from_tiles``
    takes it: a kernel backend (the ``jnp`` engines rank whole rows),
    deeper than a tile, a row of at least ``GROUP · k`` entries in some
    segment."""
    assert takes_deep_select(k, TILE_D, n_tiles * TILE_D) == deep
    want = deep and backend != "jnp"
    assert deep_select_ran([_Spec(1), _Spec(n_tiles)], k, backend) == want
    assert not deep_select_ran([_Spec(1)], k, backend)


# ---------------------------------------------------------------------------
# a served system deeper than one tile
# ---------------------------------------------------------------------------

K_SERVE = 300


@pytest.fixture(scope="module")
def deep_systems():
    """One fitted Algorithm-2 system at k_serve 300 over 8,192 docs (64
    tiles, the fewest the level pass groups), on the jnp backend and in
    the Pallas interpreter."""
    corpus = build_corpus(CorpusParams(n_docs=8192, vocab=2048,
                                       avg_doclen=60, zipf_a=1.05, seed=5))
    index = build_index(corpus, stop_k=8)
    ql = build_queries(corpus, 64, stop_k=8, seed=13)
    spec = CascadeSpec(
        routing=RoutingSpec(algorithm=2, budget=200.0, rho_max=1 << 12,
                            calibrate=True, enable_hedging=False),
        stage2=Stage2Spec(enabled=True, k_serve=K_SERVE, t_final=10),
        backend=BackendSpec(backend="jnp"),
        deploy=DeploySpec(n_shards=1, replicas=2),
        online=OnlineSpec(max_batch=8), name="deep")
    fitted = build_system(spec, index, corpus=corpus).fit(ql, None, seed=5)
    out = {}
    for backend in ("jnp", "interpret"):
        # the fitted spec holds the calibrated routing thresholds
        s = dataclasses.replace(fitted.cascade_spec,
                                backend=BackendSpec(backend=backend))
        out[backend] = build_system(s, index, corpus=corpus,
                                    models=fitted.models, ltr=fitted.ltr)
    return index, ql, out


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_served_deep_lists_match_the_oracles(deep_systems, backend):
    index, ql, systems = deep_systems
    sysm = systems[backend]
    # four queries Algorithm 2 sends to each engine
    every = sysm.sched.route(*sysm.stage0(ql.terms, ql.mask))
    rows = np.r_[every.jass_rows[:4], every.bmw_rows[:4]]
    terms, mask = ql.terms[rows], ql.mask[rows]
    routed = sysm.sched.route(*sysm.stage0(terms, mask))
    assert len(routed.jass_rows) == len(routed.bmw_rows) == 4
    before = sysm.stats()["stage1"]["deep_select_rows"]
    topk, topk_sc, _, _ = sysm.stage1(terms, mask, routed)
    deep = sysm.stats()["stage1"]["deep_select_rows"] - before
    assert deep == (len(rows) if backend == "interpret" else 0)
    for i in routed.jass_rows:
        acc, _ = oracle.jass_scores(index, terms, mask, [i],
                                    [routed.rho[i]])
        want_ids, want_sc = lower_id_topk(acc, K_SERVE)
        np.testing.assert_array_equal(topk[i], want_ids[0])
        np.testing.assert_array_equal(topk_sc[i], want_sc[0])
    acc, _ = oracle.exhaustive_scores(index, terms, mask, routed.bmw_rows)
    want = -np.sort(-acc, axis=1)[:, :K_SERVE]
    np.testing.assert_allclose(topk_sc[routed.bmw_rows], want, rtol=1e-5,
                               atol=1e-5)
    # the served ids carry the scores served
    got = np.take_along_axis(acc, topk[routed.bmw_rows], axis=1)
    np.testing.assert_allclose(got, topk_sc[routed.bmw_rows], rtol=1e-5,
                               atol=1e-5)


def test_served_deep_cascade_reranks_past_a_tile(deep_systems):
    """End to end on both backends: Stage-2 re-ranks more than one tile's
    worth of candidates, as many on either backend, and serves its top
    10 from them."""
    index, ql, systems = deep_systems
    res = [systems[b].serve(ql.terms[:8], ql.mask[:8], ql.topic[:8])
           for b in ("jnp", "interpret")]
    assert res[0].candidates_used.max() > TILE_D
    np.testing.assert_array_equal(res[0].candidates_used,
                                  res[1].candidates_used)
    for r in res:
        for i, used in enumerate(r.candidates_used):
            assert set(r.final[i][r.final[i] >= 0]) <= set(r.topk[i][:used])
