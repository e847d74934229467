"""The top-k tie rule under a hostile ``lax.top_k``: equal scores go to the
lower doc id whatever order the backend's top-k gives equal keys.

The TPU's ``lax.top_k`` put a higher doc id ahead of an equal-scoring lower
one on some shapes.  ``hostile_top_k`` does that on every shape: it returns
the right values but the order of equal keys reversed.  JASS lists
(``saat_serve`` on the ``jnp`` and ``interpret`` backends), the tiled top-k
over integer tiles and the shard merge must still match the numpy oracles'
(score desc, doc id asc) order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.index.postings import shard_from_index
from repro.isn import oracle
from repro.isn.backend import merge_shard_topk
from repro.isn.saat import saat_serve
from repro.kernels.topk import packed_key_bits, topk_from_tiles

REAL_TOP_K = jax.lax.top_k


def hostile_top_k(x, k):
    """``lax.top_k`` with the order of equal keys reversed: among equal
    values the higher index comes first."""
    n = x.shape[-1]
    order = jnp.argsort(x[..., ::-1], axis=-1, descending=True,
                        stable=True)[..., :k]
    idx = (n - 1 - order).astype(jnp.int32)
    return jnp.take_along_axis(x, idx, axis=-1), idx


@pytest.fixture
def hostile(monkeypatch):
    """Every ``jax.lax.top_k`` traced inside the test is the hostile one;
    compiled programs are dropped before and after so none is reused."""
    jax.clear_caches()
    monkeypatch.setattr(jax.lax, "top_k", hostile_top_k)
    yield
    monkeypatch.setattr(jax.lax, "top_k", REAL_TOP_K)
    jax.clear_caches()


def lower_id_topk(acc: np.ndarray, k: int):
    """Row-wise (ids, scores): score desc, then doc id asc."""
    ids = np.lexsort((np.broadcast_to(np.arange(acc.shape[1]), acc.shape),
                      -acc), axis=1)[:, :k]
    return ids, np.take_along_axis(acc, ids, axis=1)


def test_hostile_top_k_reverses_ties(hostile):
    vals, idx = jax.lax.top_k(jnp.asarray([[1, 3, 1, 3, 1]]), 4)
    np.testing.assert_array_equal(np.asarray(vals), [[3, 3, 1, 1]])
    np.testing.assert_array_equal(np.asarray(idx), [[3, 1, 4, 2]])


@pytest.mark.parametrize("n_docs", [1000, 1024])
def test_int_tiles_with_planted_ties(hostile, n_docs):
    """Integer tiles drawn from 0..6 tie everywhere; the tiles overhang the
    shard when ``n_docs`` is not a multiple of the tile width."""
    rng = np.random.RandomState(n_docs)
    acc = rng.randint(0, 7, (8, n_docs)).astype(np.int32)
    tiles = np.zeros((8, 8 * 128), np.int32)
    tiles[:, :n_docs] = acc
    sc, ids = topk_from_tiles(jnp.asarray(tiles.reshape(8, 8, 128)), 40,
                              n_docs=n_docs, max_score=6)
    want_ids, want_sc = lower_id_topk(acc, 40)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    np.testing.assert_array_equal(np.asarray(sc), want_sc)


def test_packed_key_must_fit_int32():
    # 2,040 = 8 terms x 255: 2^20 doc ids fit, 2^21 do not
    assert packed_key_bits(1 << 20, 8 * 255) == 20
    with pytest.raises(ValueError, match="overflows int32"):
        packed_key_bits(1 << 21, 8 * 255)
    with pytest.raises(ValueError, match="max_score"):
        topk_from_tiles(jnp.zeros((1, 1, 128), jnp.int32), 4)


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_saat_serve_keeps_lower_id_on_ties(hostile, small_collection,
                                           backend):
    corpus, index, ql = small_collection
    s, spec = shard_from_index(index)
    rows = np.arange(8 if backend == "jnp" else 4)
    rho, k = 1500, 30
    res = saat_serve(s, jnp.asarray(ql.terms[rows]),
                     jnp.asarray(ql.mask[rows]), jnp.full(len(rows), rho),
                     n_docs=spec.n_docs, k=k, cap=rho, tile_d=spec.tile_d,
                     backend=backend)
    acc, _ = oracle.jass_scores(index, ql.terms, ql.mask, rows, rho)
    want_ids, want_sc = lower_id_topk(acc, k)
    # the lists hold ties, so the order among them is what is checked
    assert any(len(np.unique(r)) < k for r in want_sc)
    np.testing.assert_array_equal(np.asarray(res.topk_docs), want_ids)
    np.testing.assert_array_equal(np.asarray(res.topk_scores), want_sc)


@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_merge_shard_topk_keeps_lower_id_on_ties(hostile, dtype):
    """Three shards' ranked lists over a coarse score grid, some slots
    dropped: the merge equals the (score desc, global id asc) order."""
    rng = np.random.RandomState(5)
    q, k_s, k, shard_docs = 6, 10, 16, 32
    sc_list, id_list = [], []
    for s in range(3):
        acc = rng.randint(0, 4, (q, shard_docs)).astype(dtype)
        ids, sc = lower_id_topk(acc, k_s)
        sc_list.append(sc)
        id_list.append(ids + s * shard_docs)
    drop = np.zeros((3, q), bool)
    drop[1, ::2] = True
    ids, sc = merge_shard_topk(sc_list, id_list, k, drop=drop)
    for i in range(q):
        live = [s for s in range(3) if not drop[s, i]]
        a_sc = np.concatenate([sc_list[s][i] for s in live])
        a_id = np.concatenate([id_list[s][i] for s in live])
        order = np.lexsort((a_id, -a_sc))[:k]
        np.testing.assert_array_equal(np.asarray(ids)[i], a_id[order])
        np.testing.assert_array_equal(np.asarray(sc)[i], a_sc[order])
