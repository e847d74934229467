"""BMW's block-bounds kernel (``repro.kernels.block_bounds``) under the
Pallas interpreter: each query's block upper bounds are the float32 sums of
its live slots' block maxima in slot order, bit for bit as a numpy loop
over the slots computes them, and its candidate counts are exact; the jnp
backend's ``_block_bounds_batched`` agrees on the counts exactly and on the
bounds within float32 rounding.  Hand-made CSRs cover the shapes a walk
over whole 1,024-entry tiles can get wrong (ranges that straddle a tile, a
term with ``bcap`` entries, empty and masked slots, pad rows, 512 blocks
and a multiple of 1,024); real shards and a live-delta segment cover the
layout ``shard_from_index`` builds; and BMW served through the kernel
backend at ``bcap > 1024`` answers as the jnp backend does."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.index.builder import build_index
from repro.index.corpus import (CorpusParams, build_corpus, build_queries,
                                synthesize_feed_docs)
from repro.index.delta import DeltaStore
from repro.index.postings import IndexShard, shard_from_index
from repro.isn.daat import _block_bounds_batched, daat_serve
from repro.kernels.block_bounds.ops import (block_bounds, bound_steps,
                                            check_tables)


def _loop_bounds(off, bid, bmax, bcnt, terms, mask, n_blocks):
    """The reference: slot by slot, add each live term's block maxima and
    counts into its query's blocks in float32 / int32."""
    ub = np.zeros((len(terms), n_blocks), np.float32)
    cc = np.zeros((len(terms), n_blocks), np.int32)
    for w in range(len(terms)):
        for t, m in zip(terms[w], mask[w]):
            if m > 0:
                sl = slice(off[t], off[t + 1])
                ub[w, bid[sl]] += bmax[sl]
                cc[w, bid[sl]] += bcnt[sl]
    return ub, cc


def _csr(lists, n_blocks, seed):
    """A block-max CSR from per-term sorted block-id lists, maxima drawn
    over several binades (so all three bfloat16 parts are live), counts in
    1..64, padded to whole 1,024-entry tiles as ``shard_from_index`` pads."""
    rng = np.random.default_rng(seed)
    off = np.r_[0, np.cumsum([len(x) for x in lists])].astype(np.int32)
    bid = np.concatenate(lists).astype(np.int32)
    assert bid.size == 0 or bid.max() < n_blocks
    bmax = (rng.random(len(bid)) * 2.0 ** rng.integers(-4, 5, len(bid))
            ).astype(np.float32)
    bcnt = rng.integers(1, 65, len(bid)).astype(np.int32)
    n = -(-max(len(bid), 1) // 1024) * 1024
    pad = [np.r_[a, np.zeros(n - len(a), a.dtype)] for a in (bid, bmax, bcnt)]
    return off, *pad


def _shard_of(off, bid, bmax, bcnt):
    """The CSR as the fields of an ``IndexShard`` (the bounds read only
    these)."""
    arrs = {f: None for f in IndexShard._fields}
    arrs.update(bm_offsets=jnp.asarray(off), bm_block_id=jnp.asarray(bid),
                bm_block_max=jnp.asarray(bmax),
                bm_block_cnt=jnp.asarray(bcnt))
    return IndexShard(**arrs)


def _check(shard, terms, mask, n_blocks, bcap):
    """Kernel against the numpy loop (bit for bit) and against the jnp
    backend's bounds (counts exact, bounds to float32 rounding)."""
    terms, mask = np.asarray(terms, np.int32), np.asarray(mask, np.float32)
    ub, cc = block_bounds(shard.bm_offsets, shard.bm_block_id,
                          shard.bm_block_max, shard.bm_block_cnt,
                          jnp.asarray(terms), jnp.asarray(mask),
                          n_blocks=n_blocks, bcap=bcap, interpret=True)
    want_ub, want_cc = _loop_bounds(
        *(np.asarray(x) for x in (shard.bm_offsets, shard.bm_block_id,
                                  shard.bm_block_max, shard.bm_block_cnt)),
        terms, mask, n_blocks)
    np.testing.assert_array_equal(np.asarray(ub), want_ub)
    np.testing.assert_array_equal(np.asarray(cc), want_cc)
    x_ub, x_cc = _block_bounds_batched(shard, jnp.asarray(terms),
                                       jnp.asarray(mask), n_blocks, bcap)
    np.testing.assert_array_equal(np.asarray(x_cc), want_cc)
    np.testing.assert_allclose(np.asarray(x_ub), want_ub, rtol=4e-7 * 8,
                               atol=0)
    return want_ub, want_cc


@pytest.mark.parametrize("n_blocks", [512, 2048])
def test_bounds_on_hand_made_csr(n_blocks):
    """Term 0 holds every block (``cnt == bcap``); term 1 is filler that
    puts term 2's 48 entries across a tile boundary; term 3 is empty; term
    4 holds one block, the last; terms 5..10 are sparse and spread."""
    rng = np.random.default_rng(n_blocks)
    start = 1024 * -(-(n_blocks + 25) // 1024) - 24
    lists = [np.arange(n_blocks),
             np.sort(rng.choice(n_blocks, start - n_blocks, replace=False)),
             np.sort(rng.choice(n_blocks, 48, replace=False)),
             np.zeros(0, np.int64), np.array([n_blocks - 1])]
    lists += [np.sort(rng.choice(n_blocks, c, replace=False))
              for c in (1, 3, 40, 129, 200, n_blocks // 2)]
    csr = _csr(lists, n_blocks, seed=n_blocks + 1)
    off = csr[0]
    assert off[2] % 1024 == 1000 and off[3] - off[2] == 48
    shard = _shard_of(*csr)
    terms = np.array([[0, 1, 2, 3, 4, 5, 6, 7],
                      [2, 0, 10, 9, 3, 1, 4, 8],
                      [4, 5, 6, 7, 8, 9, 10, 2],
                      [0, 1, 2, 3, 4, 5, 6, 7],     # a pad row
                      [10, 9, 8, 7, 6, 5, 4, 3]])
    mask = np.ones(terms.shape, np.float32)
    mask[1, 4:] = 0.0                               # masked slots
    mask[2, [0, 3, 6]] = 0.0
    mask[3] = 0.0
    ub, cc = _check(shard, terms, mask, n_blocks, n_blocks)
    assert not ub[3].any() and not cc[3].any()
    assert (cc[0] > 0).all()                        # term 0 everywhere


def test_bounds_of_empty_and_all_masked_queries():
    """A batch with nothing live: every step dead, all bounds zero."""
    shard = _shard_of(*_csr([np.zeros(0, np.int64), np.arange(5)], 512, 0))
    terms = np.array([[0, 0, 0, 0, 0, 0, 0, 0], [1, 0, 1, 0, 1, 0, 1, 0]])
    mask = np.array([[1] * 8, [0] * 8], np.float32)
    ub, cc = _check(shard, terms, mask, 512, 5)
    assert not ub.any() and not cc.any()


def test_bounds_on_shard_from_index(small_collection):
    """The shard's own CSR, whole and as a doc-range half, over real
    queries (every slot layout the query builder emits)."""
    _, index, ql = small_collection
    for lo, hi in ((0, index.n_docs), (index.n_docs // 2, index.n_docs)):
        shard, spec = shard_from_index(index, lo, hi)
        check_tables(shard.bm_block_id)
        _check(shard, ql.terms[:16], ql.mask[:16], spec.n_blocks,
               spec.max_blocks_per_term)


def test_bounds_on_a_live_delta_segment(small_collection):
    """The delta's capacity-padded CSR at its own spec's caps (``bcap`` =
    its capacity's blocks): the capacity pad is rounded up to whole
    tiles and stays inert."""
    corpus, index, ql = small_collection
    delta = DeltaStore(index, capacity_docs=128, capacity_postings=3000)
    assert delta.add(synthesize_feed_docs(corpus, 40, seed=5)) > 0
    shard, spec = delta.segment()
    assert spec.n_block_entries == 3072
    _check(shard, ql.terms[:16], ql.mask[:16], spec.n_blocks,
           spec.max_blocks_per_term)


def test_tables_must_be_whole_tiles():
    with pytest.raises(ValueError, match="whole 1024-entry tiles"):
        check_tables(jnp.zeros(1000, jnp.int32))
    assert bound_steps(8, 512) == 16 and bound_steps(8, 4096) == 40
    assert bound_steps(2, 1025) == 6


@pytest.fixture(scope="module")
def deep_shard():
    """A shard whose densest terms hold more than 1,024 of its 2,048
    doc blocks (4-doc blocks over 8,192 docs)."""
    corpus = build_corpus(CorpusParams(n_docs=8192, vocab=2048,
                                       avg_doclen=40, zipf_a=1.05, seed=9))
    index = build_index(corpus, block_size=4, stop_k=8)
    shard, spec = shard_from_index(index)
    ql = build_queries(corpus, 8, stop_k=8, seed=13)
    return shard, spec, ql


def test_deep_shard_bounds(deep_shard):
    shard, spec, ql = deep_shard
    assert spec.max_blocks_per_term > 1024
    dense = int(np.argmax(np.diff(np.asarray(shard.bm_offsets))))
    terms = np.array(ql.terms, np.int32)
    terms[:, 0] = dense                         # every query holds it
    _check(shard, terms, np.maximum(ql.mask, terms == dense), spec.n_blocks,
           spec.max_blocks_per_term)


def test_daat_kernel_backend_at_deep_bcap(deep_shard):
    """BMW through the kernel backend (bounds kernel and scoring kernel
    under the interpreter) against the jnp backend at ``bcap > 1024``:
    the same top-k ids, scores, surviving blocks and work."""
    shard, spec, ql = deep_shard
    dense = int(np.argmax(np.diff(np.asarray(shard.bm_offsets))))
    terms = np.array(ql.terms, np.int32)
    rows = [i for i in range(4) if dense not in terms[i]]
    terms[rows, 0] = dense                      # query terms stay unique
    mask = np.maximum(ql.mask, terms == dense).astype(np.float32)
    assert (mask * (terms == dense)).sum() >= 4
    kw = dict(n_docs=spec.n_docs, n_blocks=spec.n_blocks,
              block_size=spec.block_size, k=20, cap=spec.max_df,
              bcap=spec.max_blocks_per_term, tile_d=spec.tile_d)
    args = (shard, jnp.asarray(terms), jnp.asarray(mask),
            jnp.ones(len(terms), jnp.float32))
    a = daat_serve(*args, backend="interpret", **kw)
    b = daat_serve(*args, backend="jnp", **kw)
    np.testing.assert_array_equal(np.asarray(a.topk_docs),
                                  np.asarray(b.topk_docs))
    np.testing.assert_allclose(np.asarray(a.topk_scores),
                               np.asarray(b.topk_scores), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(a.blocks), np.asarray(b.blocks))
    np.testing.assert_array_equal(np.asarray(a.work), np.asarray(b.work))
