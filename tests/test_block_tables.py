"""The index's block-max tables as one sparse term-major CSR: it holds, at
every (term, block), what a dense (terms x blocks) table built from the
postings holds; the shard's device CSR keeps the exact float block maxima
whether it reuses the index's CSR (a whole-index shard) or derives its own
(a doc-range shard); and the numpy BMW oracle and the dry-run's block
counts read the same numbers the dense tables gave."""

import numpy as np
import pytest

from repro.index.postings import shard_from_index
from repro.isn import oracle
from repro.launch.dryrun_cascade import WorkProxies
from repro.configs.cascade_presets import get_preset


def _terms(index):
    return np.repeat(np.arange(index.vocab), np.diff(index.offsets))


def _dense(index, values, how):
    """(V, n_blocks) table of ``how`` (np.maximum or np.add) of ``values``
    over each (term, block)'s postings, 0 where a term has none."""
    out = np.zeros((index.vocab, index.n_blocks), np.float64)
    how.at(out, (_terms(index), index.docs // index.block_size), values)
    return out


def _csr_dense(index, values):
    out = np.zeros((index.vocab, index.n_blocks), np.float64)
    term = np.repeat(np.arange(index.vocab), np.diff(index.bm_offsets))
    out[term, index.bm_block] = values
    return out


def test_block_csr_equals_dense_tables(small_collection):
    _, index, _ = small_collection
    np.testing.assert_array_equal(
        _csr_dense(index, index.bm_max),
        _dense(index, index.impact.astype(np.float64), np.maximum))
    count = _dense(index, np.ones(index.n_postings), np.add)
    np.testing.assert_array_equal(_csr_dense(index, index.bm_count), count)
    # one entry per (term, block) that holds postings, blocks ascending
    assert len(index.bm_block) == int((count > 0).sum())
    for t in range(index.vocab):
        row = index.bm_block[index.bm_offsets[t]:index.bm_offsets[t + 1]]
        assert np.all(np.diff(row) > 0)


@pytest.mark.parametrize("part", [(0, 1), (0, 2), (1, 2)])
def test_shard_block_maxima_are_exact_scores(small_collection, part):
    """Whole-index shards reuse the index's CSR, halves derive theirs:
    either way each (term, local block) carries its largest float score
    and its posting count."""
    _, index, _ = small_collection
    n = index.n_docs
    lo, hi = part[0] * n // part[1], (part[0] + 1) * n // part[1]
    shard, spec = shard_from_index(index, lo, hi)
    sel = (index.docs >= lo) & (index.docs < hi)
    term, blk = _terms(index)[sel], (index.docs[sel] - lo) // spec.block_size
    want_max = np.zeros((index.vocab, spec.n_blocks), np.float32)
    np.maximum.at(want_max, (term, blk), index.bm25_score[sel])
    want_cnt = np.zeros((index.vocab, spec.n_blocks), np.int64)
    np.add.at(want_cnt, (term, blk), 1)
    off = np.asarray(shard.bm_offsets)
    row = np.repeat(np.arange(index.vocab), np.diff(off))
    # the CSR is padded to whole 1,024-entry tiles with inert entries
    n = int(off[-1])
    assert spec.n_block_entries == len(shard.bm_block_id)
    assert len(shard.bm_block_id) % 1024 == 0
    assert len(shard.bm_block_id) - n < 1024
    for col in (shard.bm_block_id, shard.bm_block_max, shard.bm_block_cnt):
        assert not np.asarray(col)[n:].any()
    bid = np.asarray(shard.bm_block_id)[:n]
    got_max = np.zeros_like(want_max)
    got_max[row, bid] = np.asarray(shard.bm_block_max)[:n]
    got_cnt = np.zeros_like(want_cnt)
    got_cnt[row, bid] = np.asarray(shard.bm_block_cnt)[:n]
    np.testing.assert_array_equal(got_max, want_max)
    np.testing.assert_array_equal(got_cnt, want_cnt)
    assert spec.max_blocks_per_term == int((want_cnt > 0).sum(axis=1).max())


def _bmw_scores_dense(index, terms, mask, rows, k, theta):
    """``oracle.bmw_scores`` as it read dense (V, n_blocks) tables."""
    block_max = _dense(index, index.impact.astype(np.float64),
                       np.maximum).astype(np.uint8)
    block_count = _dense(index, np.ones(index.n_postings),
                         np.add).astype(np.uint16)
    n, bs, nb = index.n_docs, index.block_size, index.n_blocks
    scale = index.quant_scale / 255.0
    accs, works, touched = [], [], []
    for q in rows:
        t = terms[q][mask[q] > 0]
        ub = block_max[t].astype(np.float32).sum(axis=0) * scale
        cnt = block_count[t].astype(np.int64)
        order = np.argsort(-ub, kind="stable")
        cand = np.minimum(cnt.sum(axis=0), bs)[order]
        need = int(np.searchsorted(np.cumsum(cand), 2 * k)) + 1
        in_p1 = np.zeros(nb, bool)
        in_p1[order[:min(max(need, 4), nb)]] = True
        d, w = oracle._query_postings(index, terms[q], mask[q], False)
        blk = d // bs
        acc1 = np.bincount(d, weights=np.where(in_p1[blk], w, 0.0),
                           minlength=n)
        tau = np.partition(acc1, n - min(k, n))[n - min(k, n)]
        survive = (ub > theta * tau) | in_p1
        accs.append(np.bincount(d, weights=np.where(survive[blk], w, 0.0),
                                minlength=n))
        works.append(int(cnt[:, survive].sum()))
        touched.append(int(survive.sum()))
    return np.stack(accs), np.asarray(works), np.asarray(touched)


@pytest.mark.parametrize("k,theta", [(10, 1.0), (100, 1.0), (10, 1.3)])
def test_bmw_oracle_unchanged_on_csr(small_collection, k, theta):
    _, index, ql = small_collection
    rows = np.arange(48)
    got = oracle.bmw_scores(index, ql.terms, ql.mask, rows, k, theta)
    want = _bmw_scores_dense(index, ql.terms, ql.mask, rows, k, theta)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_dryrun_blocks_per_term_from_csr(small_collection):
    _, index, _ = small_collection
    proxies = WorkProxies.from_index(index, get_preset("paper_200ms"))
    count = _dense(index, np.ones(index.n_postings), np.add)
    np.testing.assert_array_equal(proxies.blocks_per_term,
                                  (count > 0).sum(axis=1))
