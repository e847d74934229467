"""Mutable-index suite: delta tile-set mechanics (capacity admission,
shape-static rebuilds), merge-vs-rebuild bit parity, property-style
delta-scan parity for both lexical engines + dense (random ingest orders
and batch sizes, multi-shard + drop-mask cases), spec backward compat
over every shipped preset, ingest-off inertness (offline + online event
log), cache-epoch invalidation, worst-case accounting of the live scan,
and the online feed-vs-query backpressure ladder.
"""

import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.cascade_presets import PRESETS, get_preset
from repro.dense.embeddings import (build_embeddings, delta_doc_embeddings,
                                    embed_queries)
from repro.dense.engine import DenseEngine
from repro.index.builder import assemble_index, build_index, frozen_stats
from repro.index.corpus import (FeedDocs, extend_corpus, slice_feed,
                                synthesize_feed_docs)
from repro.index.delta import DeltaStore
from repro.index.postings import shard_from_index
from repro.isn import oracle
from repro.isn.backend import Segment
from repro.isn.daat import daat_serve, daat_serve_segments
from repro.isn.saat import saat_serve, saat_serve_segments
from repro.serving.online.simulator import INGEST_EVENT, MERGE_EVENT
from repro.serving.online.traffic import feed_arrival_times
from repro.serving.scheduler import RoutedBatch
from repro.serving.spec import (BackendSpec, CacheSpec, CascadeSpec,
                                DeploySpec, IngestSpec, OnlineSpec,
                                RoutingSpec, Stage2Spec, TrafficSpec)
from repro.serving.system import build_system

BIG = 1 << 20          # a rho / postings budget beyond any segment's work


def _permute_feed(feed: FeedDocs, rng) -> FeedDocs:
    """The same feed docs in a random arrival order (ids re-based)."""
    perm = rng.permutation(feed.n_docs)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(feed.n_docs)
    order = np.lexsort((inv[feed.postings_doc], feed.postings_term))
    return FeedDocs(doclen=feed.doclen[perm],
                    doc_topics=feed.doc_topics[perm],
                    postings_term=feed.postings_term[order],
                    postings_doc=inv[feed.postings_doc][order],
                    postings_tf=feed.postings_tf[order])


def _feed_in_batches(delta: DeltaStore, feed: FeedDocs, rng) -> int:
    """Ingest ``feed`` through the delta in random-sized batches."""
    lo, total = 0, 0
    while lo < feed.n_docs:
        hi = min(lo + int(rng.randint(1, 17)), feed.n_docs)
        total += delta.add(slice_feed(feed, lo, hi))
        lo = hi
    return total


def _frozen_oracle(index, ext):
    """Monolithic index over the combined collection, scored/quantized
    with the SEALED stats + stoplist — what sealed + delta must equal."""
    keep = ~np.isin(ext.postings_term, index.stoplist)
    return assemble_index(ext.postings_term[keep].astype(np.int64),
                          ext.postings_doc[keep].astype(np.int64),
                          ext.postings_tf[keep].astype(np.float64),
                          ext.doclen, ext.vocab,
                          block_size=index.block_size,
                          stoplist=index.stoplist,
                          frozen=frozen_stats(index))


def _topk_tie(acc: np.ndarray, k: int):
    """Row-wise top-k, ties broken by LOWER doc id — the dense-accumulator
    policy every layout must reproduce."""
    ids = np.empty((acc.shape[0], k), np.int64)
    sc = np.empty((acc.shape[0], k), acc.dtype)
    col = np.arange(acc.shape[1])
    for i, row in enumerate(acc):
        top = np.lexsort((col, -row))[:k]
        ids[i], sc[i] = top, row[top]
    return ids, sc


# ---------------------------------------------------------------------------
# DeltaStore mechanics
# ---------------------------------------------------------------------------


def test_delta_admission_and_fill(small_collection):
    corpus, index, ql = small_collection
    feed = synthesize_feed_docs(corpus, 24, seed=7)
    delta = DeltaStore(index, capacity_docs=16, capacity_postings=1 << 14)
    assert delta.admit_count(feed) == 16        # doc axis binds
    assert delta.add(feed) == 16
    assert delta.n_docs == 16 and delta.fill == 1.0
    assert delta.add(slice_feed(feed, 16, 24)) == 0     # full: merge first
    # a capacity that cannot hold even one doc is a hard error, not a hang
    tiny = DeltaStore(index, capacity_docs=8, capacity_postings=2)
    with pytest.raises(ValueError):
        tiny.add(feed)
    # postings can be the binding axis: fill reports the tighter one
    kept = int((~np.isin(feed.postings_term, index.stoplist)).sum())
    dp = DeltaStore(index, capacity_docs=1024, capacity_postings=kept // 2)
    took = dp.add(feed)
    assert 0 < took < 24
    assert dp.fill == dp.n_postings_kept / dp.capacity_postings
    assert dp.fill >= dp.n_docs / dp.capacity_docs


def test_delta_rebuild_is_shape_static(small_collection):
    """Every fill level materializes the SAME shard shapes and static spec
    — one jit signature from empty to full (the live-serve invariant)."""
    import jax

    corpus, index, ql = small_collection
    feed = synthesize_feed_docs(corpus, 48, seed=7)
    delta = DeltaStore(index, capacity_docs=64, capacity_postings=8192)
    shard0, spec0 = delta.segment()
    shapes0 = jax.tree_util.tree_map(lambda a: np.shape(a), shard0)
    for lo in (0, 16, 32):
        delta.add(slice_feed(feed, lo, lo + 16))
        shard, spec = delta.segment()
        assert spec == spec0
        assert jax.tree_util.tree_map(lambda a: np.shape(a),
                                      shard) == shapes0


# ---------------------------------------------------------------------------
# merge == from-scratch rebuild (the oracle the ISSUE pins)
# ---------------------------------------------------------------------------


def _assert_index_equal(a, b):
    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if isinstance(va, np.ndarray) or isinstance(vb, np.ndarray):
            np.testing.assert_array_equal(va, vb, err_msg=f.name)
        else:
            assert va == vb, f.name


def test_merge_matches_rebuild_oracle(small_collection):
    corpus, index, ql = small_collection
    rng = np.random.RandomState(41)
    feed = _permute_feed(synthesize_feed_docs(corpus, 56, seed=7), rng)
    delta = DeltaStore(index, capacity_docs=64, capacity_postings=1 << 14)
    assert _feed_in_batches(delta, feed, rng) == 56
    new_corpus, new_index = delta.merged(corpus)
    oracle_idx = build_index(extend_corpus(corpus, feed),
                             stop_k=len(index.stoplist))
    _assert_index_equal(new_index, oracle_idx)
    assert new_corpus.n_docs == corpus.n_docs + 56
    np.testing.assert_array_equal(
        new_corpus.postings_term,
        extend_corpus(corpus, feed).postings_term)


# ---------------------------------------------------------------------------
# delta-scan parity: sealed + delta segments == frozen monolithic oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("trial", [0, 1, 2])
def test_saat_delta_scan_parity(small_collection, trial):
    """Property-style: random ingest order and batch sizes; the live
    (sealed + delta) SAAT scan is bit-identical to a monolithic frozen
    oracle over the combined collection — scores AND tie order."""
    corpus, index, ql = small_collection
    rng = np.random.RandomState(100 + trial)
    n_new = int(rng.randint(40, 90))
    feed = _permute_feed(synthesize_feed_docs(corpus, n_new, seed=7), rng)
    delta = DeltaStore(index, capacity_docs=128, capacity_postings=1 << 14)
    assert _feed_in_batches(delta, feed, rng) == n_new

    ext = extend_corpus(corpus, feed)
    oidx = _frozen_oracle(index, ext)
    oshard, ospec = shard_from_index(oidx)

    rows = np.arange(32)
    terms = jnp.asarray(ql.terms[rows])
    mask = jnp.asarray(ql.mask[rows])
    cap = int(np.asarray(oidx.df).max())
    rho = jnp.full(len(rows), BIG)      # full scan: parity is exact
    ref = saat_serve(oshard, terms, mask, rho, n_docs=ospec.n_docs,
                     k=32, cap=cap)

    dshard, dspec = delta.segment()
    segments = [Segment(*shard_from_index(index), 0),
                Segment(dshard, dspec, index.n_docs)]
    out = saat_serve_segments(segments, terms, mask, [rho, rho], k=32,
                              cap=cap)
    ids, sc = out.merged
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ref.topk_docs))
    np.testing.assert_array_equal(np.asarray(sc), np.asarray(ref.topk_scores))
    # ghost capacity rows never surface
    assert int(np.asarray(ids).max()) < ext.n_docs


def test_saat_delta_multishard_and_drop(small_collection):
    """Two sealed shards + delta, with sealed shard 0 dropped for half the
    batch: exact numpy-oracle parity including the drop mask."""
    corpus, index, ql = small_collection
    rng = np.random.RandomState(77)
    feed = _permute_feed(synthesize_feed_docs(corpus, 64, seed=7), rng)
    delta = DeltaStore(index, capacity_docs=64, capacity_postings=1 << 14)
    assert _feed_in_batches(delta, feed, rng) == 64

    ext = extend_corpus(corpus, feed)
    oidx = _frozen_oracle(index, ext)
    half = index.n_docs // 2
    rows = np.arange(24)
    terms = jnp.asarray(ql.terms[rows])
    mask = jnp.asarray(ql.mask[rows])
    cap = int(np.asarray(oidx.df).max())
    rho = jnp.full(len(rows), BIG)
    dshard, dspec = delta.segment()
    segments = [Segment(*shard_from_index(index, 0, half), 0),
                Segment(*shard_from_index(index, half, index.n_docs), half),
                Segment(dshard, dspec, index.n_docs)]
    drop = np.zeros((3, len(rows)), bool)
    drop[0, ::2] = True
    out = saat_serve_segments(segments, terms, mask, [rho, rho, rho], k=24,
                              cap=cap, drop=drop)
    ids, sc = out.merged
    acc, _ = oracle.jass_scores(oidx, ql.terms, ql.mask, rows, BIG)
    acc = np.asarray(acc, np.float64)
    acc[::2, :half] = -np.inf           # dropped shard's doc range
    o_ids, o_sc = _topk_tie(acc, 24)
    np.testing.assert_array_equal(np.asarray(ids, np.int64), o_ids)
    np.testing.assert_array_equal(np.asarray(sc),
                                  o_sc.astype(np.float32))
    assert not np.isin(np.asarray(ids)[::2], np.arange(half)).any()


def test_daat_delta_scan_parity(small_collection):
    """Rank-safe DAAT over sealed + delta vs the monolithic frozen oracle.
    Block partitioning (and so phase-1 tau) differs across layouts, so the
    repo's sealed multi-shard bar applies: high overlap, exact ghost
    safety, and drop-masked ranges never surface."""
    corpus, index, ql = small_collection
    rng = np.random.RandomState(55)
    feed = _permute_feed(synthesize_feed_docs(corpus, 72, seed=7), rng)
    delta = DeltaStore(index, capacity_docs=128, capacity_postings=1 << 14)
    assert _feed_in_batches(delta, feed, rng) == 72

    ext = extend_corpus(corpus, feed)
    oidx = _frozen_oracle(index, ext)
    oshard, ospec = shard_from_index(oidx)
    rows = np.arange(32)
    terms = jnp.asarray(ql.terms[rows])
    mask = jnp.asarray(ql.mask[rows])
    theta = jnp.ones(len(rows), jnp.float32)
    k = 20
    ref = daat_serve(oshard, terms, mask, theta, n_docs=ospec.n_docs,
                     n_blocks=ospec.n_blocks, block_size=ospec.block_size,
                     k=k, cap=ospec.max_df, bcap=ospec.max_blocks_per_term)
    dshard, dspec = delta.segment()
    segments = [Segment(*shard_from_index(index), 0),
                Segment(dshard, dspec, index.n_docs)]
    ids = np.asarray(daat_serve_segments(segments, terms, mask, theta,
                                         k=k).merged[0])
    ref_ids = np.asarray(ref.topk_docs)
    overlap = np.mean([len(np.intersect1d(ids[i], ref_ids[i])) / k
                       for i in range(len(rows))])
    assert overlap > 0.97
    assert int(ids.max()) < ext.n_docs          # no ghost capacity rows
    # delta docs actually reachable: someone's top-k contains one
    assert (ids >= index.n_docs).any()
    # drop the sealed shard: only delta-range ids (or -1 padding) remain
    drop = np.zeros((2, len(rows)), bool)
    drop[0] = True
    dids = np.asarray(daat_serve_segments(segments, terms, mask, theta, k=k,
                                          drop=drop).merged[0])
    assert ((dids >= index.n_docs) | (dids == -1)).all()


@pytest.mark.parametrize("engine", ["jass", "bmw"])
@pytest.mark.parametrize("ingest", [False, True], ids=["sealed", "delta"])
@pytest.mark.parametrize("n_shards", [1, 2])
def test_stage1_serves_the_segment_list(small_collection, n_shards, ingest,
                                        engine):
    """``SearchSystem.stage1`` over its segment list (sealed shards, then
    the live delta) against the monolithic collection: JASS id for id with
    the numpy oracle at the same budget, BMW at the repo's rank-safety bar
    (exact on the sealed index, the sealed + delta bar with a delta)."""
    corpus, index, ql = small_collection
    k, rows = 32, np.arange(24)
    spec = CascadeSpec(
        routing=RoutingSpec(rho_max=1 << 14),
        stage2=Stage2Spec(enabled=False, k_serve=k),
        backend=BackendSpec(backend="jnp"),
        deploy=DeploySpec(n_shards=n_shards, replicas=2),
        ingest=IngestSpec(enabled=ingest, delta_docs=64,
                          delta_postings=1 << 14), name="segments")
    system = build_system(spec, index, corpus=corpus)
    ref_index = index
    if ingest:
        feed = synthesize_feed_docs(corpus, 48, seed=7)
        assert system.add_documents(feed) == 48
        ref_index = _frozen_oracle(index, extend_corpus(corpus, feed))
    assert len(system.segments) == n_shards + ingest
    none = np.zeros(0, np.int64)
    rho = np.full(len(rows), 1500, np.int64)
    routed = RoutedBatch(jass_rows=rows if engine == "jass" else none,
                         bmw_rows=rows if engine == "bmw" else none,
                         hedged_rows=none, k=np.full(len(rows), k, np.int64),
                         rho=rho)
    before = system.sched.stats["jass_postings"]
    topk, topk_sc, _, t_shards = system.stage1(ql.terms[rows],
                                               ql.mask[rows], routed)
    assert t_shards.shape == (n_shards, len(rows))
    # the delta's docs are served: someone's list holds one
    assert (topk >= index.n_docs).any() == ingest
    if engine == "jass":
        acc, work = oracle.jass_scores(ref_index, ql.terms, ql.mask, rows,
                                       rho)
        o_ids, o_sc = _topk_tie(np.asarray(acc), k)
        np.testing.assert_array_equal(topk, o_ids)
        np.testing.assert_array_equal(topk_sc, o_sc.astype(np.float32))
        assert system.sched.stats["jass_postings"] - before == work.sum()
        return
    oshard, ospec = shard_from_index(ref_index)
    ref = np.asarray(daat_serve(
        oshard, jnp.asarray(ql.terms[rows]), jnp.asarray(ql.mask[rows]),
        jnp.ones(len(rows), jnp.float32), n_docs=ospec.n_docs,
        n_blocks=ospec.n_blocks, block_size=ospec.block_size, k=k,
        cap=ospec.max_df, bcap=ospec.max_blocks_per_term).topk_docs)
    if not ingest:
        np.testing.assert_array_equal(topk, ref)
        return
    overlap = np.mean([len(np.intersect1d(topk[i], ref[i])) / k
                       for i in range(len(rows))])
    assert overlap > 0.97
    assert int(topk.max()) < ref_index.n_docs   # no ghost capacity rows


# ---------------------------------------------------------------------------
# dense delta parity
# ---------------------------------------------------------------------------


def test_dense_delta_parity(small_collection):
    """Incremental delta embeddings == slicing a full rebuild, and the
    engine's sealed + delta scan == a monolithic engine, bit for bit."""
    from repro.serving.spec import DenseSpec

    corpus, index, ql = small_collection
    dspec = DenseSpec(enabled=True, source="auto")
    n, m = corpus.n_docs, 40
    feed = synthesize_feed_docs(corpus, m, seed=7)
    ext = extend_corpus(corpus, feed)
    emb_ext, tt = build_embeddings(dspec, ext, n_docs=ext.n_docs,
                                   vocab=ext.vocab)
    emb_sealed, tt2 = build_embeddings(dspec, corpus, n_docs=n,
                                       vocab=corpus.vocab)
    np.testing.assert_array_equal(tt, tt2)
    np.testing.assert_array_equal(emb_ext[:n], emb_sealed)
    rows = delta_doc_embeddings(dspec, n_sealed=n, n_new=m,
                                vocab=corpus.vocab,
                                topics=feed.doc_topics, corpus=corpus)
    np.testing.assert_array_equal(rows, emb_ext[n:])

    cap = 64                            # capacity-padded: ghost rows > m
    pad = np.zeros((cap, emb_sealed.shape[1]), np.float32)
    pad[:m] = rows
    live = DenseEngine(emb_sealed, tt, [(0, n)])
    live.set_delta(pad, m, n)
    assert live.delta_tiles() == -(-cap // live.tile_d)
    mono = DenseEngine(emb_ext, tt, [(0, n + m)])
    q_emb = embed_queries(tt, ql.terms, ql.mask)
    ids, sc = live.serve(q_emb, 16)
    o_ids, o_sc = mono.serve(q_emb, 16)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(o_ids))
    np.testing.assert_array_equal(np.asarray(sc), np.asarray(o_sc))
    assert int(np.asarray(ids).max()) < n + m   # ghosts masked out
    live.clear_delta()
    ids2, _ = live.serve(q_emb, 16)
    assert int(np.asarray(ids2).max()) < n


# ---------------------------------------------------------------------------
# spec layer: presets round-trip + legacy JSON backward compat
# ---------------------------------------------------------------------------


def test_presets_round_trip_and_legacy_json():
    for name in PRESETS:
        spec = get_preset(name)
        rt = CascadeSpec.from_json(spec.to_json())
        assert rt == spec, name
        # a pre-ingest JSON (no "ingest" node) loads to the inert default:
        # byte-identical re-serialization modulo that one added node
        d = json.loads(spec.to_json())
        d.pop("ingest")
        legacy = CascadeSpec.from_json(json.dumps(d))
        assert legacy == dataclasses.replace(spec, ingest=IngestSpec())
        if name != "live_ingest":
            assert legacy == spec
            assert not legacy.ingest.active
    li = get_preset("live_ingest")
    assert li.ingest.active
    assert li.ingest.delta_docs >= li.stage2.k_serve


def test_ingest_spec_validation():
    with pytest.raises(ValueError):
        IngestSpec(enabled=True, delta_docs=0).validate()
    with pytest.raises(ValueError):
        IngestSpec(enabled=True, feed_qps=0.0).validate()
    with pytest.raises(ValueError):
        IngestSpec(enabled=True, merge_threshold=1.5).validate()
    IngestSpec().validate()             # the inert default is always legal
    ts = feed_arrival_times(IngestSpec(enabled=True, feed_qps=20.0), 32)
    np.testing.assert_array_equal(
        ts, feed_arrival_times(IngestSpec(enabled=True, feed_qps=20.0), 32))
    assert (np.diff(ts) >= 0).all()


# ---------------------------------------------------------------------------
# system layer
# ---------------------------------------------------------------------------


def _spec(ingest=None, cache=None, **routing_kw):
    routing = {"budget": 200.0, "rho_max": 1 << 14, "t_k": 150.0,
               "t_time": 18.0, "adapt_every": 0}
    routing.update(routing_kw)
    return CascadeSpec(
        routing=RoutingSpec(**routing),
        stage2=Stage2Spec(enabled=True, k_serve=32, t_final=5),
        backend=BackendSpec(backend="jnp"),
        deploy=DeploySpec(),
        cache=cache if cache is not None else CacheSpec(),
        ingest=ingest if ingest is not None else IngestSpec(),
        online=OnlineSpec(max_batch=8, batch_deadline_us=4.0),
        name="ingest_test",
    )


_ING = IngestSpec(enabled=True, delta_docs=64, delta_postings=4096,
                  feed_qps=12.0, feed_batch=8, merge_threshold=0.6)


@pytest.fixture(scope="module")
def fitted(small_collection):
    corpus, index, ql = small_collection
    spec = dataclasses.replace(
        _spec(), routing=dataclasses.replace(_spec().routing, t_k=None,
                                             t_time=None, calibrate=True))
    system = build_system(spec, index, corpus=corpus)
    system.fit(ql, None, seed=5)
    return corpus, index, ql, system, (system._base_cfg.t_k,
                                       system._base_cfg.t_time)


def _system(fitted, ingest=None, cache=None, index=None, corpus=None,
            **routing_kw):
    corpus0, index0, ql, system, (tk, tt) = fitted
    spec = _spec(ingest=ingest, cache=cache, t_k=tk, t_time=tt,
                 **routing_kw)
    return build_system(spec, index if index is not None else index0,
                        corpus=corpus if corpus is not None else corpus0,
                        models=system.models, ltr=system.ltr)


def test_system_lifecycle_merge_bit_parity(fitted):
    """serve → ingest → serve → merge → serve; the post-merge system is
    bit-identical (index AND results) to one built from scratch over the
    extended collection with the same spec."""
    corpus, index, ql, _, _ = fitted
    on = _system(fitted, ingest=_ING)
    before = on.serve(ql.terms, ql.mask, ql.topic)
    feed = synthesize_feed_docs(corpus, 48, seed=7)
    assert on.add_documents(feed) == 48
    mid = on.serve(ql.terms, ql.mask, ql.topic)
    assert (np.asarray(mid.topk) >= index.n_docs).sum() > 0   # live docs hit
    assert int(np.asarray(mid.topk).max()) < index.n_docs + 48
    merged = on.merge()
    assert merged == 48 and on.delta.n_docs == 0
    after = on.serve(ql.terms, ql.mask, ql.topic)

    ext = extend_corpus(corpus, feed)
    oracle_idx = build_index(ext, stop_k=len(index.stoplist))
    _assert_index_equal(on.index, oracle_idx)
    fresh = _system(fitted, ingest=_ING, index=oracle_idx, corpus=ext)
    ref = fresh.serve(ql.terms, ql.mask, ql.topic)
    np.testing.assert_array_equal(after.topk, ref.topk)
    np.testing.assert_array_equal(after.final, ref.final)
    np.testing.assert_array_equal(after.latency, ref.latency)
    # live serving saw strictly more collection than the sealed baseline
    assert before.topk.shape == after.topk.shape


def test_worst_case_and_stats_report_delta(fitted):
    corpus, index, ql, _, _ = fitted
    off, on = _system(fitted), _system(fitted, ingest=_ING)
    assert on.worst_case_us() == pytest.approx(
        off.worst_case_us() + on.cost.delta_time(_ING.delta_postings))
    assert "ingest" not in off.stats()
    s = on.stats()["ingest"]
    assert s["delta_docs"] == 0 and s["capacity_docs"] == 64
    assert s["delta_us"] > 0 and s["merges"] == 0
    on.add_documents(synthesize_feed_docs(corpus, 16, seed=7))
    s = on.stats()["ingest"]
    assert s["delta_docs"] == 16 and s["docs_ingested"] == 16
    assert s["feed_batches"] == 1 and 0 < s["fill"] < 1
    with pytest.raises(RuntimeError):
        off.add_documents(synthesize_feed_docs(corpus, 4, seed=7))
    # capacity below the serving depth is a spec-level error
    with pytest.raises(ValueError):
        _system(fitted, ingest=dataclasses.replace(_ING, delta_docs=16))


def test_ingest_epoch_invalidates_cache(fitted):
    corpus, index, ql, _, _ = fitted
    on = _system(fitted, ingest=_ING, cache=CacheSpec(enabled=True))
    q = len(ql.terms)
    on.serve(ql.terms, ql.mask, ql.topic)
    on.serve(ql.terms, ql.mask, ql.topic)
    assert on.cache.counters["l1_hits"] == q
    on.add_documents(synthesize_feed_docs(corpus, 16, seed=7))
    on.serve(ql.terms, ql.mask, ql.topic)
    assert on.cache.counters["l1_hits"] == q    # epoch bumped: all miss
    on.serve(ql.terms, ql.mask, ql.topic)
    assert on.cache.counters["l1_hits"] == 2 * q
    on.merge()
    on.serve(ql.terms, ql.mask, ql.topic)
    assert on.cache.counters["l1_hits"] == 2 * q


def test_disabled_ingest_is_bit_identical(fitted):
    """IngestSpec(enabled=False) must be indistinguishable from a spec
    with no ingest node at all: same offline results, same worst case,
    and a tuple-identical online event log."""
    corpus, index, ql, _, _ = fitted
    inert = IngestSpec(enabled=False, delta_docs=64, feed_qps=50.0)
    sys_a, sys_b = _system(fitted), _system(fitted, ingest=inert)
    assert sys_b.delta is None
    ra = sys_a.serve(ql.terms, ql.mask, ql.topic)
    rb = sys_b.serve(ql.terms, ql.mask, ql.topic)
    np.testing.assert_array_equal(ra.topk, rb.topk)
    np.testing.assert_array_equal(ra.final, rb.final)
    np.testing.assert_array_equal(ra.latency, rb.latency)
    assert sys_a.worst_case_us() == sys_b.worst_case_us()
    traffic = TrafficSpec(arrival="bursty", qps=150.0, seed=3)
    oa = _system(fitted).serve_online(ql.terms, ql.mask, ql.topic,
                                      traffic=traffic)
    ob = _system(fitted, ingest=inert).serve_online(ql.terms, ql.mask,
                                                    ql.topic,
                                                    traffic=traffic)
    assert oa.event_log == ob.event_log
    assert "ingest" not in oa.stats and "ingest" not in ob.stats


def test_online_ingest_backpressure_and_replay(fitted):
    """Serving under load while the feed lands: batches apply, merges run
    on the virtual clock, ingest pauses surface as real query waits, and
    the whole event log replays bit-identically."""
    corpus, index, ql, _, _ = fitted

    def run():
        on = _system(fitted, ingest=_ING)
        traffic = TrafficSpec(arrival="bursty", qps=60.0, seed=5)
        return on.serve_online(ql.terms, ql.mask, ql.topic, traffic=traffic)

    r = run()
    s = r.stats["ingest"]
    assert s["feed_batches_applied"] > 0
    assert s["docs_ingested"] == s["feed_batches_applied"] * _ING.feed_batch
    kinds = [int(e[0]) for e in r.event_log]
    assert kinds.count(INGEST_EVENT) == s["feed_batches_applied"]
    assert kinds.count(MERGE_EVENT) == s["merges"]
    assert s["feed_applied"] == s["feed_batches_applied"]
    if s["merges"]:
        assert s["merges_applied"] == s["merges"]
    # the ladder's ordering invariant: nothing sheds while the feed is
    # still being admitted freely (feed throttles BEFORE queries shed)
    assert r.event_log == run().event_log       # deterministic replay
