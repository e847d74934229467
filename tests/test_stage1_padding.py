"""Mixed routing pads each engine's sub-batch to the batcher's bucket width.

Algorithm 2 splits a batch between JASS and BMW, so each engine sees a
sub-batch of any size from 1 to ``max_batch``.  ``SearchSystem.stage1``
pads it with inert rows after the real ones, so each engine builds one
program per bucket width (and, on the ``jnp`` backend only, per BMW lane
budget ``qcap``), not one per size, and the real rows' answers are those
of the unpadded call.  Also here: the per-engine capture the benchmark
harness reads (``_debug_shard_lists``).
"""

import dataclasses

import numpy as np
import pytest

from repro.index.builder import build_index
from repro.index.corpus import CorpusParams, build_corpus, build_queries
from repro.isn.backend import query_lane_budget
from repro.isn.daat import daat_serve
from repro.serving import system as system_mod
from repro.serving.online.batcher import bucket_size
from repro.serving.scheduler import RoutedBatch
from repro.serving.spec import (BackendSpec, CascadeSpec, DeploySpec,
                                OnlineSpec, RoutingSpec, Stage2Spec)
from repro.serving.system import build_system

MB = 8


@pytest.fixture(scope="module")
def systems():
    """The same fitted system with bucketing on (padded) and off (every
    sub-batch served at its own size)."""
    corpus = build_corpus(CorpusParams(n_docs=1024, vocab=1024,
                                       avg_doclen=60, zipf_a=1.05, seed=3))
    index = build_index(corpus, stop_k=8)
    ql = build_queries(corpus, 64, stop_k=8, seed=11)
    spec = CascadeSpec(
        routing=RoutingSpec(budget=100.0, rho_max=1 << 12, calibrate=True),
        stage2=Stage2Spec(enabled=True, k_serve=32, t_final=10),
        backend=BackendSpec(backend="jnp"),
        deploy=DeploySpec(n_shards=1, replicas=2),
        online=OnlineSpec(max_batch=MB), name="padding")
    fitted = build_system(spec, index, corpus=corpus).fit(ql, None, seed=5)
    plain = dataclasses.replace(spec, online=OnlineSpec(max_batch=MB,
                                                        bucket_q=False))
    return ql, [build_system(s, index, corpus=corpus, models=fitted.models,
                             ltr=fitted.ltr) for s in (spec, plain)]


def _record(monkeypatch):
    """(engine, width, qcap) of every engine call: ``stage1`` hands the
    engines bound in ``repro.serving.system`` to the ``isn`` fan-outs,
    which call them once per segment."""
    calls = []
    for name, eng in (("saat_serve", "jass"), ("daat_serve", "bmw")):
        real = getattr(system_mod, name)

        def wrapped(shard, terms, *a, _real=real, _eng=eng, **kw):
            calls.append((_eng, terms.shape[0], kw.get("qcap")))
            return _real(shard, terms, *a, **kw)
        monkeypatch.setattr(system_mod, name, wrapped)
    return calls


def test_mixed_routing_builds_one_program_per_bucket_width(systems,
                                                           monkeypatch):
    ql, (padded, plain) = systems
    calls = _record(monkeypatch)
    terms, mask = ql.terms[:MB], ql.mask[:MB]
    none = np.zeros(0, np.int64)
    out = {}
    for sysm in (padded, plain):
        before = dict(sysm.sched.stats)
        del calls[:]
        for m in range(MB + 1):
            # rows 0..m-1 to JASS, the rest to BMW: each engine takes
            # every sub-batch size from 1 to MB
            routed = RoutedBatch(
                jass_rows=np.arange(m), bmw_rows=np.arange(m, MB),
                hedged_rows=none, k=np.full(MB, sysm.k_serve, np.int64),
                rho=np.full(MB, 1500, np.int64))
            topk, topk_sc, _, t_shards = sysm.stage1(terms, mask, routed)
            out.setdefault(id(sysm), []).append((topk, topk_sc, t_shards))
        sigs = set(calls)
        pads = {e: sysm.sched.stats[f"{e}_pad_rows"] - before[f"{e}_pad_rows"]
                for e in ("jass", "bmw")}
        if sysm is padded:
            widths = {bucket_size(n, MB) for n in range(1, MB + 1)}
            qcaps = {q for e, _, q in sigs if e == "bmw"}
            assert {w for e, w, _ in sigs if e == "jass"} == widths
            assert len([s for s in sigs if s[0] == "jass"]) == len(widths)
            assert len([s for s in sigs if s[0] == "bmw"]) \
                <= len(widths) * len(qcaps)
            want = sum(bucket_size(n, MB) - n for n in range(1, MB + 1))
            assert pads == {"jass": want, "bmw": want}
        else:
            # unpadded, every size is a program of its own
            assert {w for e, w, _ in sigs if e == "jass"} == set(
                range(1, MB + 1))
            assert pads == {"jass": 0, "bmw": 0}
    for (a, a_sc, a_t), (b, b_sc, b_t) in zip(out[id(padded)],
                                              out[id(plain)]):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a_sc, b_sc)
        np.testing.assert_array_equal(a_t, b_t)


def test_jass_postings_counter_sums_the_engine_work(systems):
    ql, (padded, _) = systems
    terms, mask = ql.terms[:5], ql.mask[:5]
    rows = np.arange(5)
    rho = np.full(5, 1500, np.int64)
    before = padded.sched.stats["jass_postings"]
    padded.stage1(terms, mask, RoutedBatch(
        jass_rows=rows, bmw_rows=np.zeros(0, np.int64),
        hedged_rows=np.zeros(0, np.int64),
        k=np.full(5, padded.k_serve, np.int64), rho=rho))
    from repro.isn import oracle
    _, work = oracle.jass_scores(padded.index, terms, mask, rows, rho)
    assert padded.sched.stats["jass_postings"] - before == int(work.sum())


@pytest.mark.parametrize("n_shards", [1, 2])
def test_debug_shard_lists_contract(systems, n_shards):
    """The capture the benchmark harness reads: one (rows, score lists, id
    lists) entry per engine branch, JASS before BMW, every list cut to the
    real rows, segment 0 first; with one segment its lists are the served
    ones."""
    ql, (padded, _) = systems
    sysm = padded if n_shards == 1 else build_system(
        dataclasses.replace(padded.cascade_spec,
                            deploy=DeploySpec(n_shards=2, replicas=2)),
        padded.index, corpus=padded.corpus, models=padded.models,
        ltr=padded.ltr)
    none = np.zeros(0, np.int64)
    jass, bmw = np.array([0, 3, 5]), np.array([1, 2, 4, 6, 7])
    sysm._debug_shard_lists = []
    topk, topk_sc, _, _ = sysm.stage1(ql.terms[:MB], ql.mask[:MB], RoutedBatch(
        jass_rows=jass, bmw_rows=bmw, hedged_rows=none,
        k=np.full(MB, sysm.k_serve, np.int64), rho=np.full(MB, 1500)))
    lists, sysm._debug_shard_lists = sysm._debug_shard_lists, None
    assert len(lists) == 2
    hi = sysm.shard_specs[0].n_docs              # segment 0's doc range
    for (rows, scs, ids), want in zip(lists, (jass, bmw)):
        np.testing.assert_array_equal(rows, want)
        assert len(scs) == len(ids) == n_shards
        for sc, d in zip(scs, ids):
            assert sc.shape == d.shape == (len(rows), sysm.k_serve)
        assert ((ids[0] >= 0) & (ids[0] < hi)).all()
        if n_shards == 1:
            np.testing.assert_array_equal(ids[0], topk[rows])
            np.testing.assert_array_equal(np.asarray(scs[0], np.float32),
                                          topk_sc[rows])
        else:
            assert (ids[1] >= hi).all()


def test_bmw_kernel_programs_are_keyed_by_width_alone(systems, monkeypatch):
    """On a kernel backend (the interpreter here) BMW reads no lane budget:
    batches whose ``jnp`` lane budgets differ share one program per batch
    width."""
    ql, (padded, _) = systems
    sysm = build_system(
        dataclasses.replace(padded.cascade_spec,
                            backend=BackendSpec(backend="interpret")),
        padded.index, corpus=padded.corpus)
    # queries of the 1, 4 and all L most frequent terms: three lane budgets
    df = sysm._df_host[0]
    terms = np.zeros((3, ql.terms.shape[1]), ql.terms.dtype)
    mask = np.zeros((3, ql.mask.shape[1]), ql.mask.dtype)
    for i, n in enumerate((1, 4, terms.shape[1])):
        terms[i, :n] = np.argsort(-df, kind="stable")[:n]
        mask[i, :n] = 1
    assert len({query_lane_budget(df, terms[i:i + 1], mask[i:i + 1])
                for i in range(3)}) == 3
    calls = _record(monkeypatch)
    none = np.zeros(0, np.int64)
    widths = sorted({bucket_size(n, MB) for n in range(1, MB + 1)})

    def serve(i, w):
        sysm.stage1(terms[[i] * w], mask[[i] * w], RoutedBatch(
            jass_rows=none, bmw_rows=np.arange(w), hedged_rows=none,
            k=np.full(w, sysm.k_serve, np.int64), rho=np.full(w, 1500)))
    for w in widths:
        serve(0, w)
    programs = daat_serve._cache_size()
    for i in (1, 2):
        for w in widths:
            serve(i, w)
    assert daat_serve._cache_size() == programs
    assert {q for _, _, q in calls} == {None}
    assert {w for _, w, _ in calls} == set(widths)
