"""Mixed routing pads each engine's sub-batch to the batcher's bucket width.

Algorithm 2 splits a batch between JASS and BMW, so each engine sees a
sub-batch of any size from 1 to ``max_batch``.  ``_stage1_full`` pads it
with inert rows after the real ones, so each engine builds one program per
bucket width (and per lane budget ``qcap`` for BMW), not one per size, and
the real rows' answers are those of the unpadded call.
"""

import dataclasses

import numpy as np
import pytest

from repro.index.builder import build_index
from repro.index.corpus import CorpusParams, build_corpus, build_queries
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
    """(engine, width, qcap) of every engine call."""
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
