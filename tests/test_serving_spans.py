"""Wall-clock spans of ``SearchSystem.serve`` in the JAX profiler trace:
each call is one ``cascade.serve`` holding the stage spans in order, every
blocking read is one ``cascade.sync``, garbage collections show as
``python.gc``, and none of it changes an answer."""

import dataclasses
import gc
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.index.builder import build_index
from repro.index.corpus import CorpusParams, build_corpus, build_queries
from repro.serving.spec import (BackendSpec, CacheSpec, CascadeSpec,
                                DeploySpec, RoutingSpec, Stage2Spec)
from repro.serving.system import build_system
from repro.serving.telemetry import spans as S

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "chipbench"))
import hostspans  # noqa: E402


@pytest.fixture(scope="module")
def tiny():
    corpus = build_corpus(CorpusParams(n_docs=1024, vocab=1024,
                                       avg_doclen=60, zipf_a=1.05, seed=3))
    index = build_index(corpus, stop_k=8)
    ql = build_queries(corpus, 64, stop_k=8, seed=11)
    spec = CascadeSpec(
        routing=RoutingSpec(budget=100.0, rho_max=1 << 12, calibrate=True),
        stage2=Stage2Spec(enabled=True, k_serve=32, t_final=10),
        backend=BackendSpec(backend="jnp"),
        deploy=DeploySpec(n_shards=1, replicas=2), name="spans")
    system = build_system(spec, index, corpus=corpus)
    system.fit(ql, None, seed=5)
    # route on the predicted k alone, split at the median of the first 16
    # queries: both engines serve rows of each 8-query batch below
    pk = system.stage0(ql.terms[:16], ql.mask[:16])[0]
    spec = dataclasses.replace(spec, routing=dataclasses.replace(
        spec.routing, algorithm=1, t_k=float(np.median(pk)),
        calibrate=False))
    system = build_system(spec, index, corpus=corpus, models=system.models,
                          ltr=system.ltr)
    return corpus, index, ql, system


def _traced(system, ql, trace_dir, batches):
    """Serve ``batches`` (row slices) under the profiler; the results and
    the program spans the trace holds, in time order."""
    with jax.profiler.trace(str(trace_dir)):
        out = [system.serve(ql.terms[b], ql.mask[b], ql.topic[b])
               for b in batches]
    spans = sorted((ev for ev in hostspans.load(hostspans.newest(trace_dir))
                    if hostspans.is_program_span(ev[0])),
                   key=lambda ev: ev[1])
    return out, spans


@pytest.mark.parametrize("cached", [False, True], ids=["direct", "cached"])
def test_serve_spans_nest_in_profiler_trace(tiny, tmp_path, cached):
    """Each call is one ``cascade.serve`` holding Stage-0, Stage-1 and
    Stage-2 in that order, with one ``cascade.sync`` per read-back: Stage-0,
    each engine that served rows, Stage-2.  The cached path reads Stage-0
    for its lookup and serves its misses the direct way; a batch served
    again is all hits and reads back Stage-0 alone."""
    corpus, index, ql, system = tiny
    batches = [slice(0, 8), slice(8, 16)]
    if cached:
        spec = dataclasses.replace(system.cascade_spec,
                                   cache=CacheSpec(enabled=True))
        system = build_system(spec, index, corpus=corpus,
                              models=system.models, ltr=system.ltr)
        batches = [slice(0, 8), slice(0, 8)]
    before = dict(system.sched.stats)
    res, spans = _traced(system, ql, tmp_path, batches)
    serves = [(s, s + d) for name, s, d in spans if name == S.SERVE]
    assert len(serves) == len(batches)
    stage = (S.STAGE0, S.STAGE1, S.STAGE2)
    for n, ((lo, hi), r) in enumerate(zip(serves, res)):
        inner = [(name, s, d) for name, s, d in spans
                 if name != S.SERVE and lo <= s and s + d <= hi]
        names = [name for name, _, _ in inner]
        engines = ((r.stats["jass"] > before["jass"])
                   + (r.stats["bmw"] > before["bmw"]))
        assert engines == 2 or (cached and n == 1)
        before = dict(r.stats)
        if cached and n == 1:
            assert S.CACHE in names and S.STAGE1 not in names
            assert names.count(S.SYNC) == 1
            continue
        firsts = [names.index(st) for st in stage]
        assert firsts == sorted(firsts)
        assert names.count(S.SYNC) == 2 + engines + cached
        # every wait lies inside a stage span
        for name, s, d in inner:
            if name == S.SYNC:
                assert any(s2 <= s and s + d <= s2 + d2
                           for n2, s2, d2 in inner if n2 in stage)


def test_spans_and_gc_spans_leave_answers_bit_identical(tiny, tmp_path):
    corpus, index, ql, system = tiny

    def serve_all():
        fresh = build_system(system.cascade_spec, index, corpus=corpus,
                             models=system.models, ltr=system.ltr)
        return [fresh.serve(ql.terms[b], ql.mask[b], ql.topic[b])
                for b in (slice(0, 8), slice(8, 16))]

    plain = serve_all()
    S.gc_spans(True)
    try:
        with jax.profiler.trace(str(tmp_path)):
            gc.collect()
            marked = serve_all()
    finally:
        S.gc_spans(False)
    assert S._gc_hook not in gc.callbacks
    for a, b in zip(plain, marked):
        np.testing.assert_array_equal(a.topk, b.topk)
        np.testing.assert_array_equal(a.final, b.final)
        np.testing.assert_array_equal(a.candidates_used, b.candidates_used)
        np.testing.assert_array_equal(a.latency, b.latency)
    names = [ev[0] for ev in hostspans.load(hostspans.newest(tmp_path))]
    assert S.GC in names and S.SERVE in names


def test_fetch_reads_every_leaf_in_one_sync_span(tmp_path):
    x = jax.numpy.arange(6).reshape(2, 3)
    with jax.profiler.trace(str(tmp_path)):
        got = S.fetch(x, [x + 1, None], {"y": x * 2})
    a, (b, none), d = got
    assert isinstance(a, np.ndarray) and none is None
    np.testing.assert_array_equal(b, np.asarray(x) + 1)
    np.testing.assert_array_equal(d["y"], np.asarray(x) * 2)
    names = [ev[0] for ev in hostspans.load(hostspans.newest(tmp_path))]
    assert names.count(S.SYNC) == 1
    assert set(S.NAMES) >= {S.SERVE, S.SYNC, S.GC}


def test_engine_spans_and_pad_counters(tiny, tmp_path):
    """Inside ``cascade.stage1`` each engine that served rows is one
    ``cascade.jass`` or ``cascade.bmw`` holding its one read-back; the pad
    rows each engine ran and the JASS postings scored are counted."""
    from repro.serving.online.batcher import bucket_size

    corpus, index, ql, system = tiny
    before = dict(system.sched.stats)
    res, spans = _traced(system, ql, tmp_path, [slice(8, 16)])
    r, = res
    stats = system.sched.stats
    n = {e: stats[e] - before[e] for e in ("jass", "bmw")}
    assert n["jass"] and n["bmw"]
    for e in ("jass", "bmw"):
        assert stats[f"{e}_pad_rows"] - before[f"{e}_pad_rows"] == \
            bucket_size(n[e], system.cascade_spec.online.max_batch) - n[e]
    assert stats["jass_postings"] > before["jass_postings"]
    (s1, d1), = [(s, d) for name, s, d in spans if name == S.STAGE1]
    for name in (S.STAGE1_JASS, S.STAGE1_BMW):
        (s, d), = [(s, d) for n2, s, d in spans if n2 == name]
        assert s1 <= s and s + d <= s1 + d1
        syncs = [s2 for n2, s2, d2 in spans
                 if n2 == S.SYNC and s <= s2 and s2 + d2 <= s + d]
        assert len(syncs) == 1
