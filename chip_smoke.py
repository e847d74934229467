#!/usr/bin/env python3
"""Chip smoke: drive the retrieval cascade once on one TPU chip.

    python chip_smoke.py              # one TPU chip, a 2^20-doc shard
    python chip_smoke.py --rehearse   # CPU dry run: tiny shard, interpret

Phases, each printed on its own lines; any failure raises and exits
non-zero:

  device  refuse to run unless JAX's first device is a TPU (``--rehearse``
          runs the same phases on the CPU with the Pallas interpreter and
          reports ``"ok": false``)
  build   a synthetic collection from ``--seed`` sized as a ClueWeb09B
          doc-range shard (2^20 docs ~ 1/48 of its 50.2M; the rehearsal's
          4096 is printed on a ``reduced`` line) with the generator's
          MQ2009-like statistics; the shard is resident on the device
  serve   ``build_system(paper_200ms)`` on the compiled ``pallas`` backend,
          ``fit`` with pseudo-labels, then 64-query batches through
          ``serve`` — once to compile, once more timed
  parity  compiled Stage-1 top-k vs the numpy oracles (JASS rows exact,
          BMW rows up to float32 ties), Stage-2 features vs ``jnp``
  kernels ``tpu_custom_call`` in a lowered serving call of each kernel;
          XLA compiles in the timed pass; peak device memory
  dense   one batch through ``hybrid_fusion`` (dense Stage-1 on), its
          dense top-k vs the numpy oracle

The last line of standard output is one JSON object:
``{"ok": ..., "device": {"platform", "kind", "count"}}``.  The persistent
compilation cache goes where ``repro.launch.compile_cache`` puts it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import resource
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

CLUEWEB09B_DOCS = 50_220_423
SHARD_DOCS = 1 << 20      # one ClueWeb09B doc-range shard of 48
BATCH = 64
BATCHES = 3               # served batches, once to compile and once timed


T0 = time.perf_counter()


def host_memory() -> str:
    """Peak resident memory of this process and memory in use on the host
    (MemTotal - MemAvailable), GiB."""
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
    info = {}
    with open("/proc/meminfo") as f:
        for line in f:
            key, val = line.split(":", 1)
            info[key] = int(val.split()[0])
    used = (info["MemTotal"] - info["MemAvailable"]) / 2**20
    return f"rss-peak {peak:.1f}GiB host-used {used:.1f}GiB"


def say(phase: str, msg: str) -> None:
    print(f"[{phase} +{time.perf_counter() - T0:.0f}s {host_memory()}] {msg}",
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dry run: tiny shard, Pallas interpreter")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU found (first device: {dev.platform}); "
              "use --rehearse for a CPU dry run", file=sys.stderr)
        return 1
    backend = "interpret" if args.rehearse else "pallas"
    say("device", f"{device} backend={backend}")

    from repro.launch.compile_cache import enable_compile_cache
    say("device", f"compile cache: {enable_compile_cache()}")

    n_docs = 4096 if args.rehearse else SHARD_DOCS
    index, corpus, queries = build_phase(args, n_docs)
    system = serve_phase(args, backend, index, corpus, queries)
    parity_phase(system, index, queries, backend)
    kernels_phase(system, queries, backend, dev)
    del system
    dense_phase(args, backend, index, corpus, queries)
    print(json.dumps({"ok": not args.rehearse, "device": device}))
    return 0


def build_phase(args, n_docs):
    from repro.configs.cascade_presets import get_preset
    from repro.index.builder import build_index
    from repro.index.corpus import CorpusParams, build_corpus, build_queries

    if n_docs < SHARD_DOCS:
        say("build", f"reduced: n_docs={n_docs} < {SHARD_DOCS} "
            "(rehearsal size)")
    ix = get_preset("paper_200ms").index
    t0 = time.perf_counter()
    corpus = build_corpus(CorpusParams(n_docs=n_docs, seed=args.seed))
    say("build", f"corpus: {corpus.n_postings} raw postings")
    index = build_index(corpus, block_size=ix.block_size, stop_k=ix.stop_k)
    n_q = 512 + BATCHES * BATCH
    queries = build_queries(corpus, n_q, seed=args.seed + 1,
                            stop_k=ix.stop_k)
    say("build", f"n_docs={n_docs} (1/{CLUEWEB09B_DOCS / n_docs:.1f} of "
        f"ClueWeb09B) postings={index.n_postings} queries={n_q} "
        f"set-up {time.perf_counter() - t0:.1f}s")
    return index, corpus, queries


def _spec(name, backend):
    from repro.configs.cascade_presets import get_preset
    spec = get_preset(name)
    return dataclasses.replace(
        spec, backend=dataclasses.replace(spec.backend, backend=backend))


def _rows(queries, lo, hi):
    return queries.terms[lo:hi], queries.mask[lo:hi], queries.topic[lo:hi]


def _train(queries):
    from repro.index.corpus import QueryLog
    return QueryLog(terms=queries.terms[:512], mask=queries.mask[:512],
                    topic=queries.topic[:512], lengths=queries.lengths[:512])


def serve_phase(args, backend, index, corpus, queries):
    import jax

    from repro.serving.system import build_system

    t0 = time.perf_counter()
    system = build_system(_spec("paper_200ms", backend), index,
                          corpus=corpus)
    shard = system.shards[0]
    shard_bytes = sum(int(a.nbytes) for a in shard)
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    share = f"{shard_bytes / limit:.3f} of {limit} B" if limit else "n/a"
    say("build", f"shard arrays resident on device: {shard_bytes} B "
        f"({share}); tile_cap={system.shard_specs[0].tile_cap} "
        f"n_tiles={system.shard_specs[0].n_tiles}")
    system.fit(_train(queries))
    say("serve", f"build_system + fit (pseudo-labels) "
        f"{time.perf_counter() - t0:.1f}s")

    batches = [_rows(queries, 512 + b * BATCH, 512 + (b + 1) * BATCH)
               for b in range(BATCHES)]
    jass = bmw = reranked = 0
    t0 = time.perf_counter()
    for terms, mask, topics in batches:
        before = dict(system.sched.stats)
        res = system.serve(terms, mask, topics)
        jax.block_until_ready((res.topk, res.final))
        jass += system.sched.stats["jass"] - before["jass"]
        bmw += system.sched.stats["bmw"] - before["bmw"]
        reranked += int(np.sum(res.candidates_used > 0))
    say("serve", f"warm-up pass (compiles) {time.perf_counter() - t0:.1f}s: "
        f"rows to JASS={jass} BMW={bmw} Stage-2 re-ranked={reranked}")
    if jass <= 0 or bmw <= 0 or reranked <= 0:
        raise RuntimeError("both Stage-1 engines and Stage-2 must serve rows")

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    walls = []
    for terms, mask, topics in batches:
        t = time.perf_counter()
        res = system.serve(terms, mask, topics)
        jax.block_until_ready((res.topk, res.final))
        walls.append(time.perf_counter() - t)
    say("serve", "smoke timing, not a metric: wall s per 64-query batch "
        f"after warm-up {walls}")
    say("kernels", f"XLA compiles in the timed pass: {len(compiles)}")
    return system


def _ranked(acc):
    """Oracle ranking: score descending, lower doc id first on ties."""
    return np.argsort(-acc, axis=1, kind="stable")


def parity_phase(system, index, queries, backend):
    from repro.isn import oracle
    from repro.isn.backend import query_lane_budget
    from repro.ltr.ranker import qd_features_batched
    from repro.serving.scheduler import RoutedBatch

    terms, mask, topics = _rows(queries, 512, 512 + 16)
    pk, pr, pt = system.stage0(terms, mask)
    routed = system.sched.route(pk, pr, pt)
    rows = np.arange(len(terms))
    jass_rows, bmw_rows = routed.jass_rows, routed.bmw_rows
    if len(jass_rows) == 0 or len(bmw_rows) == 0:
        jass_rows, bmw_rows = rows[::2], rows[1::2]
    routed = RoutedBatch(jass_rows=jass_rows, bmw_rows=bmw_rows,
                         hedged_rows=np.zeros(0, np.int64), k=routed.k,
                         rho=routed.rho)
    topk, topk_sc, _, _ = system.stage1(terms, mask, routed)
    k = topk.shape[1]

    acc, _ = oracle.jass_scores(index, terms, mask, jass_rows,
                                routed.rho[jass_rows])
    want = _ranked(acc)[:, :k]
    got = topk[jass_rows]
    if not np.array_equal(got, want):
        raise RuntimeError(f"JASS top-{k} ids differ from the numpy oracle")
    if not np.array_equal(topk_sc[jass_rows],
                          np.take_along_axis(acc, want, 1)):
        raise RuntimeError(f"JASS top-{k} scores differ from the oracle")
    say("parity", f"JASS rows={len(jass_rows)}: top-{k} ids and scores "
        "equal the numpy oracle exactly")

    acc, _ = oracle.exhaustive_scores(index, terms, mask, bmw_rows)
    want = _ranked(acc)[:, :k]
    got = topk[bmw_rows]
    want_sc = np.take_along_axis(acc, want, 1)
    got_sc_oracle = np.take_along_axis(acc, got, 1)
    tol = 1e-4
    if (any(len(set(r)) != k for r in got)
            or np.abs(topk_sc[bmw_rows] - got_sc_oracle).max() > tol
            or np.abs(got_sc_oracle - want_sc).max() > tol):
        raise RuntimeError(f"BMW top-{k} differs from the exhaustive oracle")
    same = int(np.sum(got == want))
    say("parity", f"BMW rows={len(bmw_rows)}: rank-safe top-{k} equals the "
        f"exhaustive oracle at every rank to {tol} ({same}/{got.size} ids "
        "identical; the rest are float32 near-ties)")

    cand = np.asarray(topk, np.int32)
    qcap = query_lane_budget(index.df, terms, mask)
    feats = {b: np.asarray(qd_features_batched(
        system.s2, terms, mask, topics, cand, n_iter=system.n_iter,
        backend=b, qcap=qcap if b != "jnp" else None))
        for b in (backend, "jnp")}
    a, b = feats[backend], feats["jnp"]
    exact = (0, 2, 3, 5, 6, 7)
    if (not np.allclose(a, b, rtol=0, atol=tol)
            or not np.array_equal(a[..., exact], b[..., exact])):
        raise RuntimeError("Stage-2 features differ from the jnp backend")
    say("parity", f"Stage-2 features {a.shape}: qd_feature_gather == jnp "
        f"(sums to {tol}, max abs diff {np.abs(a - b).max():.3g}; "
        "counts/max/gathers exact)")


def _kernel_check(name, lowered, backend):
    text = lowered.as_text()
    if backend != "pallas":
        say("kernels", f"{name}: interpret mode, no tpu_custom_call expected")
        return
    if "tpu_custom_call" not in text:
        raise RuntimeError(f"{name}: no tpu_custom_call in the lowered call")
    say("kernels", f"{name}: tpu_custom_call present")


def kernels_phase(system, queries, backend, dev):
    import jax.numpy as jnp

    from repro.isn.backend import query_lane_budget
    from repro.isn.daat import daat_serve
    from repro.isn.saat import saat_serve
    from repro.ltr.ranker import qd_features_batched

    terms, mask, topics = _rows(queries, 512, 512 + BATCH)
    shard, sp = system.shards[0], system.shard_specs[0]
    t, m = jnp.asarray(terms), jnp.asarray(mask)
    _kernel_check("impact_accumulate (saat_serve)", saat_serve.lower(
        shard, t, m, jnp.full(BATCH, 4096.0), n_docs=sp.n_docs,
        k=system.k_serve, cap=int(system.sched.cfg.rho_max),
        tile_d=sp.tile_d, backend=backend), backend)
    _kernel_check("blockmax_score (daat_serve)", daat_serve.lower(
        shard, t, m, jnp.ones(BATCH), n_docs=sp.n_docs,
        n_blocks=sp.n_blocks, block_size=sp.block_size, k=system.k_serve,
        cap=sp.max_df, bcap=sp.max_blocks_per_term, tile_d=sp.tile_d,
        backend=backend), backend)
    cand = jnp.zeros((BATCH, system.k_serve), jnp.int32)
    _kernel_check("qd_feature_gather (qd_features_batched)",
                  qd_features_batched.lower(
                      system.s2, t, m, jnp.asarray(topics), cand,
                      n_iter=system.n_iter, backend=backend,
                      qcap=query_lane_budget(system.index.df, terms, mask)),
                  backend)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    say("kernels", f"peak_bytes_in_use={peak}")


def dense_phase(args, backend, index, corpus, queries):
    import jax
    import jax.numpy as jnp

    from repro.dense import M_LEX
    from repro.kernels.dense_topk import dense_topk, dense_topk_oracle
    from repro.serving.system import build_system

    t0 = time.perf_counter()
    system = build_system(_spec("hybrid_fusion", backend), index,
                          corpus=corpus)
    system.fit(_train(queries))
    terms, mask, topics = _rows(queries, 512, 512 + BATCH)
    res = system.serve(terms, mask, topics)
    jax.block_until_ready((res.topk, res.final))
    n_dense = int(np.sum(res.dense["modality"] != M_LEX))
    say("dense", f"hybrid_fusion batch served in "
        f"{time.perf_counter() - t0:.1f}s (build, fit, compile included): "
        f"rows with dense Stage-1={n_dense}")
    if n_dense <= 0:
        raise RuntimeError("no row reached the dense engine")

    eng = system.dense
    q_emb = eng.embed(terms[:16], mask[:16])
    emb = eng.shard_emb[0]
    k = system.k_serve
    sc, ids = dense_topk(jnp.asarray(q_emb), emb, k, tile_d=eng.tile_d,
                         backend=backend)
    o_sc, o_ids = dense_topk_oracle(q_emb, np.asarray(emb), k)
    if not (np.array_equal(np.asarray(sc), o_sc)
            and np.array_equal(np.asarray(ids, np.int64), o_ids)):
        raise RuntimeError("dense top-k differs from the numpy oracle")
    say("parity", f"dense top-{k} over {emb.shape} embeddings equals the "
        "numpy oracle bitwise (ties to the lower doc id)")
    _kernel_check("dense_topk", dense_topk.lower(
        jnp.asarray(q_emb), emb, k, tile_d=eng.tile_d, backend=backend),
        backend)


if __name__ == "__main__":
    sys.exit(main())
