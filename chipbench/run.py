#!/usr/bin/env python3
"""Run one benchmark cell once: open-loop queries through ``SearchSystem.serve``.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` → ``chipbench/configs/<config>.json`` and
``chipbench/workloads/<traffic>.json``) names the deployment and the traffic.
Set-up makes the collection, the query stream and the forests from the seed,
builds the index and the system with the program, and serves every batch
shape the window uses once.  The window then offers queries on a seeded
Poisson schedule on the real clock; the program's ``MicroBatcher`` and
``pad_batch`` form each batch and every query is timed from its due time to
its answer.  After the window a seeded sample of the answered queries is
compared with the plain reference (``reference.py``).

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and ``checks`` last).  Without a TPU it exits 2 and prints
no result; ``--rehearse`` (the benchmark's own tests only) runs a tiny cell
on the CPU and reports no device metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import deque  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import gen  # noqa: E402
import reference as R  # noqa: E402
import traffic  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
N_TRAIN = 512
WARM_THREADS = 8
# the rehearsal: a tiny shard on the CPU's jnp backend, batches of up to 4
REHEARSAL = {"n_docs": 2048, "max_batch": 4, "backend": "jnp"}


def host_gib() -> str:
    """This process's peak resident memory and the host's memory in use."""
    import resource
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
    info = {}
    with open("/proc/meminfo") as f:
        for row in f:
            key, val = row.split(":", 1)
            info[key] = int(val.split()[0])
    used = (info["MemTotal"] - info["MemAvailable"]) / 2**20
    return f"rss-peak {peak:.1f} GiB, host in use {used:.1f} GiB"


def say(msg: str) -> None:
    print(f"[chipbench +{time.perf_counter() - T_START:.1f}s] {msg} "
          f"({host_gib()})", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, by name
# ---------------------------------------------------------------------------

def load_cell(name: str, root: Path = ROOT) -> dict:
    """The workload entry, its configuration file and traffic file, and the
    metrics the cell reports."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell, = [w for w in bench["workloads"] if w["name"] == name]
    conf, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    return {
        "cell": cell,
        "config": json.loads((root / conf["file"]).read_text()),
        "traffic": json.loads(
            (HERE / "workloads" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": bench["end_to_end"],
        "per_layer": bench["per_layer"],
    }


def metric_reader(name: str):
    return importlib.import_module(f"metrics.{name}")


def build_spec(config: dict, backend: str):
    """The preset with the configuration's overrides (one dict per spec
    node) and the serving backend."""
    from repro.configs.cascade_presets import get_preset

    spec = get_preset(config["preset"])
    nodes = {k: dataclasses.replace(getattr(spec, k), **v)
             for k, v in config["overrides"].items()}
    nodes["backend"] = dataclasses.replace(spec.backend, backend=backend)
    return dataclasses.replace(spec, **nodes).validate()


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

class Inputs:
    """Everything one run makes from the seed: the collection, the
    reference over it, the forests, the routing thresholds and the query
    stream with its due times."""

    def __init__(self, c: dict, seed: int, rehearse: bool):
        self.c, self.seed = c, seed
        cfg, tr = c["config"], c["traffic"]
        corpus_cfg = dict(cfg["corpus"])
        backend = "pallas"
        if rehearse:
            corpus_cfg["n_docs"] = REHEARSAL["n_docs"]
            cfg = dict(cfg, overrides=dict(cfg["overrides"], online=dict(
                cfg["overrides"].get("online", {}),
                max_batch=REHEARSAL["max_batch"])))
            backend = REHEARSAL["backend"]
        spec = build_spec(cfg, backend)
        t = time.perf_counter()
        self.corpus = gen.make_corpus(corpus_cfg,
                                      corpus_cfg["collection_seed"])
        say(f"collection made: {self.corpus.n_docs} docs, "
            f"{self.corpus.n_postings} postings, {self.corpus.vocab} terms "
            f"({time.perf_counter() - t:.1f}s)")
        self.ref = R.Reference(self.corpus, spec.index.stop_k)
        # the deployment (collection, forests, the window's query set and
        # arrival gaps) comes from the collection seed; the run's seed
        # orders the queries and the gaps and draws the checked sample, so
        # every run offers the same work in another order
        fixed = corpus_cfg["collection_seed"]
        q = dict(tr["queries"], stop_k=spec.index.stop_k)
        train = gen.make_queries(self.corpus, q, N_TRAIN, fixed, stream=5)
        self.models, self.s0_parts, self.s0_edges = gen.stage0_models(
            self.ref, train, cfg, spec.stage0, fixed)
        self.sched = self._calibrate(train, spec.routing)
        self.spec = dataclasses.replace(spec, routing=dataclasses.replace(
            spec.routing, t_k=self.sched["t_k"],
            t_time=self.sched["t_time"]))
        self.ltr, self.s2_parts, self.s2_edges = gen.ltr_model(
            self.ref, train, spec.stage2, cfg, fixed)
        self.k_serve = spec.stage2.k_serve
        self.t_final = spec.stage2.t_final
        self.due = traffic.poisson_due_ms(tr["arrivals"]["rate_qps"],
                                          c["seconds"], fixed, seed)
        ql = gen.make_queries(self.corpus, q, len(self.due), fixed, stream=6)
        order = gen.rng_for(seed, 6).permutation(len(self.due))
        self.queries = type(ql)(ql.terms[order], ql.mask[order],
                                ql.topic[order], ql.lengths[order])
        self.warm = gen.make_queries(self.corpus, q, spec.online.max_batch,
                                     fixed, stream=7)
        self.mass = (self.ref.df[self.queries.terms]
                     * (self.queries.mask > 0)).sum(axis=1)
        self.sample = sample_ids(len(self.due), seed,
                                 tr["check"]["sample"], self.mass)
        say(f"inputs made; {len(self.due)} queries due in the window")

    def _calibrate(self, train, r) -> dict:
        """Routing thresholds: by the program's ``calibrate`` rule, from the
        reference predictions over the training queries, where the spec
        asks for it, else the spec's own."""
        out = {"t_k": float(r.t_k), "t_time": float(r.t_time),
               "rho_min": int(r.rho_min), "rho_max": int(r.rho_max)}
        if r.calibrate:
            xb, _ = self.ref.stage0_bins(train.terms, train.mask,
                                         self.s0_edges)
            pk = np.expm1(R.forest_score(self.s0_parts["k"], xb))
            pt = np.expm1(R.forest_score(self.s0_parts["t"], xb))
            out["t_k"] = float(np.percentile(pk, 60))
            out["t_time"] = float(min(r.budget * 0.75,
                                      np.percentile(pt, 75)))
        return out


class Cell(Inputs):
    """The seeded inputs and the program's system built over them."""

    def __init__(self, c: dict, seed: int, rehearse: bool):
        from repro.index.builder import build_index
        from repro.serving.system import build_system

        super().__init__(c, seed, rehearse)
        spec = self.spec
        t = time.perf_counter()
        index = build_index(self.corpus, block_size=spec.index.block_size,
                            stop_k=spec.index.stop_k)
        say(f"index built ({time.perf_counter() - t:.1f}s): "
            f"{index.n_postings} postings")
        t = time.perf_counter()
        self.system = build_system(spec, index, corpus=self.corpus)
        self.system.set_models(self.models, self.ltr)
        sp = self.system.shard_specs[0]
        self.shapes = {"n_docs": sp.n_docs, "n_tiles": sp.n_tiles,
                       "tile_cap": sp.tile_cap, "tile_d": sp.tile_d,
                       "k_serve": self.system.k_serve,
                       "slots": int(self.queries.terms.shape[1]),
                       "df": np.asarray(index.df, np.int64)}
        say(f"build_system + set_models {time.perf_counter() - t:.1f}s: "
            f"n_tiles={sp.n_tiles} tile_cap={sp.tile_cap}")

    def warm_up(self) -> None:
        """Run every program the window can use once.

        A window batch is some of the cell's fixed query set, padded to one
        of the batcher's widths.  The program routes each query on its own,
        and sizes the lane budget ``qcap`` of Stage-1 BMW and of Stage-2 as
        the largest budget of the queries in the (sub-)batch; it compiles
        JASS per sub-batch size, BMW per (sub-batch size, ``qcap``) and
        Stage-2 per (width, ``qcap``).  So set-up routes the query set once,
        takes each budget that occurs, and runs every such combination on
        copies of a query that has that budget.  An engine that serves
        every query sees whole batches, so only the batch widths; one that
        serves some sees any sub-batch size; one that serves none, none."""
        import jax

        from repro.isn.backend import query_lane_budget
        from repro.serving.online.batcher import bucket_size
        from repro.serving.scheduler import RoutedBatch

        t = time.perf_counter()
        system, q, mb = self.system, self.queries, self.spec.online.max_batch
        n, k = len(q.terms), self.system.k_serve
        widths = sorted({bucket_size(m, mb) for m in range(1, mb + 1)})
        for w in widths:
            res = system.serve(q.terms[:w], q.mask[:w], q.topic[:w])
            jax.block_until_ready((res.topk, res.final))
        stats = dict(system.sched.stats)
        bmw = np.zeros(n, bool)
        for lo in range(0, n, mb):
            rows = np.resize(np.arange(lo, min(lo + mb, n)), mb)
            routed = system.sched.route(*system.stage0(q.terms[rows],
                                                       q.mask[rows]))
            bmw[rows[routed.bmw_rows]] = True
        system.sched.stats.update(stats)

        def sizes(routed):
            if not routed.any():
                return []
            return widths if routed.all() else list(range(1, mb + 1))

        def budgets(df, rows):
            """{qcap: a query that has it} over ``rows``."""
            return {query_lane_budget(df, q.terms[i:i + 1],
                                      q.mask[i:i + 1]): i for i in rows}
        s1 = budgets(system._df_host[0], np.flatnonzero(bmw))
        s2 = budgets(system.index.df, range(n))

        def stage1(job):
            engine, size, i = job
            rows = np.arange(size)
            none = np.zeros(0, np.int64)
            system.stage1(q.terms[[i] * size], q.mask[[i] * size],
                          RoutedBatch(
                              jass_rows=rows if engine == "jass" else none,
                              bmw_rows=rows if engine == "bmw" else none,
                              hedged_rows=none,
                              k=np.full(size, k, np.int64),
                              rho=np.full(size, self.sched["rho_max"],
                                          np.int64)))

        def stage2(job):
            w, i = job
            res = system.stage2(q.terms[[i] * w], q.mask[[i] * w],
                                q.topic[[i] * w],
                                np.tile(np.arange(k, dtype=np.int32), (w, 1)),
                                np.full(w, k, np.int64))
            return res.final
        jass = np.flatnonzero(~bmw)
        jobs1 = ([("jass", m, int(jass[0])) for m in sizes(~bmw)]
                 + [("bmw", m, i) for m in sizes(bmw) for i in s1.values()])
        jobs2 = [(w, i) for w in widths for i in s2.values()]
        # compiles overlap on the host's cores; the chip runs one at a time
        with ThreadPoolExecutor(WARM_THREADS) as pool:
            list(pool.map(stage1, jobs1))
            list(pool.map(stage2, jobs2))
        say(f"warm-up {time.perf_counter() - t:.1f}s: {len(widths)} batch "
            f"widths, {len(jobs1)} Stage-1 and {len(jobs2)} Stage-2 "
            f"programs ({len(s1)} BMW and {len(s2)} Stage-2 lane budgets)")


# ---------------------------------------------------------------------------
# the window: open loop on the real clock
# ---------------------------------------------------------------------------

def _sleep_until(t0: float, ms: float) -> float:
    """Sleep to ``ms`` after ``t0``; returns how late the wake-up was (ms)."""
    d = t0 + ms / 1e3 - time.perf_counter()
    if d > 0:
        time.sleep(d)
    return (time.perf_counter() - t0) * 1e3 - ms


def run_window(cell: Cell, drain_s: float, sample: set) -> dict:
    """Offer every due query, serve micro-batches until the queue drains
    (or ``drain_s`` after the window closes), and record each query's
    dispatch and answer times and the sampled queries' answers."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.serving.online.batcher import MicroBatcher, pad_batch

    system, q, due = cell.system, cell.queries, cell.due
    spec = cell.spec.online
    batcher = MicroBatcher(spec)
    n = len(due)
    dispatch = np.full(n, np.nan)
    done = np.full(n, np.nan)
    batches, answers, late = [], {}, []
    pending: deque = deque()
    nxt = 0
    stop_ms = cell.c["seconds"] * 1e3 + drain_s * 1e3
    t0 = time.perf_counter()
    with TraceAnnotation("window"):
        while True:
            now = (time.perf_counter() - t0) * 1e3
            while nxt < n and due[nxt] <= now:
                pending.append(nxt)
                nxt += 1
            if now > stop_ms:
                break
            if not pending:
                if nxt >= n:
                    break
                with TraceAnnotation("wait_arrival"):
                    late.append(_sleep_until(t0, due[nxt]))
                continue
            with TraceAnnotation("form_batch"):
                take, close = batcher.close(due[list(pending)], now)
                wait_to = close
                if nxt < n:
                    wait_to = min(wait_to, due[nxt])
                if close > now and take < spec.max_batch:
                    _sleep_until(t0, wait_to)
                    continue
                rows = np.asarray([pending.popleft() for _ in range(take)])
                padded, _ = pad_batch(rows, spec.max_batch, spec.bucket_q)
            dispatch[rows] = (time.perf_counter() - t0) * 1e3
            before = dict(system.sched.stats)
            system._debug_shard_lists = []
            ts = time.perf_counter()
            with TraceAnnotation("serve"):
                res = system.serve(q.terms[padded], q.mask[padded],
                                   q.topic[padded])
                jax.block_until_ready((res.topk, res.final))
            te = time.perf_counter()
            done[rows] = (te - t0) * 1e3
            b = {"rows": rows, "q": len(padded), "serve_s": te - ts,
                 "jass": system.sched.stats["jass"] - before["jass"],
                 "bmw": system.sched.stats["bmw"] - before["bmw"],
                 "used": res.candidates_used[:len(rows)].copy()}
            batches.append(b)
            _keep_answers(answers, sample, rows, res, b,
                          system._debug_shard_lists)
            system._debug_shard_lists = None
    return {"due": due, "dispatch": dispatch, "done": done,
            "batches": batches, "answers": answers, "late_ms": late,
            "window_s": (time.perf_counter() - t0)}


def _keep_answers(answers, sample, rows, res, b, lists) -> None:
    """Record what the timed path produced for the sampled queries: the
    engine that served each, its Stage-1 list, the merged candidates, the
    Stage-2 depth and the final ranking."""
    engines = []
    if b["jass"]:
        engines.append(("jass", lists[0]))
    if b["bmw"]:
        engines.append(("bmw", lists[-1]))
    for pos, qid in enumerate(rows):
        if int(qid) not in sample:
            continue
        got = {"topk": res.topk[pos].copy(), "final": res.final[pos].copy(),
               "used": int(res.candidates_used[pos])}
        for eng, (erows, scs, ids) in engines:
            hit = np.flatnonzero(erows == pos)
            if len(hit):
                got["engine"] = eng
                got["ids"] = np.asarray(ids[0][hit[0]])
                got["scores"] = np.asarray(scs[0][hit[0]])
        answers[int(qid)] = got


# ---------------------------------------------------------------------------
# correctness: the served answers against the plain reference
# ---------------------------------------------------------------------------

def compare(cell: Cell, answers: dict, ref: R.Reference) -> dict:
    """The numbers compared, over the sampled answered queries:

    ``route_mismatch``  queries whose serving engine is not the reference's
                        route (queries within float32 rounding of a split
                        or a threshold are left out and counted apart);
    ``jass_mismatch``   JASS-served queries whose list is not, id for id,
                        the reference traversal's top k under the same
                        budget, equal scores to the lower doc id (the
                        program's tie rule, which merging shards rests on);
    ``bmw_gap``         widest gap, over BMW-served ranks, by which the
                        served doc's exhaustive BM25 lies below the
                        reference's doc at that rank, over the top score;
    ``final_gap``       widest gap, over final ranks, by which the served
                        doc's Stage-2 score lies below the reference's
                        best at that rank, over the candidates' score
                        range (inf when a served doc is not a candidate)."""
    q = cell.queries
    qids = np.asarray(sorted(answers))
    is_jass, _, rho_lo, rho_hi, amb = ref.route(
        cell.s0_parts, cell.s0_edges, q.terms[qids], q.mask[qids],
        cell.sched)
    out = {"route_mismatch": 0, "jass_mismatch": 0, "bmw_gap": 0.0,
           "final_gap": 0.0, "route_unverified": 0, "sampled": len(qids)}
    k = cell.k_serve
    for i, qid in enumerate(qids):
        a = answers[qid]
        terms, mask = q.terms[qid], q.mask[qid]
        eng = a.get("engine")
        if amb[i]:
            out["route_unverified"] += 1
        elif eng != ("jass" if is_jass[i] else "bmw"):
            out["route_mismatch"] += 1
        if eng == "jass":
            want, ok = [], False
            for r in sorted({int(rho_lo[i]), int(rho_hi[i])}):
                ids, sc = ref.jass_list(terms, mask, r, k)
                want.append((ids, sc))
                ok |= np.array_equal(np.asarray(a["ids"]), ids)
            out["jass_mismatch"] += int(not ok)
            if not ok:
                ids, sc = want[0]
                diff = np.flatnonzero(np.asarray(a["ids"]) != ids)
                say(f"JASS mismatch: query {qid} rho {int(rho_lo[i])}.."
                    f"{int(rho_hi[i])} cut {ref.last_cut} ranks differing "
                    f"{len(diff)} from rank {diff[0]}: served "
                    f"{a['ids'][diff[0]]} ({a['scores'][diff[0]]}) "
                    f"reference {ids[diff[0]]} ({sc[diff[0]]}); served "
                    f"sum {float(np.sum(a['scores']))} reference sum "
                    f"{float(np.sum(sc))}")
        elif eng == "bmw":
            out["bmw_gap"] = max(out["bmw_gap"],
                                 list_gap(ref.bm25_acc(terms, mask),
                                          a["ids"]))
        out["final_gap"] = max(out["final_gap"], final_gap(
            cell, ref, qid, a))
    return out


def list_gap(acc: np.ndarray, served: np.ndarray) -> float:
    """Widest rank-by-rank shortfall of a served top-k against the top-k of
    ``acc``, over the best score; inf for a repeated or foreign id."""
    served = np.asarray(served, np.int64)
    if len(set(served.tolist())) != len(served) or served.min() < 0 \
            or served.max() >= len(acc):
        return float("inf")
    best = np.sort(acc)[::-1][:len(served)]
    return float(np.max(best - acc[served]) / max(best[0], 1e-30))


def final_gap(cell: Cell, ref: R.Reference, qid: int, a: dict) -> float:
    q, t_final = cell.queries, cell.t_final
    used, final = a["used"], np.asarray(a["final"], np.int64)
    if used == 0:
        return 0.0 if np.array_equal(final, a["topk"][:t_final]) \
            else float("inf")
    cand = np.asarray(a["topk"][:used], np.int64)
    sc = ref.ltr_scores(cell.s2_parts, cell.s2_edges, q.terms[qid],
                        q.mask[qid], int(q.topic[qid]), cand)
    n = min(t_final, used)
    if np.any(final[n:] != -1) or len(set(final[:n].tolist())) != n \
            or not np.all(np.isin(final[:n], cand)):
        return float("inf")
    pos = {int(d): j for j, d in enumerate(cand)}
    got = sc[[pos[int(d)] for d in final[:n]]]
    best = np.sort(sc)[::-1][:n]
    span = max(float(sc.max() - sc.min()), 1e-30)
    return float(np.max(best - got) / span)


def checks(numbers: dict, limits: dict) -> dict:
    return {name: {"value": numbers[name], "limit": limits[name]}
            for name in limits}


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def end_to_end(rec: dict, seconds: float, setup_s: float) -> dict:
    resp = rec["done"] - rec["due"]
    drained = rec["window_s"] * 1e3
    # an unanswered query counts at least the whole drain it waited
    resp = np.where(np.isnan(resp), drained - rec["due"], resp)
    return {"resp_p50_ms": float(np.percentile(resp, 50)),
            "resp_p95_ms": float(np.percentile(resp, 95)),
            "setup_s": setup_s}


def sample_ids(n: int, seed: int, size: int, mass: np.ndarray) -> set:
    """Seeded sample of the window's queries, with the one of largest
    posting mass (the longest) in it."""
    rng = gen.rng_for(seed, 8)
    pick = set(rng.choice(n, size=min(size, n), replace=False).tolist())
    pick.add(int(np.argmax(mass)))
    return pick


def compile_cache() -> None:
    """Keep every program in JAX's persistent compilation cache, at a fixed
    path in the checkout or where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(HERE / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny CPU run for the benchmark's own tests; "
                         "prints no device metric")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    c = load_cell(args.workload)
    c["seconds"] = args.seconds

    import jax

    devs = jax.devices()
    if not args.rehearse and (devs[0].platform != "tpu"
                              or len(devs) < c["cell"]["chips"]):
        say(f"needs {c['cell']['chips']} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return 2
    if not args.rehearse:
        compile_cache()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(secs)
        if name == COMPILE_EVENT else None)

    cell = Cell(c, args.seed, args.rehearse)
    cell.warm_up()
    tr = c["traffic"]
    trace_dir = HERE / "traces" / args.workload
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    n_warm = len(compiles)
    setup_s = time.perf_counter() - T_START
    say(f"set-up {setup_s:.1f}s ({n_warm} compiles, {sum(compiles):.1f}s "
        "of them); window opens")
    rec = run_window(cell, tr["drain_s"], cell.sample)
    if args.trace:
        jax.profiler.stop_trace()
    rec["compiles"] = len(compiles) - n_warm
    rec["compile_s"] = sum(compiles[n_warm:])
    say(f"window closed after {rec['window_s']:.1f}s: "
        f"{len(rec['batches'])} batches, {rec['compiles']} compiles "
        f"({rec['compile_s']:.1f}s); "
        f"generator late mean "
        f"{np.mean(rec['late_ms']) if rec['late_ms'] else 0:.3f} ms "
        f"max {np.max(rec['late_ms']) if rec['late_ms'] else 0:.3f} ms")
    dev = devs[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}

    attempted = int(np.sum(rec["due"] < args.seconds * 1e3))
    failed = int(np.sum(np.isnan(rec["done"])))
    metrics, breakdown = {}, None
    if not args.rehearse:
        if args.trace:
            import devtrace as T
            tred = T.load(str(trace_dir))
            ctx = metric_context(cell, rec, tred)
            device["busy_s"], device["window_s"] = ctx["busy_s"], \
                ctx["window_s"]
            for m in c["per_layer"]:
                v = metric_reader(m["name"]).read(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            breakdown = {"device_ops": T.top_ops(ctx["events"]),
                         "idle_gaps": T.idle_gaps(ctx["events"], tred["host"],
                                                  *ctx["window_ns"])}
        else:
            e2e = end_to_end(rec, args.seconds, setup_s)
            metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                       for m in c["end_to_end"]}

    answers = {k: v for k, v in rec["answers"].items()
               if not np.isnan(rec["done"][k])}
    ref = cell.ref
    del cell.system
    gc.collect()
    t = time.perf_counter()
    numbers = compare(cell, answers, ref)
    say(f"reference check {time.perf_counter() - t:.1f}s over "
        f"{numbers['sampled']} queries "
        f"({numbers['route_unverified']} routes unverifiable)")
    chk = checks(numbers, tr["check"]["limits"])
    correct = bool(numbers["sampled"] > 0 and all(
        v["value"] <= v["limit"] for v in chk.values()))
    for name, v in chk.items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = chk
    print(json.dumps(line))
    return 0


def metric_context(cell: Cell, rec: dict, tred: dict) -> dict:
    """What the per-layer readers read: the run record, the chip's device
    events inside the window, the shard shapes and the device's peaks."""
    import jax

    import devtrace as T

    lo, hi = T.window(tred["host"])
    plane = sorted(tred["device"])[0]
    events = [ev for ev in tred["device"][plane] if lo <= ev[1] <= hi]
    peaks = json.loads((HERE / "peaks.json").read_text())
    kind = jax.devices()[0].device_kind
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    busy = T.busy_ns(events, lo, hi) * 1e-9
    return {"rec": rec, "events": events, "window_ns": (lo, hi),
            "busy_s": busy, "window_s": (hi - lo) * 1e-9,
            "shapes": cell.shapes, "queries": cell.queries,
            "peaks": peaks[kind], "compiles": rec["compiles"]}


if __name__ == "__main__":
    sys.exit(main())
