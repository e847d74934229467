"""``SearchSystem``: one declarative spec → a multi-shard serving cascade.

This is the unified serving facade the paper's framework implies: a
:class:`~repro.serving.spec.CascadeSpec` names an operating point and
``build_system`` instantiates the full lifecycle —

    spec = get_preset("paper_200ms")
    system = build_system(spec, corpus)      # builds + shards the index
    system.fit(ql, labels)                   # Stage-0 predictors + LTR
    res = system.serve(ql.terms, ql.mask, ql.topic)
    system.stats()                           # tails + pool health

Deployment shape (``DeploySpec``)
---------------------------------
The index is partitioned into ``n_shards`` contiguous **doc-range shards**
(``shard_from_index`` over ``shard_ranges``).  Stage-1 serves an ordered
list of segments, the sealed shards then (ingest on) the live delta:
``stage1`` makes one ``saat_serve_segments`` / ``daat_serve_segments``
call per engine branch, which runs the batched SAAT/DAAT engine on every
segment and merges the per-segment top-k with ``merge_shard_topk``.  The
merge ranks (score desc, global doc id asc), so score ties break toward
the **lower global doc id**, exactly the tie-break of a single-shard run.

Multi-shard exactness: DAAT is rank-safe per shard, so the merged top-k is
the exact global top-k.  For SAAT, the ρ budget resolves to a **global**
impact-level cut (from the full-collection level table); each shard then
processes exactly its slice of that cut's posting set, so the union equals
the single-shard traversal and — accumulation being integer — the merged
top-k matches bit-for-bit.

Latency is scatter-gather: a query finishes when its *slowest* shard
responds (``CostModel.gather_time`` = max over shards + fan-out overhead)
— the tail is a max, which is the paper's tail-latency story at deployment
scale.  Each partition is backed by a :class:`~repro.serving.replicas.
ReplicaPool` of BMW/JASS mirror replicas: every served query routes through
power-of-two-choices replica selection, observed per-(query, shard)
latencies feed the pool's EWMA estimates back, and the mirror split is
re-balanced online toward the scheduler's observed routing mix.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import features as F
from repro.core import gbrt
from repro.dense import (M_BOTH, M_DENSE, M_LEX, DenseEngine,
                         build_embeddings, fuse)
from repro.dense.embeddings import delta_doc_embeddings
from repro.dense.engine import SCORE_FILL
from repro.index.builder import InvertedIndex, build_index
from repro.index.corpus import Corpus, FeedDocs
from repro.index.delta import DeltaStore
from repro.index.postings import shard_from_index, shard_ranges
from repro.isn.backend import Segment, query_lane_budget, resolve_backend
# the per-segment engines ``stage1`` hands its fan-outs, bound here so that
# a test can replace them on the serve path
from repro.isn.daat import daat_serve, daat_serve_segments
from repro.isn.saat import saat_serve, saat_serve_segments
from repro.ltr.cascade import CascadeResult, rerank_batched
from repro.ltr.ranker import (LTRModel, csr_search_iters, ltr_training_set,
                              qd_features, stage2_arrays, train_ltr)
from repro.serving.cache import (HEALTHY_EPOCH, ServingCache, ingest_epoch,
                                 l1_key, l2_key, normalize_query, route_sig)
from repro.serving.faults import FaultInjector
from repro.serving.latency import (CostModel, budget_attribution,
                                   over_budget, percentiles,
                                   resolve_level_cut, stage2_afford)
from repro.serving.online.batcher import bucket_size
from repro.serving.replicas import BMW, JASS, PoolConfig, ReplicaPool
from repro.serving.scheduler import (RoutedBatch, SchedulerConfig,
                                     StageZeroScheduler)
from repro.serving.spec import CascadeSpec, RoutingSpec
from repro.serving.telemetry import QueryTrace, Span, Telemetry
from repro.serving.telemetry.spans import (ACCOUNT, CACHE, REPLICAS, SERVE,
                                           STAGE0, STAGE1, STAGE1_BMW,
                                           STAGE1_JASS, STAGE2, fetch, span)
from repro.serving.telemetry.export import (legacy_stats_view,
                                            render_json,
                                            render_prometheus)


@dataclass
class PipelineResult:
    """One served batch, end to end."""
    topk: np.ndarray                 # (Q, k_serve) Stage-1 candidates
    final: np.ndarray | None         # (Q, t_final) re-ranked (None: no LTR)
    candidates_used: np.ndarray | None   # (Q,) candidates entering Stage-2
    latency: np.ndarray              # (Q,) full-cascade latency
    stage_latency: dict              # {"stage0"|"stage1"|"stage2": (Q,)}
    stats: dict
    coverage: np.ndarray | None = None   # (Q,) fraction of partitions that
                                         # answered (None: full coverage,
                                         # no fault/partial path engaged)
    dense: dict | None = None        # {"modality", "theta_skip",
                                     #  "fallback"} (Q,) vectors (None:
                                     #  dense modality disabled)


def scheduler_config(routing: RoutingSpec) -> SchedulerConfig:
    """The runtime scheduler configuration a RoutingSpec describes."""
    return SchedulerConfig(
        algorithm=routing.algorithm, t_k=routing.t_k, t_time=routing.t_time,
        rho_max=routing.rho_max, rho_min=routing.rho_min,
        budget=routing.budget, hedge_band=routing.hedge_band,
        enable_hedging=routing.enable_hedging,
        hedge_deadline=routing.hedge_deadline, late_rho=routing.late_rho,
        enforce_budget=routing.enforce_budget,
        failover_timeout=routing.failover_timeout,
        max_retries=routing.max_retries)


def routing_spec(cfg: SchedulerConfig) -> RoutingSpec:
    """The RoutingSpec describing a runtime SchedulerConfig."""
    return RoutingSpec(
        algorithm=cfg.algorithm, t_k=cfg.t_k, t_time=cfg.t_time,
        rho_max=cfg.rho_max, rho_min=cfg.rho_min, budget=cfg.budget,
        hedge_band=cfg.hedge_band, enable_hedging=cfg.enable_hedging,
        hedge_deadline=cfg.hedge_deadline, late_rho=cfg.late_rho,
        enforce_budget=cfg.enforce_budget,
        failover_timeout=cfg.failover_timeout, max_retries=cfg.max_retries)


def build_system(spec: CascadeSpec, corpus_or_index, *, corpus=None,
                 models: dict | None = None, ltr: LTRModel | None = None,
                 cost: CostModel | None = None) -> "SearchSystem":
    """Instantiate the deployment a spec describes.

    ``corpus_or_index`` is either a :class:`Corpus` (the index is built
    with the spec's ``IndexSpec``) or a pre-built :class:`InvertedIndex`
    (pass ``corpus=`` separately if Stage-2 needs doc topics).  Pre-trained
    ``models``/``ltr`` can be attached directly; otherwise call
    :meth:`SearchSystem.fit`.

    With a pre-built index the spec's ``block_size`` is reconciled from
    the index (the index is ground truth), so ``to_json()`` describes the
    deployed layout; ``stop_k`` is not recoverable from a built index —
    when shipping a spec for rebuild elsewhere, keep it truthful.
    """
    if isinstance(corpus_or_index, InvertedIndex):
        index = corpus_or_index
    elif isinstance(corpus_or_index, Corpus):
        corpus = corpus_or_index if corpus is None else corpus
        index = build_index(corpus_or_index,
                            block_size=spec.index.block_size,
                            stop_k=spec.index.stop_k)
    else:
        raise TypeError("build_system needs a Corpus or an InvertedIndex, "
                        f"got {type(corpus_or_index).__name__}")
    return SearchSystem(spec, index, corpus=corpus, models=models, ltr=ltr,
                        cost=cost)


class SearchSystem:
    """A spec-built multi-shard cascade with the full serving lifecycle."""

    def __init__(self, spec: CascadeSpec, index: InvertedIndex, *,
                 corpus=None, models: dict | None = None,
                 ltr: LTRModel | None = None, cost: CostModel | None = None):
        if index.block_size != spec.index.block_size:
            # the built index is ground truth for its own layout; fold it
            # back so spec.to_json() describes the deployed system
            spec = replace(spec, index=replace(spec.index,
                                               block_size=index.block_size))
        spec.validate()
        self.cascade_spec = spec
        self.index = index
        self.corpus = corpus
        self.cost = cost or getattr(CostModel, spec.backend.cost)()
        self.k_serve = spec.stage2.k_serve
        self.t_final = spec.stage2.t_final
        self.backend = spec.backend.backend
        self.budget = spec.routing.budget
        self._base_cfg = scheduler_config(spec.routing)

        # ---- shard the index into doc-range partitions ----
        self._attach_index(index)

        # ---- live ingest (spec.ingest; inert by default) ----
        # None keeps every serve path, cache key, and timing term
        # bit-identical to the sealed-only system — the same discipline as
        # FaultSpec/CacheSpec/DenseSpec.  The delta scan's cost is a single
        # shape-static term (its arrays are capacity-padded), charged at
        # capacity to every served query and to worst_case_us().
        self.delta: DeltaStore | None = None
        self._delta_us = 0.0
        self._ingest_counters = {
            "epoch": 0,          # cache-epoch bumps (feeds + merges)
            "feed_batches": 0,   # applied ingest batches
            "docs_ingested": 0,  # docs accepted into the delta
            "merges": 0,         # background merges (reseals)
            "docs_merged": 0,    # docs folded into the sealed index
        }
        if spec.ingest.active:
            if spec.ingest.delta_docs < self.k_serve:
                raise ValueError(
                    f"ingest.delta_docs={spec.ingest.delta_docs} is below "
                    f"k_serve={self.k_serve}; the delta segment must be "
                    "able to answer a full candidate list")
            self.delta = DeltaStore(
                index, capacity_docs=spec.ingest.delta_docs,
                capacity_postings=spec.ingest.delta_postings,
                tile_d=spec.index.tile_d)
            self._delta_us = float(
                self.cost.delta_time(self.delta.capacity_postings))
            if self.dense is not None:
                # the dense delta segment is capacity-padded too, so its
                # tile count — and hence its cost term — is spec-static
                d_tiles = -(-self.delta.capacity_docs // self.dense.tile_d)
                self._delta_us += self.cost.dense_tile_us * d_tiles

        self.pool = ReplicaPool(
            PoolConfig(n_partitions=spec.deploy.n_shards,
                       replicas_per_partition=spec.deploy.replicas,
                       jass_fraction=spec.deploy.jass_fraction),
            seed=spec.deploy.seed)
        # deterministic fault injection (spec.fault; inert by default) +
        # the serving clock fault windows are evaluated against.  serve()
        # advances the clock by each batch's occupancy; the online
        # simulator drives it explicitly (now=dispatch time).
        self.faults = FaultInjector(spec.fault, spec.deploy.n_shards)
        self._clock = 0.0
        # two-level result/candidate cache (spec.cache; inert by default):
        # None keeps every serve path bit-identical to the uncached system
        # — the same inertness discipline as FaultSpec
        self.cache = (ServingCache(spec.cache) if spec.cache.active
                      else None)
        # deterministic observability (spec.telemetry; inert by default):
        # None keeps every serve path bit-identical to the pre-telemetry
        # system — every hook below guards on `self.telemetry is None`,
        # the same inertness discipline as FaultSpec/CacheSpec
        self.telemetry = (Telemetry(spec.telemetry, spec.routing.budget)
                          if spec.telemetry.active else None)
        self._tel_suppress = False    # True inside a cache-miss sub-serve
                                      # so batch metrics aren't double-fed
        self._tel_cache_tag = None    # "miss" tags sub-serve traces
        self._fault_counters = {
            "retries": 0,        # failover re-issues after a shard timeout
            "transient": 0,      # attempts killed by the timeout storm
            "down_requests": 0,  # attempts sent to a crashed/outaged replica
            "lost_partitions": 0,   # (query, shard) slots lost after retries
            "no_route": 0,       # partitions with no healthy replica at all
            "degraded_queries": 0,  # queries served with partial coverage
            "probes": 0,         # health probes sent to unhealthy replicas
            "recovered": 0,      # probes that re-admitted a replica
        }
        self._debug_shard_lists = None   # tests: set to [] to capture the
                                         # per-shard candidate lists
        # Stage-1 rows served through the deep top-k select (the engines
        # report it: SegmentLists.deep_select); 0 wherever k_serve <= tile_d
        self._stage1_counters = {"deep_select_rows": 0}
        self._batches = 0
        self._last_stats: dict = {}
        self._budget_reserve = self._attribute_budget(self.budget, None)
        self._adapt_last = {"late_hedged": 0, "bmw": 0}
        # rolling pinball loss of the t-predictor against observed BMW
        # engine times — drives the hedge_deadline adaptation (None until
        # a batch with BMW traffic has been served)
        self._pinball_ewma: float | None = None

        self.models: dict | None = None
        self.ltr: LTRModel | None = None
        self._stacked = None
        self.sched = StageZeroScheduler(self._base_cfg, self.cost)
        if models is not None:
            self.set_models(models, ltr)
        elif ltr is not None:
            raise ValueError("ltr without Stage-0 models — pass both")

    @property
    def n_shards(self) -> int:
        return len(self._sealed)

    @property
    def segments(self) -> list[Segment]:
        """Stage-1's segments in global doc order: the sealed doc-range
        shards, then (ingest on) the live delta, read from the delta as it
        stands.  The delta carries no df, so its ``jnp`` lane budget stays
        the spec-static ``L * max_df`` and its fill never changes a
        signature."""
        if self.delta is None:
            return self._sealed
        d = self.delta
        return self._sealed + [Segment(d.shard, d.shard_spec, d.base_docs,
                                       d.level_cum)]

    @property
    def shards(self) -> list:
        return [g.shard for g in self._sealed]

    @property
    def shard_specs(self) -> list:
        return [g.spec for g in self._sealed]

    @property
    def _df_host(self) -> list:
        return [g.df for g in self._sealed]

    def _attach_index(self, index: InvertedIndex) -> None:
        """(Re)build every index-derived serving structure — doc-range
        shards, host-side df/level tables, the dense engine.  Called at
        construction and again when a background merge reseals the
        collection: resealing changes the doc ranges (and so the jit
        signatures), which is exactly the once-per-merge retrace the
        delta's capacity padding exists to avoid on the per-batch path."""
        spec = self.cascade_spec
        self.index = index
        ranges = shard_ranges(index.n_docs, spec.deploy.n_shards)
        built = [shard_from_index(index, lo, hi, tile_d=spec.index.tile_d)
                 for lo, hi in ranges]
        min_docs = min(sp.n_docs for _, sp in built)
        if min_docs < self.k_serve:
            raise ValueError(
                f"k_serve={self.k_serve} exceeds the smallest shard "
                f"({min_docs} docs at n_shards={spec.deploy.n_shards}); "
                f"use fewer shards or a smaller k_serve")
        # host-side impact-level tables: the global SAAT level cut (and the
        # deterministic JASS cost) are resolved against the full collection,
        # then split per shard — see module docstring for why this keeps
        # multi-shard SAAT bit-identical to the single-shard traversal
        level_cum = ([index.level_cum] if len(built) == 1
                     else [np.asarray(s.level_cum) for s, _ in built])
        self._sealed = [Segment(s, sp, lo, lc, np.asarray(s.df))
                        for (s, sp), (lo, _), lc
                        in zip(built, ranges, level_cum)]

        self.term_stats = jnp.asarray(index.term_stats)
        self.df = jnp.asarray(index.df)

        # ---- dense Stage-1 modality (spec.dense; inert by default) ----
        # None keeps every serve path and cache key bit-identical to the
        # lexical-only system — the same discipline as FaultSpec/CacheSpec.
        # The embedding matrix is partitioned by the SAME doc ranges as the
        # inverted index, so merge_shard_topk and the pool failover
        # protocol apply to dense traffic unchanged.
        self.dense = None
        if spec.dense.enabled:
            doc_emb, term_table = build_embeddings(
                spec.dense, corpus=self.corpus, n_docs=index.n_docs,
                vocab=int(np.asarray(index.df).shape[0]))
            self.dense = DenseEngine(doc_emb, term_table, ranges,
                                     tile_d=spec.dense.tile_d,
                                     backend=self.backend)

    def _attribute_budget(self, budget: float, k_serve: int | None) -> dict:
        """``budget_attribution`` plus the dense modality's fusion reserve:
        with dense enabled, ``fusion_us`` is carved out of the scheduler's
        stage-1 share, so a both-routed query — max(lexical, dense) plus
        the host-side merge — still lands inside the cascade budget."""
        reserve = budget_attribution(budget, self.cost, k_serve)
        if self.cascade_spec.dense.enabled:
            reserve["fusion"] = self.cost.fusion_us
            reserve["stage1"] = max(reserve["stage1"] - self.cost.fusion_us,
                                    0.0)
        return reserve

    # ------------------------------------------------------------------
    # lifecycle: attach / train models
    # ------------------------------------------------------------------

    def set_models(self, models: dict, ltr: LTRModel | None = None):
        """Attach pre-trained Stage-0 predictors (and optionally the
        Stage-2 LTR model); rebuilds the scheduler so the cascade budget
        reservation matches the attached stages."""
        self.models = models
        # fused Stage-0: one stacked forest when the three ensembles share a
        # shape (fit() always trains them that way); per-model fallback
        # otherwise — same predictions either way, bit-for-bit.
        try:
            self._stacked, self._stack_depth = gbrt.stack_models(
                [models[n] for n in ("k", "rho", "t")])
        except ValueError:
            self._stacked = None
        self.ltr = ltr
        cfg = self._base_cfg
        # budget attribution: reserve the unconditional Stage-0 prediction
        # cost and the (deterministic) worst-case Stage-2 cost, so the
        # scheduler's deadline re-route enforces the *cascade* budget with
        # what remains — see "Guarantee accounting" in serving/latency.py
        if ltr is not None:
            if self.corpus is None:
                raise ValueError("Stage-2 re-ranking needs the corpus "
                                 "(doc topic mixtures)")
            self.s2 = stage2_arrays(self.index, self.corpus)
            self.n_iter = csr_search_iters(int(self.index.df.max()))
        self._budget_reserve = self._attribute_budget(
            cfg.budget, self.k_serve if ltr is not None else None)
        cfg = replace(cfg, budget=self._budget_reserve["stage1"])
        self.sched = StageZeroScheduler(cfg, self.cost)
        return self

    def fit(self, ql, labels=None, *, seed: int = 0) -> "SearchSystem":
        """Train the spec's Stage-0 predictors (and Stage-2 LTR model when
        enabled) from a query log.

        ``labels`` is a ``generate_labels`` result (oracle k/ρ/t targets +
        reference lists).  ``labels=None`` falls back to cheap pseudo-labels
        derived from posting-list mass — enough to exercise routing and
        re-ranking in benchmarks and CI smokes without the label oracle.
        """
        s0 = self.cascade_spec.stage0
        x = np.asarray(F.extract(self.term_stats, self.df,
                                 jnp.asarray(ql.terms), jnp.asarray(ql.mask)))
        rng = np.random.RandomState(seed)
        if labels is not None:
            targets = {"k": labels.oracle_k, "rho": labels.oracle_rho,
                       "t": labels.t_bmw}
        else:
            eff = ((self.index.df[ql.terms] * (ql.mask > 0))
                   .sum(axis=1).astype(np.float64))
            targets = {n: eff * sc * np.exp(rng.randn(len(eff)) * 0.3)
                       for n, sc in (("k", 0.05), ("rho", 0.5), ("t", 0.002))}
        taus = {"k": s0.tau_k, "rho": s0.tau_rho, "t": s0.tau_t}
        models = {
            name: gbrt.fit(
                x, np.log1p(y.astype(np.float32)),
                gbrt.GBRTParams(n_trees=s0.n_trees, depth=s0.depth,
                                loss="quantile", tau=taus[name]))
            for name, y in targets.items()}

        ltr = None
        if self.cascade_spec.stage2.enabled:
            if self.corpus is None:
                raise ValueError("Stage-2 training needs the corpus")
            s2 = self.cascade_spec.stage2
            if labels is not None:
                rows = np.flatnonzero(labels.keep)[:s2.n_train_queries]
                lf, lg = ltr_training_set(self.index, self.corpus, ql,
                                          labels.ref_lists, rows)
            else:
                feats = []
                for q in range(min(len(ql.terms), 32)):
                    docs = rng.randint(0, self.index.n_docs, 64)
                    feats.append(qd_features(self.index, self.corpus,
                                             ql.terms[q], ql.mask[q],
                                             ql.topic[q],
                                             docs.astype(np.int64)))
                lf = np.concatenate(feats)
                lg = (lf[:, 5] + 0.2 * lf[:, 1]).astype(np.float32)
            ltr = train_ltr(lf, lg, n_trees=s2.ltr_trees)

        if labels is not None and self.cascade_spec.backend.calibrate_cost:
            # close the cost-model loop: the label oracle measured per-query
            # (work, latency) pairs — regress the engine rates from them so
            # the budget enforcement runs on observed constants, not the
            # static roofline prior (rejected fits keep the prior)
            keep = labels.keep
            self.cost = self.cost.regressed(
                work_saat=labels.work_exhaustive[keep],
                t_saat=labels.t_exh[keep],
                work_daat=labels.work_bmw[keep],
                blocks_daat=labels.blocks_bmw[keep],
                t_daat=labels.t_bmw[keep])

        if self.cascade_spec.routing.calibrate:
            # name the operating point from the data: route on the trained
            # predictors' own distribution (paper trains thresholds the
            # same way), keeping both pools in play on any collection
            pk = np.expm1(np.asarray(gbrt.predict(models["k"],
                                                  jnp.asarray(x))))
            pt = np.expm1(np.asarray(gbrt.predict(models["t"],
                                                  jnp.asarray(x))))
            t_k = float(np.percentile(pk, 60))
            t_time = float(min(self.budget * 0.75, np.percentile(pt, 75)))
            self._base_cfg = replace(self._base_cfg, t_k=t_k, t_time=t_time)
            # fold the resolved thresholds back into the spec so
            # to_json() captures the *operating* point, not the template —
            # a round-tripped spec then serves bit-identically
            self.cascade_spec = replace(
                self.cascade_spec,
                routing=replace(self.cascade_spec.routing, t_k=t_k,
                                t_time=t_time))
        return self.set_models(models, ltr)

    # ------------------------------------------------------------------
    # stages
    # ------------------------------------------------------------------

    def stage0(self, terms: np.ndarray, mask: np.ndarray):
        """All three predictions in one fused device call: (pk, pr, pt)."""
        if self.models is None:
            raise RuntimeError("no Stage-0 models: call fit() or "
                               "set_models() first")
        x = F.extract(self.term_stats, self.df, jnp.asarray(terms),
                      jnp.asarray(mask))
        if self._stacked is not None:
            p = np.expm1(fetch(gbrt.predict_stacked(
                self._stacked, x, self._stack_depth))[0])
            return p[0], p[1], p[2]
        return tuple(np.expm1(p) for p in fetch(
            *(gbrt.predict(self.models[n], x) for n in ("k", "rho", "t"))))

    def _modality(self, pt: np.ndarray) -> np.ndarray:
        """Stage-0 modality dispatch from the predicted lexical time:
        cheap queries stay lexical, predicted-expensive ones go dense only
        (the dense cost is shape-static, so it undercuts any traversal the
        t-predictor priced above ``t_dense``), and the uncertainty band in
        between runs both engines and fuses."""
        ds = self.cascade_spec.dense
        td = ds.t_dense if ds.t_dense > 0 else self.sched.cfg.t_time
        m = np.full(len(pt), M_BOTH, np.int64)
        m[pt <= td * (1.0 - ds.fuse_band)] = M_LEX
        m[pt > td * (1.0 + ds.fuse_band)] = M_DENSE
        return m

    def _restrict_lexical(self, routed: RoutedBatch,
                          modality: np.ndarray) -> RoutedBatch:
        """Strip dense-only rows from a routed batch: those queries never
        touch the lexical engines, and the scheduler's mirror counters
        (which drive pool rebalance and ``_adapt_routing``) must not claim
        they did."""
        lex = modality != M_DENSE

        def keep(rows, stat):
            kept = rows[lex[rows]]
            self.sched.stats[stat] -= int(len(rows) - len(kept))
            return kept

        return replace(routed,
                       jass_rows=keep(routed.jass_rows, "jass"),
                       bmw_rows=keep(routed.bmw_rows, "bmw"),
                       hedged_rows=keep(routed.hedged_rows, "hedged"))

    def _jass_split(self, terms, mask, rows, rho, cache: dict | None = None):
        """Resolve the ρ budget to the global impact-level cut and split the
        cut's work per segment.  Returns (per-segment work list, any_ok).

        Every segment's level table joins the cut, a live delta's too, so ρ
        budgets the *whole* collection including undigested feed docs.

        ``cache`` memoizes on (rows, rho) for the duration of one served
        batch — stage-1 budgeting, hedging resolution, and pool feedback
        all ask for the same splits, and the host-side level-table gather
        is the heaviest numpy work in the serve path.
        """
        key = None
        if cache is not None:
            key = (np.asarray(rows).tobytes(),
                   np.asarray(rho, np.float64).tobytes())
            if key in cache:
                return cache[key]
        m = (mask[rows] > 0)[:, :, None]
        totals = [(g.level_cum[terms[rows]] * m).sum(axis=1)  # (R, n_levels)
                  for g in self.segments]
        total_g = totals[0] if len(totals) == 1 else np.sum(totals, axis=0)
        lstar, any_ok = resolve_level_cut(total_g, rho)
        rr = np.arange(len(rows))
        work_s = [np.where(any_ok, t[rr, lstar], 0) for t in totals]
        if key is not None:
            cache[key] = (work_s, any_ok)
        return work_s, any_ok

    def _shard_times(self, work, blocks=None):
        """(n_shards, R) engine times from per-segment work counts: JASS's
        postings, or BMW's postings and ``blocks``.  Only the sealed shards
        are timed: a live delta (the last segment) is charged as the
        shape-static ``_delta_us`` term, never from its per-query work."""
        ns = self.n_shards
        if blocks is None:
            return np.stack([self.cost.saat_time(w.astype(np.float64))
                             for w in work[:ns]])
        return np.stack([self.cost.daat_time(w, b)
                         for w, b in zip(work[:ns], blocks[:ns])])

    def _jass_time(self, terms, mask, cache: dict | None = None):
        """Deterministic JASS time under scatter-gather: the ρ budget
        resolves to a global level cut, each shard's slice of the cut costs
        its own work, and the query waits for the slowest shard."""
        def fn(rows, rho):
            work_s, _ = self._jass_split(terms, mask, rows, rho, cache)
            return self.cost.gather_time(self._shard_times(work_s))
        return fn

    def stage1(self, terms: np.ndarray, mask: np.ndarray, routed,
               split_cache: dict | None = None, drop=None):
        """Fan the routed sub-batches out over every segment (sealed shards,
        then the live delta) with one engine call per branch, and merge the
        per-segment top-k.

        Returns (topk, topk_sc, t_bmw, t_shards): merged global candidates
        and their merged scores (engine-native units; ``SCORE_FILL`` marks
        never-served / dropped slots — the fusion layer needs the scores,
        lexical-only callers may ignore them), the scatter-gather BMW time
        per query, and the (n_shards, Q) per-shard engine-time matrix that
        feeds the replica pool's EWMA estimates.

        ``split_cache`` memoizes the JASS level-cut splits for one served
        batch (a fresh one per call when omitted).  ``drop`` ((n_shards, Q)
        bool, optional) marks (shard, query) slots whose response was lost
        (fault injection) or never requested (partial-coverage admission):
        their candidates are excluded from the merge (padded with ``-1``
        ids when fewer than ``k_serve`` survive), so a degraded query's list
        is exactly the merge over its surviving partitions.  The delta is
        local to the merge host: never lost, never admission-dropped.
        """
        split_cache = {} if split_cache is None else split_cache
        q = terms.shape[0]
        segs = self.segments
        topk = np.zeros((q, self.k_serve), np.int64)
        topk_sc = np.full((q, self.k_serve), SCORE_FILL, np.float32)
        t_bmw = np.zeros(q)
        t_shards = np.zeros((self.n_shards, q))

        def padded(rows):
            """The rows' terms and mask with inert rows (mask 0) after the
            real ones, up to the batcher's bucket width, so each engine
            compiles per width rather than per sub-batch size; the
            segment-ordered drop mask over them; and the number of pad
            rows."""
            n, on = len(rows), self.cascade_spec.online
            w = (bucket_size(n, on.max_batch, on.bucket_q)
                 if n <= on.max_batch else n)
            t = np.zeros((w, terms.shape[1]), terms.dtype)
            m = np.zeros((w, mask.shape[1]), mask.dtype)
            t[:n], m[:n] = terms[rows], mask[rows]
            dr = None if drop is None else np.pad(
                drop[:, rows], ((0, len(segs) - len(drop)), (0, w - n)))
            return t, m, dr, w - n

        def gather(rows, out):
            """Write one engine's merged list into ``topk``/``topk_sc`` and
            read it back, with the engines' work counts, in one device
            wait; returns (work, blocks) on the host, for the real rows."""
            debug = self._debug_shard_lists is not None
            lists = (out.scores, out.ids) if debug or out.merged is None \
                else None
            lists, merged, work, blocks = jax.tree.map(
                lambda a: a[:len(rows)],
                fetch(lists, out.merged, out.work, out.blocks))
            if debug:
                self._debug_shard_lists.append((rows, *lists))
            ids, sc = ((lists[1][0], lists[0][0]) if merged is None
                       else merged)
            topk[rows] = ids
            # SCORE_FILL marks a dropped slot whatever the score dtype (the
            # merge fills one with the dtype's minimum)
            topk_sc[rows] = np.where(ids < 0, SCORE_FILL, sc)
            return work, blocks

        stats = self.sched.stats
        if len(routed.jass_rows):
            with span(STAGE1_JASS):
                rows = routed.jass_rows
                rhos = [routed.rho[rows]]
                if len(segs) > 1:
                    # one global level cut → per-segment budgets that
                    # reproduce exactly the single-shard posting set (see
                    # module docstring)
                    work_s, any_ok = self._jass_split(terms, mask, rows,
                                                      rhos[0], split_cache)
                    rhos = [np.where(any_ok, w, -1.0).astype(np.float64)
                            for w in work_s]
                t_q, m_q, dr, pad = padded(rows)
                # pad rows have no terms: any budget scores nothing
                out = saat_serve_segments(
                    segs, t_q, m_q, [np.pad(r, (0, pad)) for r in rhos],
                    k=self.k_serve, cap=int(self.sched.cfg.rho_max),
                    backend=self.backend, drop=dr, engine=saat_serve)
                work, _ = gather(rows, out)
                self._stage1_counters["deep_select_rows"] += \
                    len(rows) * out.deep_select
                stats["jass_pad_rows"] += pad
                stats["jass_postings"] += int(sum(w.sum() for w in work))
                t_shards[:, rows] = self._shard_times(work)

        if len(routed.bmw_rows):
            with span(STAGE1_BMW):
                rows = routed.bmw_rows
                t_q, m_q, dr, pad = padded(rows)
                out = daat_serve_segments(
                    segs, t_q, m_q, jnp.ones(len(t_q), jnp.float32),
                    k=self.k_serve, backend=self.backend, drop=dr,
                    engine=daat_serve)
                work, blocks = gather(rows, out)
                self._stage1_counters["deep_select_rows"] += \
                    len(rows) * out.deep_select
                stats["bmw_pad_rows"] += pad
                t_shards[:, rows] = self._shard_times(work, blocks)
                t_bmw[rows] = self.cost.gather_time(t_shards[:, rows])
        return topk, topk_sc, t_bmw, t_shards

    def stage2(self, terms, mask, topics, cand, k_per_query) -> CascadeResult:
        """Batched LTR re-rank of the merged Stage-1 candidate grid (the
        re-ranker sees global doc ids, so it is shard-agnostic): one
        compiled program per (batch width, grid width, lane budget
        ``qcap``) featurizes, bins, scores, masks and takes the top
        ``t_final``, with one read-back (``rerank_batched``)."""
        backend = resolve_backend(self.backend)
        qcap = None
        if backend != "jnp":
            qcap = query_lane_budget(self.index.df, terms, mask)
        return rerank_batched(self.s2, self.ltr, terms, mask, topics,
                              cand, k_per_query, t_final=self.t_final,
                              n_iter=self.n_iter, backend=backend, qcap=qcap,
                              lane_need=qcap)

    # ------------------------------------------------------------------
    # replica-pool bookkeeping
    # ------------------------------------------------------------------

    def _pool_route(self, routed, n_queries: int):
        """Pick one replica of every partition for each query (its routed
        mirror; hedged queries also occupy the JASS mirror).  A partition
        with no healthy replica yields ``None`` in its slot (degraded
        serving), never an exception — with a fully-healthy pool the pick
        sequence is identical to the historical all-or-nothing route."""
        is_jass = np.zeros(n_queries, bool)
        is_jass[routed.jass_rows] = True
        picks = [self.pool.route_query_partial(JASS if is_jass[i] else BMW)
                 for i in range(n_queries)]
        hedge_picks = {int(i): self.pool.route_query(JASS)
                       for i in routed.hedged_rows}
        return picks, hedge_picks

    def _fault_plan(self, picks, routed, now: float):
        """Run the scatter-gather failure protocol for one batch against
        the fault schedule at clock ``now``.

        For every (query, shard) request: an attempt to a crashed/outaged
        replica — or one killed by a transient-timeout draw — is detected
        after ``failover_timeout``, reported ``ok=False`` to the pool (so
        ``fail_after`` can trip), and re-issued to a different healthy
        replica of the same partition, at most ``max_retries`` times.  When
        the chain is exhausted the slot is declared lost and the query
        degrades to partial coverage.

        Mutates ``picks`` in place (final serving replica, or ``None`` for
        a lost slot) and returns ``(delay, mult, lost)``: per-(shard,
        query) accumulated timeout wait, straggler slowdown of the serving
        replica, and the lost mask.
        """
        cfg = self.sched.cfg
        timeout, max_retries = cfg.failover_timeout, cfg.max_retries
        ns, q = self.n_shards, len(picks)
        delay = np.zeros((ns, q))
        mult = np.ones((ns, q))
        lost = np.zeros((ns, q), bool)
        ctr = self._fault_counters
        is_jass = np.zeros(q, bool)
        is_jass[routed.jass_rows] = True
        for i, reps in enumerate(picks):
            mirror = JASS if is_jass[i] else BMW
            for s in range(ns):
                r = reps[s]
                if r is None:            # no healthy replica to even try
                    lost[s, i] = True
                    ctr["no_route"] += 1
                    continue
                tried = {id(r)}
                failures = 0
                while True:
                    if not self.faults.is_up(s, r.replica_id, now):
                        ctr["down_requests"] += 1
                    elif self.faults.transient(now):
                        ctr["transient"] += 1
                    else:                # attempt serves
                        mult[s, i] = self.faults.slowdown(s, r.replica_id,
                                                          now)
                        reps[s] = r
                        break
                    # attempt dead: detected at the timeout, charged to the
                    # query's wait and to the replica's health record
                    self.pool.complete(r, latency=timeout, ok=False)
                    delay[s, i] += timeout
                    failures += 1
                    nxt = (self.pool.pick_retry(s, mirror, tried)
                           if failures <= max_retries else None)
                    if nxt is None:      # retry budget / pool exhausted
                        lost[s, i] = True
                        reps[s] = None
                        ctr["lost_partitions"] += 1
                        break
                    ctr["retries"] += 1
                    nxt.inflight += 1
                    tried.add(id(nxt))
                    r = nxt
        return delay, mult, lost

    def _pool_complete(self, terms, mask, routed, picks, hedge_picks,
                       t_shards, cache: dict | None = None):
        """Feed observed per-(query, shard) latencies back into the pool."""
        for i, reps in enumerate(picks):
            if reps is None:
                continue
            for s, r in enumerate(reps):
                if r is None:            # lost/dropped slot: already
                    continue             # released by the failure protocol
                self.pool.complete(r, latency=float(t_shards[s, i]))
        if hedge_picks:
            rows = np.fromiter(hedge_picks, dtype=np.int64)
            work_s, _ = self._jass_split(terms, mask, rows,
                                         routed.rho[rows], cache)
            t_h = self._shard_times(work_s)
            for j, i in enumerate(rows):
                reps = hedge_picks[int(i)]
                if reps is None:
                    continue
                for s, r in enumerate(reps):
                    self.pool.complete(r, latency=float(t_h[s, j]))
        self._batches += 1
        every = self.cascade_spec.deploy.rebalance_every
        if every and self._batches % every == 0:
            n_j = len(routed.jass_rows)
            n_b = len(routed.bmw_rows)
            if n_j + n_b:
                self.pool.rebalance(n_j / (n_j + n_b))

    # ------------------------------------------------------------------
    # end to end
    # ------------------------------------------------------------------

    def serve(self, terms: np.ndarray, mask: np.ndarray,
              topics: np.ndarray | None = None, *,
              stage2_cap: np.ndarray | None = None,
              shard_cap: np.ndarray | None = None,
              now: float | None = None) -> PipelineResult:
        """Serve one batch through the full cascade.

        ``stage2_cap`` is an optional per-query hard cap on the Stage-2
        candidate grid (admission control's degrade ladder: ``k_serve`` =
        full service, ``0 < cap < k_serve`` = trimmed re-rank, ``0`` =
        stage1-only — the rank-safe Stage-1 order is served directly).

        ``shard_cap`` is an optional per-query cap on the number of
        partitions queried (admission's partial-coverage rung: queries
        only the first ``shard_cap[i]`` partitions, trading coverage for
        gather overhead).  ``now`` pins the serving clock the fault
        schedule is evaluated against (default: the system's own clock,
        advanced by each batch's occupancy; the online simulator passes
        its dispatch time).  With an inert fault spec and no ``shard_cap``
        this path is bit-identical to fault-free serving.

        With an active :class:`~repro.serving.spec.CacheSpec` every query
        is first looked up in the two-level serving cache (L1 exact
        results bypass the cascade, L2 candidates skip retrieval and
        re-run Stage-2) and full-coverage results are filled back; with
        the cache disabled (the default) this method IS the direct
        cascade, bit-identical to the pre-cache system.
        """
        with span(SERVE):
            if self.cache is None:
                return self._serve_direct(terms, mask, topics,
                                          stage2_cap=stage2_cap,
                                          shard_cap=shard_cap, now=now)
            return self._serve_cached(terms, mask, topics,
                                      stage2_cap=stage2_cap,
                                      shard_cap=shard_cap, now=now)

    def _serve_direct(self, terms: np.ndarray, mask: np.ndarray,
                      topics: np.ndarray | None = None, *,
                      stage2_cap: np.ndarray | None = None,
                      shard_cap: np.ndarray | None = None,
                      now: float | None = None) -> PipelineResult:
        """The uncached cascade (see :meth:`serve` for the contract)."""
        q = terms.shape[0]
        ns = self.n_shards
        now = float(self._clock if now is None else now)
        faulted = self.faults.active or shard_cap is not None
        if self.faults.active:
            with span(REPLICAS):
                # drive recovery from the serve loop: probe unhealthy
                # replicas against the schedule (a cleared window re-admits
                # the replica)
                probes, rec = self.pool.probe_unhealthy(
                    lambda r: self.faults.is_up(r.partition, r.replica_id,
                                                now))
                self._fault_counters["probes"] += probes
                self._fault_counters["recovered"] += rec
        with span(STAGE0):
            pk, pr, pt = self.stage0(terms, mask)
            routed = self.sched.route(pk, pr, pt)
            modality = None
            if self.dense is not None:
                # modality dispatch: dense-only rows leave the lexical
                # sub-batches entirely (their replica picks below still pin the
                # co-located partition replicas the dense engine runs on, so
                # the failure protocol covers dense traffic too)
                modality = self._modality(pt)
                routed = self._restrict_lexical(routed, modality)
        with span(REPLICAS):
            # route replicas before the engines run so the pool sees the whole
            # batch in flight (power-of-two-choices balances against inflight)
            picks, hedge_picks = self._pool_route(routed, q)

            drop = None
            coverage = None
            if faulted:
                # admission-chosen partial coverage: the trailing partitions
                # are never requested — release their routed picks
                dropped = np.zeros((ns, q), bool)
                if shard_cap is not None:
                    cap = np.clip(np.asarray(shard_cap, np.int64), 1, ns)
                    for i in range(q):
                        for s in range(int(cap[i]), ns):
                            r = picks[i][s]
                            if r is not None:
                                r.inflight = max(r.inflight - 1, 0)
                                picks[i][s] = None
                            dropped[s, i] = True
                # injected faults: timeout detection, bounded failover, loss
                delay, mult, lost = self._fault_plan(picks, routed, now)
                lost &= ~dropped
                drop = lost | dropped
                coverage = 1.0 - drop.sum(axis=0) / ns
                n_deg = int((coverage < 1.0).sum())
                self._fault_counters["degraded_queries"] += n_deg

        with span(STAGE1):
            split_cache: dict = {}
            topk, topk_sc, t_bmw, t_shards = self.stage1(
                terms, mask, routed, split_cache, drop=drop)

            theta_skip = np.zeros(q, bool)
            fallback = np.zeros(q, bool)
            fb_extra = np.zeros(q)        # theta_low lexical-fallback latency
            t_dense_mat = None              # (ns, Q) per-shard dense time
            d_rows = (np.flatnonzero(modality != M_LEX)
                      if self.dense is not None else np.zeros(0, np.int64))
            if len(d_rows):
                ds = self.cascade_spec.dense
                q_emb = self.dense.embed(terms[d_rows], mask[d_rows])
                d_ids, d_sc = self.dense.serve(
                    q_emb, self.k_serve,
                    drop=None if drop is None else drop[:, d_rows])
                # shape-static per-shard dense time: every query scores every
                # tile of every shard, so the matrix is query-independent
                t_dense_mat = np.zeros((ns, q))
                for s in range(ns):
                    t_dense_mat[s, d_rows] = float(
                        self.cost.dense_time(self.dense.n_tiles(s)))
                dmod = modality[d_rows]
                only_rows = d_rows[dmod == M_DENSE]
                both_rows = d_rows[dmod == M_BOTH]
                # dense-only rows serve the dense list; both rows fuse the two
                topk[only_rows] = d_ids[dmod == M_DENSE]
                topk_sc[only_rows] = d_sc[dmod == M_DENSE]
                if len(both_rows):
                    f_ids, f_sc = fuse(self.cascade_spec.fusion,
                                       topk[both_rows], topk_sc[both_rows],
                                       d_ids[dmod == M_BOTH],
                                       d_sc[dmod == M_BOTH], self.k_serve)
                    topk[both_rows] = f_ids
                    topk_sc[both_rows] = f_sc
                top_dense = d_sc[:, 0].astype(np.float64)
                if np.isfinite(ds.theta_high):
                    # high-confidence shortcut: Stage-2 is skipped rank-safely
                    # (the existing zero-grid path serves the Stage-1 order)
                    theta_skip[d_rows] = top_dense >= ds.theta_high
                if np.isfinite(ds.theta_low) and len(only_rows):
                    fb_rows = only_rows[top_dense[dmod == M_DENSE]
                                        < ds.theta_low]
                    if len(fb_rows):
                        # low-confidence dense-only rows re-issue a ρ-capped
                        # lexical traversal — same cap and nominal-healthy
                        # pricing as the scheduler's late hedge, so the route
                        # stays inside worst_case_us
                        fb_routed = RoutedBatch(
                            jass_rows=fb_rows,
                            bmw_rows=np.zeros(0, np.int64),
                            hedged_rows=np.zeros(0, np.int64),
                            k=routed.k,
                            rho=np.minimum(
                                routed.rho,
                                float(self.sched.cfg.resolved_late_rho())))
                        fb_topk, fb_sc, _, fb_tsh = self.stage1(
                            terms, mask, fb_routed, split_cache)
                        topk[fb_rows] = fb_topk[fb_rows]
                        topk_sc[fb_rows] = fb_sc[fb_rows]
                        fb_extra[fb_rows] = self.cost.gather_time(
                            fb_tsh[:, fb_rows])
                        fallback[fb_rows] = True

        with span(ACCOUNT):
            if faulted:
                # per-shard completion time under the plan: a served slot pays
                # its retry wait plus the (possibly straggler-slowed) engine
                # time; a lost slot pays the full detection chain; a dropped
                # slot was never requested.  The query still waits for its
                # slowest slot (scatter-gather), and pays merge fan-out only
                # over the partitions that answered.
                t_fault = np.where(dropped, 0.0,
                                   delay + np.where(lost, 0.0,
                                                    t_shards * mult))
                n_live = ns - drop.sum(axis=0)
                gather_ov = (self.cost.gather_per_shard_us
                             * np.maximum(n_live - 1, 0))

                def _gather_fault(tmat, rows):
                    return tmat.max(axis=0) + gather_ov[rows]

                t_bmw = np.zeros(q)
                if len(routed.bmw_rows):
                    rows = routed.bmw_rows
                    t_bmw[rows] = _gather_fault(t_fault[:, rows], rows)

                def jass_fault_fn(rows, rho):
                    work_s, _ = self._jass_split(terms, mask, rows, rho,
                                                 split_cache)
                    t = self._shard_times(work_s)
                    tf = np.where(dropped[:, rows], 0.0,
                                  delay[:, rows]
                                  + np.where(lost[:, rows], 0.0,
                                             t * mult[:, rows]))
                    return _gather_fault(tf, rows)

                # the deadline re-issue goes to a fresh healthy replica, so
                # it pays nominal JASS cost — the retry wait it could still
                # incur is charged analytically via SchedulerConfig.retry_us()
                lat01 = self.sched.resolve_times(
                    routed, t_bmw, jass_fault_fn,
                    late_jass_fn=self._jass_time(terms, mask, split_cache))
                t_pool = t_fault
                if t_dense_mat is not None:
                    # dense requests ride the same failure protocol: a served
                    # slot pays its retry wait + (possibly straggler-slowed)
                    # dense engine time, lost/dropped slots exactly as lexical
                    t_dense_eff = np.where(
                        dropped, 0.0,
                        delay + np.where(lost, 0.0, t_dense_mat * mult))
                    t_pool = np.maximum(t_pool, t_dense_eff)
                    tdr = np.zeros(q)
                    tdr[d_rows] = (t_dense_eff[:, d_rows].max(axis=0)
                                   + gather_ov[d_rows])
            else:
                lat01 = self.sched.resolve_times(
                    routed, t_bmw, self._jass_time(terms, mask, split_cache))
                t_pool = t_shards
                if t_dense_mat is not None:
                    # a partition replica hosting both engines is busy for the
                    # max of its co-located work
                    t_pool = np.maximum(t_pool, t_dense_mat)
                    tdr = np.zeros(q)
                    tdr[d_rows] = self.cost.gather_time(t_dense_mat[:, d_rows])
            if len(d_rows):
                # dense-only: predict + dense scatter-gather (+ any theta_low
                # fallback); both: the two engines run in parallel, the query
                # waits for the slower and pays the host-side fusion merge
                pd = self.cost.predict_us
                only = modality == M_DENSE
                both = modality == M_BOTH
                lat01 = np.where(only, pd + tdr + fb_extra, lat01)
                lat01 = np.where(both,
                                 pd + np.maximum(lat01 - pd, tdr)
                                 + self.cost.fusion_us, lat01)
            if self.delta is not None:
                # every served query scans the delta segment; its arrays are
                # capacity-padded, so the cost is one shape-static term —
                # charged here, BEFORE budget enforcement trims Stage-2, and
                # identically inside worst_case_us()
                lat01 = lat01 + self._delta_us
            t0 = np.full(q, self.cost.predict_us)
            stage_latency = {"stage0": t0, "stage1": lat01 - t0}

            if len(routed.bmw_rows):
                # online quantile-error signal for the t predictor: pinball
                # loss of pred_t against the observed BMW engine time, at the
                # predictor's own training tau — feeds _adapt_routing's
                # hedge_deadline loop
                tau = self.cascade_spec.stage0.tau_t
                e = t_bmw[routed.bmw_rows] - pt[routed.bmw_rows]
                pin = float(np.mean(np.maximum(tau * e, (tau - 1.0) * e)))
                self._pinball_ewma = (
                    pin if self._pinball_ewma is None
                    else 0.8 * self._pinball_ewma + 0.2 * pin)

        with span(STAGE2):
            final = None
            used = None
            enforce = self.sched.cfg.enforce_budget
            trimmed = skipped = 0
            if self.ltr is not None:
                if topics is None:
                    raise ValueError(
                        "Stage-2 re-ranking needs per-query topics")
                k2 = np.minimum(routed.k, self.k_serve)
                if stage2_cap is not None:
                    # admission-control degrade ladder: the cap is decided from
                    # response-time slack (queueing included), before the
                    # service-budget enforcement below
                    k2 = np.minimum(k2, np.asarray(stage2_cap, np.int64))
                if drop is not None:
                    # degraded queries may hold fewer than k_serve real
                    # candidates (-1 padding from the masked merge): never ask
                    # Stage-2 to rank the padding
                    k2 = np.minimum(k2, (topk >= 0).sum(axis=1))
                if theta_skip.any():
                    # dense confidence shortcut: the Stage-1 order is served
                    # directly (rank-safe), zeroed BEFORE enforcement so these
                    # rows never count as budget-driven skips
                    k2 = np.where(theta_skip, 0, k2)
                if enforce:
                    # cascade hedge: a query whose Stage-1 time already ate
                    # the budget gets its candidate grid trimmed (masked
                    # re-rank) — or skipped outright — so ltr_time cannot
                    # push it over.
                    # When the Stage-1 bound holds, the Stage-2 reservation
                    # guarantees afford >= k_serve and this is a no-op.
                    afford = stage2_afford(self.cost, self.budget - lat01,
                                           self.k_serve)
                    trimmed = int(np.sum((0 < afford) & (afford < k2)))
                    skipped = int(np.sum((afford == 0) & (k2 > 0)))
                    k2 = np.minimum(k2, afford)
                cand = topk if drop is None else np.where(topk >= 0, topk, 0)
                res2 = self.stage2(terms, mask, topics,
                                   cand.astype(np.int32), k2)
                final, used = res2.final, res2.candidates_used
                skip_rows = np.flatnonzero(k2 == 0)
                if len(skip_rows):
                    # zero-grid queries (enforcement skip or admission's
                    # stage1-only rung) serve their Stage-1 order directly
                    # (the rank-safe list) at zero Stage-2 cost
                    final[skip_rows] = topk[skip_rows, :self.t_final]
                stage_latency["stage2"] = np.where(
                    used > 0, self.cost.ltr_time(used), 0.0)
            else:
                stage_latency["stage2"] = np.zeros(q)

        with span(REPLICAS):
            self._pool_complete(terms, mask, routed, picks, hedge_picks,
                                t_pool, split_cache)
            every = self.cascade_spec.routing.adapt_every
            if every and self._batches % every == 0:
                self._adapt_routing()

        with span(ACCOUNT):
            lat = lat01 + stage_latency["stage2"]
            # the serving clock advances by the batch's occupancy so fault
            # windows expressed in cost-model time mean the same thing whether
            # serve() is driven offline or by the online event loop
            self._clock = now + (float(lat.max()) if q else 0.0)
            dense_info = None
            if self.dense is not None:
                dense_info = {"modality": modality, "theta_skip": theta_skip,
                              "fallback": fallback}
            stats = self._build_stats(
                lat, stage_latency, trimmed, skipped, faulted, coverage, now,
                dense_info=dense_info)
            if self.telemetry is not None:
                self._record_traces(
                    q=q, now=now, lat=lat, stage_latency=stage_latency,
                    pk=pk, pr=pr, pt=pt, routed=routed, modality=modality,
                    theta_skip=theta_skip, fallback=fallback, used=used,
                    t_shards=t_shards, faulted=faulted,
                    delay=delay if faulted else None,
                    mult=mult if faulted else None,
                    lost=lost if faulted else None,
                    dropped=dropped if faulted else None, coverage=coverage)
        return PipelineResult(topk=topk, final=final, candidates_used=used,
                              latency=lat, stage_latency=stage_latency,
                              stats=stats, coverage=coverage,
                              dense=dense_info)

    # ------------------------------------------------------------------
    # result/candidate caching
    # ------------------------------------------------------------------

    def _cache_epoch(self, now: float):
        """The coverage/fault epoch cache entries are tagged with at clock
        ``now``: the per-partition reachability vector plus the transient-
        storm window flag.  Entries only hit inside the epoch they were
        filled in, so serving across a fault transition (a partition dying
        or healing, a storm starting) re-derives from the live cascade
        instead of trusting results certified under different coverage.
        With an inert fault spec this is one constant — no per-query work,
        no RNG (``transient`` draws are never consumed here).

        With live ingest attached the epoch additionally carries the
        ingest counter (bumped on every applied feed batch and every
        merge), so entries filled against one delta state never hit after
        the collection has changed under them."""
        if not self.faults.active:
            base = HEALTHY_EPOCH
        else:
            reps = self.cascade_spec.deploy.replicas
            up = tuple(self.faults.partition_up(p, reps, now)
                       for p in range(self.n_shards))
            sp = self.faults.spec
            storm = bool(sp.timeout_p > 0
                         and sp.timeout_start <= now < sp.timeout_end)
            base = up + (storm,)
        if self.delta is not None:
            return ingest_epoch(base, self._ingest_counters["epoch"])
        return base

    def _pure_route(self, pk, pr, pt):
        """Route a batch WITHOUT counting it: ``StageZeroScheduler.route``
        accumulates routing stats, but cache-key derivation must not double
        count rows the miss sub-batch re-routes for real below."""
        saved = dict(self.sched.stats)
        routed = self.sched.route(pk, pr, pt)
        self.sched.stats.clear()
        self.sched.stats.update(saved)
        return routed

    def cache_peek(self, terms: np.ndarray, mask: np.ndarray,
                   topics: np.ndarray | None = None, *,
                   now: float | None = None) -> np.ndarray:
        """Per-query bool mask of *guaranteed* L1 hits at clock ``now`` —
        rows for which :meth:`serve` (called at the same clock, before any
        other serve) will bypass the cascade at full service.  Probes only
        the FULL-mode key (``cap = k_serve``), mutates nothing (no recency
        moves, no stats, no RNG), so admission can peek at dispatch time
        without perturbing replay determinism."""
        q = terms.shape[0]
        out = np.zeros(q, bool)
        if self.cache is None or self.cache.l1 is None:
            return out
        now = float(self._clock if now is None else now)
        epoch = self._cache_epoch(now)
        pk, pr, pt = self.stage0(terms, mask)
        routed = self._pure_route(pk, pr, pt)
        modality = self._modality(pt) if self.dense is not None else None
        is_jass = np.zeros(q, bool)
        is_jass[routed.jass_rows] = True
        for i in range(q):
            qk = normalize_query(terms[i], mask[i],
                                 None if topics is None else topics[i])
            rs = route_sig(bool(is_jass[i]), float(routed.rho[i]),
                           float(routed.k[i]),
                           b"" if modality is None
                           else b"|M%d" % modality[i])
            out[i] = self.cache.l1_contains(
                l1_key(qk, rs, self.k_serve, self.t_final, self.k_serve),
                epoch)
        return out

    def _serve_cached(self, terms: np.ndarray, mask: np.ndarray,
                      topics: np.ndarray | None = None, *,
                      stage2_cap: np.ndarray | None = None,
                      shard_cap: np.ndarray | None = None,
                      now: float | None = None) -> PipelineResult:
        """serve() with the two-level cache in front of the cascade.

        Per query: L1 hit → the cached (topk, final, used) row at
        ``predict_us + cache_hit_us``; L2 hit → cached Stage-1 candidates,
        fresh Stage-2 re-rank; miss → the full cascade via
        :meth:`_serve_direct` on the miss sub-batch (row-independent
        batched kernels keep sub-batch results bit-identical to the
        full-batch ones).  Every query pays the ``cache_hit_us`` lookup —
        that is the term :meth:`worst_case_us` charges.

        Correctness guards: rows admitted at partial coverage
        (``shard_cap < n_shards``) bypass the cache entirely, results that
        came back with ``coverage < 1`` are never filled, and every entry
        carries the fill-time fault epoch (see :meth:`_cache_epoch`).  A
        hit may serve the *untrimmed* re-rank where a cold serve would
        have had to trim for budget — the hit has the slack to spend;
        whenever enforcement didn't trim the cold path, hit == recompute
        bit-for-bit (certified by ``benchmarks/bench_cache.py``).
        """
        q = terms.shape[0]
        ns = self.n_shards
        now = float(self._clock if now is None else now)
        cache = self.cache
        with span(STAGE0):
            pk, pr, pt = self.stage0(terms, mask)
            routed = self._pure_route(pk, pr, pt)
            # the resolved modality is part of the route: lexical, dense and
            # fused entries for the same query must never collide (with dense
            # disabled the suffix is b"" and keys are byte-identical)
            modality = self._modality(pt) if self.dense is not None else None
            is_jass = np.zeros(q, bool)
            is_jass[routed.jass_rows] = True

        with span(CACHE):
            epoch = self._cache_epoch(now)
            cap = np.full(q, self.k_serve, np.int64)
            if stage2_cap is not None:
                cap = np.minimum(np.asarray(stage2_cap, np.int64),
                                 self.k_serve)
            # the partial-coverage rung deliberately queries fewer partitions:
            # those rows neither look up nor fill (a full-coverage cached
            # result would silently upgrade the admission decision)
            eligible = (np.ones(q, bool) if shard_cap is None
                        else np.asarray(shard_cap, np.int64) >= ns)

            keys1 = [None] * q
            keys2 = [None] * q
            l1_hit = np.zeros(q, bool)
            l2_hit = np.zeros(q, bool)
            l1_vals: dict = {}
            l2_vals: dict = {}
            for i in range(q):
                if not eligible[i]:
                    cache.counters["skipped_partial"] += 1
                    continue
                cache.counters["lookups"] += 1
                qk = normalize_query(terms[i], mask[i],
                                     None if topics is None else topics[i])
                rs = route_sig(bool(is_jass[i]), float(routed.rho[i]),
                               float(routed.k[i]),
                               b"" if modality is None
                               else b"|M%d" % modality[i])
                keys1[i] = l1_key(qk, rs, self.k_serve, self.t_final,
                                  int(cap[i]))
                v = cache.l1_get(keys1[i], epoch)
                if v is not None:
                    l1_hit[i] = True
                    l1_vals[i] = v
                    cache.counters["l1_hits"] += 1
                    continue
                keys2[i] = l2_key(qk, rs)
                if self.ltr is not None:
                    v2 = cache.l2_get(keys2[i], epoch)
                    if v2 is not None:
                        l2_hit[i] = True
                        l2_vals[i] = v2
                        cache.counters["l2_hits"] += 1
                        continue
                cache.counters["full_misses"] += 1

            hit_us = self.cost.cache_hit_us
            topk = np.zeros((q, self.k_serve), np.int64)
            final_rows: list = [None] * q
            used = np.zeros(q, np.int64) if self.ltr is not None else None
            t0 = np.full(q, self.cost.predict_us)
            t1 = np.zeros(q)
            t2 = np.zeros(q)
            faulted = self.faults.active or shard_cap is not None
            coverage = np.ones(q) if faulted else None
            trimmed = skipped = 0

            rows1 = np.flatnonzero(l1_hit)
            for i in rows1:
                tk, f, u = l1_vals[i]
                topk[i] = tk
                if self.ltr is not None:
                    final_rows[i] = f
                    used[i] = u
            t1[rows1] = hit_us

        rows2 = np.flatnonzero(l2_hit)
        skip_flags = None
        if len(rows2):
            with span(STAGE2):
                vals = [l2_vals[i] for i in rows2]
                if self.dense is not None:
                    # dense-mode L2 entries carry the fill-time theta-skip
                    # decision, so a hit replays the same Stage-2 shortcut the
                    # cold serve took
                    cand = np.stack([v[0] for v in vals])
                    skip_flags = np.array([bool(v[1]) for v in vals])
                else:
                    cand = np.stack(vals)
                topk[rows2] = cand
                t1[rows2] = hit_us
                k2 = np.minimum(np.minimum(routed.k[rows2], self.k_serve),
                                cap[rows2]).astype(np.int64)
                if skip_flags is not None:
                    k2[skip_flags] = 0
                if self.sched.cfg.enforce_budget:
                    # same enforcement as the cold path, priced at the hit's
                    # actual stage-1 cost — a hit has the slack to afford the
                    # full grid whenever the reserve holds
                    afford = stage2_afford(
                        self.cost,
                        self.budget - (self.cost.predict_us + hit_us),
                        self.k_serve)
                    trimmed += int(np.sum((0 < afford) & (afford < k2)))
                    skipped += int(np.sum((afford == 0) & (k2 > 0)))
                    k2 = np.minimum(k2, afford)
                res2 = self.stage2(terms[rows2], mask[rows2], topics[rows2],
                                   cand.astype(np.int32), k2)
                f2, u2 = res2.final, res2.candidates_used
                skip = np.flatnonzero(k2 == 0)
                if len(skip):
                    f2[skip] = cand[skip, :self.t_final]
                for j, i in enumerate(rows2):
                    final_rows[i] = f2[j]
                    used[i] = u2[j]
                t2[rows2] = np.where(u2 > 0, self.cost.ltr_time(u2), 0.0)
            with span(CACHE):
                # promote: the fresh full-coverage re-rank is exactly an L1
                # entry for this (query, route, stage-2 params) point
                for j, i in enumerate(rows2):
                    cache.l1_put(keys1[i],
                                 (topk[i].copy(), f2[j].copy(), int(u2[j])),
                                 epoch)

        miss_rows = np.flatnonzero(~(l1_hit | l2_hit))
        sub = None
        if len(miss_rows):
            tel = self.telemetry
            outer_ctx = tel.batch_context if tel is not None else None
            if tel is not None:
                # the sub-serve records the miss rows' traces (it is the
                # real cascade execution) tagged "miss", but must not
                # re-feed batch metrics: this batch feeds them once below
                if outer_ctx is not None:
                    tel.batch_context = {
                        k: (v[miss_rows] if isinstance(v, np.ndarray)
                            else v)
                        for k, v in outer_ctx.items()}
                self._tel_suppress = True
                self._tel_cache_tag = "miss"
            try:
                sub = self._serve_direct(
                    terms[miss_rows], mask[miss_rows],
                    None if topics is None else topics[miss_rows],
                    stage2_cap=(None if stage2_cap is None
                                else np.asarray(stage2_cap)[miss_rows]),
                    shard_cap=(None if shard_cap is None
                               else np.asarray(shard_cap)[miss_rows]),
                    now=now)
            finally:
                if tel is not None:
                    tel.batch_context = outer_ctx
                    self._tel_suppress = False
                    self._tel_cache_tag = None
            topk[miss_rows] = sub.topk
            if self.ltr is not None:
                for j, i in enumerate(miss_rows):
                    final_rows[i] = sub.final[j]
                used[miss_rows] = sub.candidates_used
            t0[miss_rows] = sub.stage_latency["stage0"]
            # misses pay the failed lookup on top of the cascade
            t1[miss_rows] = sub.stage_latency["stage1"] + hit_us
            t2[miss_rows] = sub.stage_latency["stage2"]
            if coverage is not None and sub.coverage is not None:
                coverage[miss_rows] = sub.coverage
            sb = sub.stats["budget"]
            trimmed += sb["stage2_trimmed"]
            skipped += sb["stage2_skipped"]
            with span(CACHE):
                for j, i in enumerate(miss_rows):
                    if not eligible[i]:
                        continue
                    if sub.coverage is not None and sub.coverage[j] < 1.0:
                        cache.counters["skipped_partial"] += 1
                        continue   # partial coverage is never cached
                    if self.ltr is not None:
                        v2 = sub.topk[j].copy()
                        if self.dense is not None:
                            v2 = (v2, bool(sub.dense["theta_skip"][j]))
                        cache.l2_put(keys2[i], v2, epoch)
                        cache.l1_put(keys1[i],
                                     (sub.topk[j].copy(), sub.final[j].copy(),
                                      int(sub.candidates_used[j])), epoch)
                    else:
                        cache.l1_put(keys1[i],
                                     (sub.topk[j].copy(), None, None), epoch)

        with span(ACCOUNT):
            final = (np.stack(final_rows) if self.ltr is not None else None)
            lat = t0 + t1 + t2
            stage_latency = {"stage0": t0, "stage1": t1, "stage2": t2}
            # the batch advances the shared serving clock exactly like the
            # direct path (the miss sub-serve's advance is overridden: the
            # batch's occupancy is the max over ALL its rows)
            self._clock = now + (float(lat.max()) if q else 0.0)

            dense_info = None
            if self.dense is not None:
                theta_all = np.zeros(q, bool)
                fb_all = np.zeros(q, bool)
                if sub is not None:
                    theta_all[miss_rows] = sub.dense["theta_skip"]
                    fb_all[miss_rows] = sub.dense["fallback"]
                if skip_flags is not None:
                    theta_all[rows2] = skip_flags
                # L1 rows keep False flags: their final list already baked in
                # whatever shortcut the fill-time serve took
                dense_info = {"modality": modality, "theta_skip": theta_all,
                              "fallback": fb_all}
            stats = self._build_stats(
                lat, stage_latency, trimmed, skipped, faulted, coverage, now,
                dense_info=dense_info, cache_stats=cache.stats())
            if self.telemetry is not None:
                self._record_hit_traces(l1_hit, l2_hit, lat, t0, t2, hit_us,
                                        now)
        return PipelineResult(topk=topk, final=final, candidates_used=used,
                              latency=lat, stage_latency=stage_latency,
                              stats=stats, coverage=coverage,
                              dense=dense_info)

    # ------------------------------------------------------------------
    # batch stats + telemetry
    # ------------------------------------------------------------------

    def _build_stats(self, lat, stage_latency, trimmed, skipped, faulted,
                     coverage, now, *, dense_info=None,
                     cache_stats=None) -> dict:
        """The per-batch stats dict both serve paths report — one builder
        so the direct and cached paths cannot drift — plus the telemetry
        feed (per-query/per-stage histograms and degradation counters)
        when a registry is attached."""
        q = len(lat)
        stats = dict(self.sched.stats)
        stats.update(percentiles(lat))
        n_over, pct = over_budget(lat, self.budget)
        stats["over_budget"] = n_over
        stats["over_budget_pct"] = pct
        stats["stages"] = {}
        for name, t in stage_latency.items():
            if not np.any(t > 0):
                continue
            entry = percentiles(t)
            # per-stage budget attribution: each stage is accountable to
            # its reserved share of the cascade budget (fused routes spend
            # the fusion reserve inside stage 1)
            b = (self._budget_reserve[name]
                 + (self._budget_reserve.get("fusion", 0.0)
                    if name == "stage1" else 0.0))
            entry["budget"] = b
            entry["over_budget"] = over_budget(t, b)[0]
            stats["stages"][name] = entry
        stats["budget"] = {
            "total": self.budget,
            "reserve": dict(self._budget_reserve),
            "enforce": self.sched.cfg.enforce_budget,
            "worst_case_bound": self.worst_case_us(),
            "stage2_trimmed": trimmed,
            "stage2_skipped": skipped,
        }
        stats["n_shards"] = self.n_shards
        stats["pool"] = self.pool.stats()
        if faulted:
            stats["faults"] = dict(self._fault_counters)
            stats["faults"]["clock"] = now
            stats["coverage"] = {
                "min": float(coverage.min()) if q else 1.0,
                "mean": float(coverage.mean()) if q else 1.0,
                "degraded": int((coverage < 1.0).sum()),
            }
        if cache_stats is not None:
            stats["cache"] = cache_stats
        if dense_info is not None:
            modality = dense_info["modality"]
            stats["dense"] = {
                "lexical": int(np.sum(modality == M_LEX)),
                "dense_only": int(np.sum(modality == M_DENSE)),
                "fused": int(np.sum(modality == M_BOTH)),
                "theta_skips": int(dense_info["theta_skip"].sum()),
                "fallbacks": int(dense_info["fallback"].sum()),
            }
        tel = self.telemetry
        if tel is not None and not self._tel_suppress:
            # micro-batch pads carry qid=-1 in the batch context: real
            # device work, but not queries — keep them out of the
            # per-query latency histograms and counters
            ctx_q = (tel.batch_context or {}).get("qid")
            keep = (np.asarray(ctx_q) >= 0 if ctx_q is not None
                    else slice(None))
            tel.record_batch(lat[keep],
                             {k: v[keep] for k, v in stage_latency.items()},
                             self.budget, trimmed=trimmed, skipped=skipped)
            if dense_info is not None:
                d = stats["dense"]
                for k in ("lexical", "dense_only", "fused"):
                    tel.registry.counter("modality", route=k).inc(d[k])
                tel.registry.counter("theta_skips").inc(d["theta_skips"])
                tel.registry.counter("dense_fallbacks").inc(d["fallbacks"])
        self._last_stats = stats
        return stats

    def _tel_context(self, q: int):
        """Resolve the per-row trace context: the online simulator sets
        ``telemetry.batch_context`` with queue waits, admission modes and
        real query ids around ``serve``; offline serves synthesize
        sequential qids and zero wait."""
        tel = self.telemetry
        ctx = tel.batch_context or {}
        wait = ctx.get("wait")
        modes = ctx.get("mode")
        qids = ctx.get("qid")
        budget = float(ctx.get("budget", self.budget))
        if qids is None:
            qids = tel.query_seq + np.arange(q)
            tel.query_seq += q
        return wait, modes, qids, budget

    def _record_traces(self, *, q, now, lat, stage_latency, pk, pr, pt,
                       routed, modality, theta_skip, fallback, used,
                       t_shards, faulted, delay, mult, lost, dropped,
                       coverage) -> None:
        """Build span trees for the rows the trace store would retain
        (slowest / budget-violating first; ``would_keep`` prunes the rest
        so trace building stays off the common path)."""
        tel = self.telemetry
        if tel.traces.capacity == 0:
            return
        wait, modes, qids, budget = self._tel_context(q)
        is_jass = np.zeros(q, bool)
        is_jass[routed.jass_rows] = True
        is_hedge = np.zeros(q, bool)
        is_hedge[routed.hedged_rows] = True
        timeout = self.sched.cfg.failover_timeout
        mod_name = {M_LEX: "lexical", M_DENSE: "dense", M_BOTH: "fused"}
        for r in range(q):
            if int(qids[r]) < 0:
                continue   # micro-batch pad row, not a query
            w = float(wait[r]) if wait is not None else 0.0
            total = float(lat[r]) + w
            violation = total > budget
            if not tel.traces.would_keep(total, violation):
                continue
            t0r = float(stage_latency["stage0"][r])
            root = Span("query")
            root.child("stage0", 0.0, t0r, pred_k=float(pk[r]),
                       pred_rho=float(pr[r]), pred_t=float(pt[r]))
            mirror = "jass" if is_jass[r] else "bmw"
            if is_hedge[r]:
                mirror += "+hedge"
            rmeta = dict(mirror=mirror, rho=float(routed.rho[r]),
                         k=int(routed.k[r]))
            if modality is not None:
                rmeta["modality"] = mod_name[int(modality[r])]
            root.child("route", t0r, 0.0, **rmeta)
            s1 = root.child("stage1", t0r,
                            float(stage_latency["stage1"][r]))
            for s in range(self.n_shards):
                smeta: dict = {"shard": s}
                dur = float(t_shards[s, r])
                if faulted:
                    d = float(delay[s, r])
                    if d > 0:
                        smeta["retry_wait_us"] = d
                        smeta["attempts_failed"] = (
                            int(round(d / timeout)) if timeout else 0)
                    if lost[s, r]:
                        smeta["lost"] = True
                    if dropped[s, r]:
                        smeta["dropped"] = True
                    if mult[s, r] != 1.0:
                        smeta["slowdown"] = float(mult[s, r])
                    dur = (0.0 if dropped[s, r] else
                           d + (0.0 if lost[s, r]
                                else float(t_shards[s, r] * mult[s, r])))
                s1.child("shard", t0r, dur, **smeta)
            if modality is not None and int(modality[r]) == M_BOTH:
                s1.child("fusion", 0.0, float(self.cost.fusion_us))
            if fallback is not None and fallback[r]:
                s1.child("dense_fallback", 0.0, 0.0)
            if self.delta is not None:
                s1.child("delta_scan", 0.0, float(self._delta_us))
            s2dur = float(stage_latency["stage2"][r])
            s2meta: dict = {}
            if used is not None:
                s2meta["candidates"] = int(used[r])
                if used[r] == 0:
                    s2meta["skipped"] = True
            if theta_skip is not None and theta_skip[r]:
                s2meta["theta_skip"] = True
            root.child("stage2", float(lat[r]) - s2dur, s2dur, **s2meta)
            meta = {
                "wait_us": w,
                "service_us": float(lat[r]),
                "reserve_us": float(
                    self._budget_reserve.get("stage2", 0.0)),
            }
            if modes is not None:
                meta["mode"] = str(modes[r])
            if self._tel_cache_tag is not None:
                meta["cache"] = self._tel_cache_tag
            if faulted:
                meta["coverage"] = float(coverage[r])
            tel.traces.offer(QueryTrace(
                qid=int(qids[r]), clock_us=now, latency_us=total,
                budget_us=budget, violation=violation, root=root,
                meta=meta))

    def _record_hit_traces(self, l1_hit, l2_hit, lat, t0, t2, hit_us,
                           now) -> None:
        """Traces for cache-hit rows (miss rows were traced by the
        sub-serve with a ``cache: miss`` tag)."""
        tel = self.telemetry
        if tel.traces.capacity == 0:
            return
        q = len(lat)
        wait, modes, qids, budget = self._tel_context(q)
        for r in np.flatnonzero(l1_hit | l2_hit):
            level = "l1" if l1_hit[r] else "l2"
            w = float(wait[r]) if wait is not None else 0.0
            total = float(lat[r]) + w
            violation = total > budget
            if not tel.traces.would_keep(total, violation):
                continue
            root = Span("query")
            root.child("stage0", 0.0, float(t0[r]))
            root.child("cache_lookup", float(t0[r]), float(hit_us),
                       level=level, hit=True)
            if t2[r] > 0:
                root.child("stage2", float(lat[r]) - float(t2[r]),
                           float(t2[r]))
            meta = {"wait_us": w, "service_us": float(lat[r]),
                    "cache": level,
                    "reserve_us": float(
                        self._budget_reserve.get("stage2", 0.0))}
            if modes is not None:
                meta["mode"] = str(modes[r])
            tel.traces.offer(QueryTrace(
                qid=int(qids[r]), clock_us=now, latency_us=total,
                budget_us=budget, violation=violation, root=root,
                meta=meta))

    def _export_metrics(self) -> None:
        """Mirror every cumulative stats dict and subsystem counter into
        the registry (``key=`` labels preserve the legacy key names so
        ``legacy_stats_view`` can reconstruct the old sections)."""
        reg = self.telemetry.registry
        for k, v in self.sched.stats.items():
            reg.counter("scheduler", key=k).set_total(v)
        for k, v in self._fault_counters.items():
            reg.counter("faults", key=k).set_total(v)
        for k, v in self._stage1_counters.items():
            reg.counter("stage1", key=k).set_total(v)
        reg.gauge("faults", key="clock").set(self._clock)
        for k, v in self._ingest_counters.items():
            reg.counter("ingest", key=k).set_total(v)
        reg.gauge("n_shards").set(self.n_shards)
        reg.gauge("batches").set(self._batches)
        reg.gauge("budget_us").set(self.budget)
        reg.gauge("worst_case_us").set(self.worst_case_us())
        reg.gauge("clock_us").set(self._clock)
        self.pool.export_metrics(reg)
        self.faults.export_metrics(reg)
        if self.cache is not None:
            self.cache.export_metrics(reg)
        if self.delta is not None:
            self.delta.export_metrics(reg)
            reg.gauge("ingest", key="delta_us").set(self._delta_us)
        self.telemetry.export_online()

    def snapshot(self, now: float | None = None) -> dict:
        """One scrapeable observability snapshot: every counter, gauge and
        histogram in the registry plus the retained slowest/violating
        traces with their ``why_slow`` attribution.  Deterministic — two
        same-seed runs render byte-identical JSON.  Requires an enabled
        :class:`~repro.serving.spec.TelemetrySpec`."""
        if self.telemetry is None:
            raise RuntimeError(
                "telemetry is disabled (spec.telemetry.enabled=False); "
                "enable it to export snapshots")
        self._export_metrics()
        snap = self.telemetry.registry.snapshot()
        snap["version"] = 1
        snap["spec"] = self.cascade_spec.name
        snap["clock_us"] = float(self._clock if now is None else now)
        snap["budget_us"] = float(self.budget)
        snap["worst_case_us"] = float(self.worst_case_us())
        snap["traces"] = [t.to_dict()
                          for t in self.telemetry.traces.slowest()]
        return snap

    def render_snapshot(self, fmt: str = "json",
                        now: float | None = None) -> str:
        """Render :meth:`snapshot` as ``json`` (byte-deterministic) or
        ``prom`` (Prometheus text exposition; traces are JSON-only)."""
        snap = self.snapshot(now=now)
        if fmt == "json":
            return render_json(snap)
        if fmt == "prom":
            return render_prometheus(snap)
        raise ValueError(f"unknown snapshot format {fmt!r}")

    def serve_online(self, terms: np.ndarray, mask: np.ndarray,
                     topics: np.ndarray | None = None, *,
                     traffic, online=None):
        """Serve the query log under load: event-driven arrivals
        (:class:`~repro.serving.spec.TrafficSpec`), dynamic micro-batching,
        and admission control, reporting end-to-end **response-time**
        percentiles (queueing included) up to p99.99.

        ``online`` overrides the spec's :class:`~repro.serving.spec.
        OnlineSpec`.  Returns an :class:`~repro.serving.online.simulator.
        OnlineResult`."""
        from repro.serving.online import simulate
        return simulate(self, terms, mask, topics, traffic, online)

    def worst_case_us(self) -> float:
        """The hard analytic bound on any served query's cascade latency:
        the scheduler's Stage-1 bound (which already pays ``predict_us``)
        plus the reserved worst-case Stage-2 cost.  With ``enforce_budget``
        and ``late_rho <= SchedulerConfig.max_late_rho(cost, n_shards)``
        this is at most the cascade budget — the paper's 99.99 % as a hard
        guarantee (certified on a trace by ``benchmarks/bench_tail.py``).
        The bound is scatter-gather aware: the late re-issue pays the
        per-extra-shard gather overhead, so ``max_late_rho`` shrinks as
        shards are added.  With a serving cache attached, every query
        additionally pays the lookup (``cache_hit_us``) — charging it here
        keeps the guarantee analytic with caching on (a hit costs strictly
        less than the bound; a miss costs the cascade plus the lookup).

        With the dense modality enabled the bound is the max over the
        three routes, all analytic from spec shapes alone:

        * **lexical** — the scheduler bound, unchanged (the stage-1 share
          it enforces already had ``fusion_us`` carved out);
        * **dense only** — ``predict + dense_time(max_tiles) + gather +
          retry``, plus the ρ_late-capped fallback traversal when
          ``theta_low`` is armed (the dense per-shard cost is shape-static,
          so this term needs no df tables);
        * **both + fused** — the engines run in parallel (max of the two
          stage-1 terms) plus the reserved ``fusion_us``; since the
          scheduler enforces the reduced share, this collapses back to at
          most the original stage-1 reserve.
        """
        cfg = self.sched.cfg
        base = cfg.worst_case_us(self.cost, self.n_shards)
        if self.dense is not None:
            ds = self.cascade_spec.dense
            pd = self.cost.predict_us
            gather = self.cost.gather_per_shard_us * (self.n_shards - 1)
            td = (float(self.cost.dense_time(self.dense.max_tiles()))
                  + gather + cfg.retry_us())
            fb = (float(self.cost.saat_time(
                      np.float64(cfg.resolved_late_rho()))) + gather
                  if np.isfinite(ds.theta_low) else 0.0)
            dense_bound = pd + td + fb
            both_bound = pd + max(base - pd, td) + self.cost.fusion_us
            base = max(base, dense_bound, both_bound)
        # live ingest: every query additionally scans the capacity-padded
        # delta segment (lexical + dense tiles) — the same static term the
        # serve path charges, so the bound stays analytic while feeding
        return (base + self._delta_us + self._budget_reserve["stage2"]
                + (self.cost.cache_hit_us if self.cache is not None
                   else 0.0))

    # ------------------------------------------------------------------
    # live ingest: feed → delta segment → background merge
    # ------------------------------------------------------------------

    def _refresh_dense_delta(self) -> None:
        """Re-embed the delta docs through the sealed quantized source and
        hand the capacity-padded matrix to the dense engine (ghost rows
        stay zero; the engine masks them after ranking)."""
        if self.dense is None or self.delta is None:
            return
        d = self.delta
        emb = np.zeros((d.capacity_docs, self.dense.d), np.float32)
        if d.n_docs:
            emb[:d.n_docs] = delta_doc_embeddings(
                self.cascade_spec.dense, n_sealed=d.base_docs,
                n_new=d.n_docs,
                vocab=int(np.asarray(self.index.df).shape[0]),
                topics=d.doc_topics, corpus=self.corpus)
        self.dense.set_delta(emb, d.n_docs, d.base_docs)

    def add_documents(self, feed: FeedDocs) -> int:
        """Ingest the longest admissible prefix of ``feed`` into the live
        delta segment; returns the number of docs accepted (0 = the delta
        is full — call :meth:`merge` to reseal, then re-offer the rest).
        Served results include the new docs immediately; the cache epoch
        bumps so no stale entry survives the collection change."""
        if self.delta is None:
            raise RuntimeError("live ingest is disabled "
                               "(spec.ingest.enabled=False)")
        took = self.delta.add(feed)
        if took:
            self._ingest_counters["epoch"] += 1
            self._ingest_counters["feed_batches"] += 1
            self._ingest_counters["docs_ingested"] += took
            self._refresh_dense_delta()
        return took

    def merge(self) -> int:
        """Fold the delta into the sealed collection (the background
        merge): rebuilds the index bit-identically to a from-scratch build
        over the extended corpus, re-attaches every index-derived serving
        structure, and resets the delta against the new seal.  Returns the
        number of docs merged (0 = nothing to do)."""
        if self.delta is None:
            raise RuntimeError("live ingest is disabled "
                               "(spec.ingest.enabled=False)")
        n = self.delta.n_docs
        if n == 0:
            return 0
        if self.corpus is None:
            raise RuntimeError("merge needs the corpus the sealed index "
                               "was built from")
        new_corpus, new_index = self.delta.merged(self.corpus)
        self.corpus = new_corpus
        self._attach_index(new_index)
        self.delta.reset(new_index)
        if self.dense is not None:
            self.dense.clear_delta()
        if self.ltr is not None:
            # Stage-2 ranks against the resealed collection's CSR arrays
            self.s2 = stage2_arrays(self.index, self.corpus)
            self.n_iter = csr_search_iters(int(self.index.df.max()))
        self._ingest_counters["epoch"] += 1
        self._ingest_counters["merges"] += 1
        self._ingest_counters["docs_merged"] += n
        return n

    def _adapt_routing(self):
        """Close the routing feedback loop from pool EWMAs + scheduler
        counters (``RoutingSpec.adapt_every``).

        * ``t_time`` tracks the observed mirror balance: when the BMW
          mirror's EWMA latency rises relative to JASS, the threshold drops
          and Algorithm 2 routes more traffic to the bounded mirror.
        * ``hedge_band`` widens after a window that needed late hedges
          (hedge earlier next time) and decays slowly through clean
          windows, so duplicated JASS work shrinks when the tail is quiet.
        * ``hedge_deadline`` follows the t-predictor's online quantile
          error (rolling pinball-loss EWMA): unreliable predictions →
          detect stragglers earlier; trustworthy ones → later detection,
          less duplicated JASS work.  The deadline never exceeds the
          feasibility ceiling ``(B₁ - ρ_late·c_s - gather) / B₁``, so the
          worst-case bound keeps collapsing to the budget — adaptation can
          only spend hedge work, never the guarantee.  With
          ``adapt_every=0`` the spec's fixed value is used unchanged.

        The adapted values are folded back into ``cascade_spec`` so
        ``to_json()`` names the *live* operating point.
        """
        cfg = self.sched.cfg
        changed: dict = {}
        ewma = self.pool.mirror_ewma()
        e_j, e_b = ewma[JASS], ewma[BMW]
        if e_j is not None and e_b is not None and e_j + e_b > 0:
            alpha, b1 = 0.2, cfg.budget
            target = b1 * float(np.clip(e_j / (e_j + e_b), 0.1, 0.9))
            changed["t_time"] = float(np.clip(
                (1 - alpha) * cfg.t_time + alpha * target,
                0.05 * b1, 0.95 * b1))
        d_late = self.sched.stats["late_hedged"] \
            - self._adapt_last["late_hedged"]
        d_bmw = self.sched.stats["bmw"] - self._adapt_last["bmw"]
        self._adapt_last = {"late_hedged": self.sched.stats["late_hedged"],
                            "bmw": self.sched.stats["bmw"]}
        if d_bmw > 0:
            band = cfg.hedge_band * (1.25 if d_late > 0 else 0.98)
            changed["hedge_band"] = float(np.clip(band, 0.05, 0.5))
        if self._pinball_ewma is not None:
            late = float(self.cost.saat_time(
                np.float64(cfg.resolved_late_rho())))
            gather = self.cost.gather_per_shard_us * (self.n_shards - 1)
            d_max = (cfg.budget - late - gather) / cfg.budget
            if d_max > 0.05:
                # relative quantile error of the t predictor; 2x scaling
                # so a pinball loss of half the budget already pins the
                # deadline at its floor
                err = self._pinball_ewma / cfg.budget
                d_target = float(np.clip(
                    d_max * (1.0 - min(2.0 * err, 0.8)), 0.05, d_max))
                changed["hedge_deadline"] = float(np.clip(
                    0.8 * cfg.hedge_deadline + 0.2 * d_target,
                    0.05, min(d_max, 1.0)))
        if changed:
            self.sched.cfg = replace(cfg, **changed)
            self._base_cfg = replace(self._base_cfg, **changed)
            self.cascade_spec = replace(
                self.cascade_spec,
                routing=replace(self.cascade_spec.routing, **changed))

    def stats(self) -> dict:
        """Deployment-level health: spec identity, shard layout, scheduler
        counters, replica-pool health, and the last batch's tail.

        With telemetry enabled the scalar counter sections (scheduler /
        faults / ingest) are *derived from the registry snapshot* — the
        registry is the one source of truth and this dict is a thin
        compat view over it; with telemetry disabled the legacy dicts are
        reported directly (identical values either way)."""
        tel = self.telemetry
        if tel is not None:
            self._export_metrics()
            snap = tel.registry.snapshot()
            scheduler = legacy_stats_view(snap, "scheduler")
            stage1 = legacy_stats_view(snap, "stage1")
            fault_ctr = legacy_stats_view(snap, "faults")
            ingest = legacy_stats_view(snap, "ingest")
        else:
            scheduler = dict(self.sched.stats)
            stage1 = dict(self._stage1_counters)
            fault_ctr = dict(self._fault_counters)
            fault_ctr["clock"] = self._clock
            ingest = None
        s = {
            "spec": self.cascade_spec.name,
            "n_shards": self.n_shards,
            "shard_docs": [sp.n_docs for sp in self.shard_specs],
            "replicas": self.cascade_spec.deploy.replicas,
            "batches": self._batches,
            "scheduler": scheduler,
            "stage1": stage1,
            "budget": {"total": self.budget,
                       "reserve": dict(self._budget_reserve),
                       "enforce": self.sched.cfg.enforce_budget,
                       "worst_case_bound": self.worst_case_us()},
            "pool": self.pool.stats(),
        }
        if self.faults.active or any(self._fault_counters.values()):
            s["faults"] = fault_ctr
        if self.delta is not None:
            if ingest is None:
                ingest = dict(self.delta.stats())
                ingest.update(self._ingest_counters)
                ingest["delta_us"] = self._delta_us
            s["ingest"] = ingest
        if self._last_stats:
            s["last_batch"] = {k: self._last_stats[k]
                               for k in ("p50", "p99", "p99.99", "max",
                                         "over_budget", "over_budget_pct")
                               if k in self._last_stats}
        return s
