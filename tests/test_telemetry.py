"""Telemetry subsystem suite: deterministic metrics (histogram quantile
bounds, registry semantics), per-query trace trees across serving paths
(full / degraded / shed / cache-hit / faulted), snapshot determinism and
exports, the enabled=False inertness contract, the shared bench-payload
schema, and the obs_diff regression rules."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.serving.latency import CostModel
from repro.serving.spec import (BackendSpec, CacheSpec, CascadeSpec,
                                DeploySpec, FaultSpec, OnlineSpec,
                                RoutingSpec, Stage2Spec, TelemetrySpec,
                                TrafficSpec)
from repro.serving.system import build_system
from repro.serving.telemetry import (LogHistogram, MetricsRegistry,
                                     QueryTrace, Span, TraceStore, why_slow)
from repro.serving.telemetry.export import (legacy_stats_view, render_json,
                                            render_prometheus)

INF = float("inf")


# ---------------------------------------------------------------------------
# histogram: exact small-N path + bounded bucketed quantiles
# ---------------------------------------------------------------------------

def _adversarial_streams():
    rng = np.random.RandomState(7)
    return {
        "constant": np.full(200, 42.5),
        "two_point": np.array([1.0] * 150 + [5000.0] * 50),
        "arange": np.arange(1, 201, dtype=np.float64),
        "heavy_tail": np.exp(rng.normal(3.0, 2.0, size=200)),
        "near_edges": np.array([1e-3, 1e-3 * 1.0001, 9.99e6, 1e7] * 50),
    }


@pytest.mark.parametrize("name,vals",
                         sorted(_adversarial_streams().items()))
def test_histogram_exact_small_n_matches_numpy(name, vals):
    """While N <= exact_n the histogram answers quantiles EXACTLY —
    bit-equal to numpy's inverted-CDF estimator."""
    h = LogHistogram(exact_n=256)
    h.observe(vals)
    assert h.exact
    for q in (0.0, 0.1, 0.5, 0.9, 0.95, 0.99, 0.9999, 1.0):
        assert h.quantile(q) == float(
            np.quantile(vals, q, method="inverted_cdf")), (name, q)


@pytest.mark.parametrize("name,vals",
                         sorted(_adversarial_streams().items()))
def test_histogram_bucketed_within_documented_bound(name, vals):
    """Past exact_n the relative error of any quantile is bounded by
    sqrt(gamma) - 1 for values inside [lo, hi] (the documented
    guarantee), at the default 64 bins/decade ~1.8%."""
    big = np.tile(vals, 50)             # 10k values >> exact_n
    h = LogHistogram(exact_n=64)
    h.observe(big)
    assert not h.exact
    for q in (0.5, 0.95, 0.99, 0.9999):
        truth = float(np.quantile(big, q, method="inverted_cdf"))
        est = h.quantile(q)
        if h.lo <= truth <= h.hi:
            assert abs(est - truth) <= h.rel_err_bound * truth + 1e-12, (
                name, q, truth, est)


def test_histogram_out_of_range_and_errors():
    h = LogHistogram(exact_n=0, lo=1.0, hi=100.0)
    h.observe(np.zeros(10))             # underflow bucket
    assert h.quantile(0.5) == 0.0       # rep lo/2 clamped to max=0
    h2 = LogHistogram(exact_n=0, lo=1.0, hi=100.0)
    h2.observe([1e9] * 5)               # overflow bucket -> tracked max
    assert h2.quantile(0.99) == 1e9
    with pytest.raises(ValueError, match=">= 0"):
        h2.observe([-1.0])
    assert np.isnan(LogHistogram().quantile(0.5))  # empty
    with pytest.raises(ValueError):
        LogHistogram().quantile(1.5)
    # flush: crossing exact_n converts the buffer without losing counts
    h3 = LogHistogram(exact_n=8)
    h3.observe(np.arange(1.0, 7.0))
    assert h3.exact
    h3.observe(np.arange(7.0, 20.0))
    assert not h3.exact and h3.count == 19
    assert h3.snapshot()["rel_err_bound"] == pytest.approx(
        10 ** (1 / 128) - 1, rel=1e-6)


def test_registry_and_counter_semantics():
    reg = MetricsRegistry()
    reg.counter("served", mode="full").inc(3)
    reg.counter("served", mode="full").inc()
    assert reg.counters['served{mode="full"}'].value == 4
    with pytest.raises(ValueError, match=">= 0"):
        reg.counter("served").inc(-1)
    c = reg.counter("mirrored")
    c.set_total(10)
    with pytest.raises(ValueError, match="backwards"):
        c.set_total(9)
    reg.gauge("depth").set(7)
    snap = reg.snapshot()
    assert snap["gauges"]["depth"] == 7.0
    assert list(snap["counters"]) == sorted(snap["counters"])


def test_trace_store_keeps_slowest_and_violations():
    st = TraceStore(capacity=3)

    def trace(lat, viol):
        return QueryTrace(qid=0, clock_us=0.0, latency_us=lat,
                          budget_us=100.0, violation=viol,
                          root=Span("query"), meta={})

    for lat in (10.0, 20.0, 30.0, 40.0, 5.0):
        st.offer(trace(lat, False))
    assert [t.latency_us for t in st.slowest()] == [40.0, 30.0, 20.0]
    # a violating trace outranks any non-violating one
    st.offer(trace(1.0, True))
    assert st.slowest()[0].violation and len(st) == 3
    assert st.offered == 6 and not st.would_keep(0.5, False)


def test_why_slow_attribution():
    root = Span("query")
    root.child("stage0", 0.0, 5.0)
    root.child("stage1", 5.0, 80.0)
    root.child("stage2", 85.0, 10.0)
    tr = QueryTrace(qid=3, clock_us=0.0, latency_us=120.0, budget_us=100.0,
                    violation=True, root=root, meta={"wait_us": 25.0})
    w = why_slow(tr)
    assert w["stage"] == "stage1" and w["duration_us"] == 80.0
    assert "VIOLATED" in w["detail"]
    # queue time competes as a pseudo-stage
    tr2 = QueryTrace(qid=4, clock_us=0.0, latency_us=120.0,
                     budget_us=200.0, violation=False, root=root,
                     meta={"wait_us": 90.0})
    assert why_slow(tr2)["stage"] == "queue"


# ---------------------------------------------------------------------------
# spec node
# ---------------------------------------------------------------------------

def test_telemetry_spec_round_trip_and_validation():
    spec = CascadeSpec(telemetry=TelemetrySpec(
        enabled=True, bins_per_decade=32, exact_n=128,
        trace_reservoir=16, snapshot_every_us=500.0, max_snapshots=8))
    again = CascadeSpec.from_json(spec.to_json())
    assert again.telemetry == spec.telemetry and again.telemetry.active
    # pre-telemetry wire format (no node) still loads, inert by default
    d = json.loads(spec.to_json())
    d.pop("telemetry")
    assert CascadeSpec.from_dict(d).telemetry == TelemetrySpec()
    assert not TelemetrySpec().active
    with pytest.raises(ValueError, match="bins_per_decade"):
        TelemetrySpec(bins_per_decade=0).validate()
    with pytest.raises(ValueError, match="trace_reservoir"):
        TelemetrySpec(trace_reservoir=-1).validate()
    with pytest.raises(ValueError, match="snapshot_every_us"):
        TelemetrySpec(snapshot_every_us=-2.0).validate()


# ---------------------------------------------------------------------------
# end-to-end: a small fitted system, telemetry on vs off
# ---------------------------------------------------------------------------

def _spec(telemetry=None, fault=None, cache=None, failover=0.0, retries=0,
          budget=100.0, **online_kw):
    online = {"max_batch": 8, "batch_deadline_us": 4.0}
    online.update(online_kw)
    return CascadeSpec(
        routing=RoutingSpec(budget=budget, rho_max=1 << 14, t_k=150.0,
                            t_time=18.0, adapt_every=0,
                            failover_timeout=failover,
                            max_retries=retries),
        stage2=Stage2Spec(enabled=True, k_serve=32, t_final=5),
        backend=BackendSpec(backend="jnp"),
        deploy=DeploySpec(n_shards=2, replicas=2),
        online=OnlineSpec(**online),
        telemetry=telemetry if telemetry is not None else TelemetrySpec(),
        fault=fault if fault is not None else FaultSpec(),
        cache=cache if cache is not None else CacheSpec(),
        name="telemetry_test",
    )


@pytest.fixture(scope="module")
def fitted(small_collection):
    corpus, index, ql = small_collection
    spec = _spec()
    spec = dataclasses.replace(
        spec, routing=dataclasses.replace(spec.routing, t_k=None,
                                          t_time=None, calibrate=True))
    system = build_system(spec, index, corpus=corpus)
    system.fit(ql, None, seed=5)
    return corpus, index, ql, system, (system._base_cfg.t_k,
                                       system._base_cfg.t_time)


def _system(fitted, **kw):
    corpus, index, ql, system, (tk, tt) = fitted
    spec = _spec(**kw)
    spec = dataclasses.replace(
        spec, routing=dataclasses.replace(spec.routing, t_k=tk, t_time=tt))
    return build_system(spec, index, corpus=corpus, models=system.models,
                        ltr=system.ltr)


TEL = TelemetrySpec(enabled=True)


def test_disabled_telemetry_is_provably_inert(fitted):
    """enabled=False means no registry is allocated, serving is
    bit-identical to a telemetry-on run, and snapshot() refuses."""
    corpus, index, ql, _, _ = fitted
    off = _system(fitted)
    on = _system(fitted, telemetry=TEL)
    assert off.telemetry is None and on.telemetry is not None
    a = off.serve(ql.terms, ql.mask, ql.topic)
    b = on.serve(ql.terms, ql.mask, ql.topic)
    np.testing.assert_array_equal(a.topk, b.topk)
    np.testing.assert_array_equal(a.final, b.final)
    np.testing.assert_array_equal(a.latency, b.latency)
    with pytest.raises(RuntimeError, match="telemetry is disabled"):
        off.snapshot()


def test_disabled_telemetry_online_event_log_bit_identical(fitted):
    corpus, index, ql, _, _ = fitted
    traffic = TrafficSpec(arrival="bursty", qps=150.0, seed=3)
    a = _system(fitted).serve_online(ql.terms, ql.mask, ql.topic,
                                     traffic=traffic)
    b = _system(fitted, telemetry=TEL).serve_online(
        ql.terms, ql.mask, ql.topic, traffic=traffic)
    assert a.event_log == b.event_log
    np.testing.assert_array_equal(a.response, b.response)
    np.testing.assert_array_equal(a.topk, b.topk)
    assert "telemetry" not in a.stats and "telemetry" in b.stats


def test_stats_compat_view_matches_legacy(fitted):
    """stats() with telemetry on routes scheduler/fault/ingest sections
    through the registry — and must equal the legacy direct dicts."""
    corpus, index, ql, _, _ = fitted
    off = _system(fitted)
    on = _system(fitted, telemetry=TEL)
    off.serve(ql.terms, ql.mask, ql.topic)
    on.serve(ql.terms, ql.mask, ql.topic)
    s_off, s_on = off.stats(), on.stats()
    assert set(s_on) == set(s_off)
    for section in ("scheduler", "faults", "ingest", "pool"):
        if section in s_off:
            assert s_on[section] == s_off[section], section
    assert s_on["scheduler"] and s_on["n_shards"] == s_off["n_shards"]


def test_stage1_deep_select_counter_in_snapshot(fitted):
    """``stage1{key="deep_select_rows"}`` counts the Stage-1 rows a kernel
    backend serves through the deep top-k select: 0 at a depth within one
    accumulator tile, every routed row past it; ``stats()`` reports the
    same section with telemetry on or off."""
    corpus, index, ql, _, _ = fitted
    key = 'stage1{key="deep_select_rows"}'
    shallow = _system(fitted, telemetry=TEL)
    shallow.serve(ql.terms[:8], ql.mask[:8], ql.topic[:8])
    assert shallow.snapshot()["counters"][key] == 0
    assert shallow.stats()["stage1"] == {"deep_select_rows": 0}
    spec = dataclasses.replace(
        shallow.cascade_spec, backend=BackendSpec(backend="interpret"),
        stage2=dataclasses.replace(shallow.cascade_spec.stage2,
                                   k_serve=200))
    deep = [build_system(dataclasses.replace(spec, telemetry=tel), index,
                         corpus=corpus, models=shallow.models,
                         ltr=shallow.ltr)
            for tel in (TEL, TelemetrySpec())]
    for sysm in deep:
        sysm.serve(ql.terms[:8], ql.mask[:8], ql.topic[:8])
        assert sysm.stats()["stage1"] == {"deep_select_rows": 8}
    assert deep[0].snapshot()["counters"][key] == 8


def test_offline_snapshot_contents_and_determinism(fitted):
    """The snapshot exports per-stage quantiles + counters, and two
    same-seed runs render byte-identical JSON."""
    corpus, index, ql, _, _ = fitted

    def run():
        sysm = _system(fitted, telemetry=TEL)
        sysm.serve(ql.terms, ql.mask, ql.topic)
        return sysm.snapshot()

    snap = run()
    h = snap["histograms"]
    assert h["service_latency_us"]["count"] == len(ql.terms)
    for st in ("stage0", "stage1", "stage2"):
        key = f'stage_latency_us{{stage="{st}"}}'
        assert key in h and "p99.99" in h[key]
        assert h[key]["p50"] <= h[key]["p99"] <= h[key]["p99.99"]
    assert snap["counters"]["queries_served"] == len(ql.terms)
    assert "worst_case_us" in snap and snap["budget_us"] == 100.0
    assert snap["traces"], "trace reservoir must retain slowest queries"
    tr = snap["traces"][0]
    names = [c["name"] for c in tr["spans"]["children"]]
    assert names[:2] == ["stage0", "route"] and "stage1" in names
    assert "why_slow" in tr
    assert render_json(snap) == render_json(run())  # byte-deterministic
    prom = render_prometheus(snap)
    assert "# TYPE repro_service_latency_us summary" in prom
    assert 'quantile="0.9999"' in prom
    assert "repro_queries_served_total" in prom


def test_online_snapshot_counters_and_shed_traces(fitted):
    """Overload: shed/degrade events surface as counters + shed traces
    carry an admission span; mode counters reconcile with the event
    log."""
    corpus, index, ql, _, _ = fitted
    sysm = _system(fitted, telemetry=TEL, queue_cap=8)
    res = sysm.serve_online(ql.terms, ql.mask, ql.topic,
                            traffic=TrafficSpec(arrival="bursty",
                                                qps=3000.0, seed=3))
    snap = sysm.snapshot()
    c = snap["counters"]
    shed = sum(v for k, v in c.items() if k.startswith("shed_queries"))
    assert shed == res.stats["shed"] and shed > 0
    served = sum(v for k, v in c.items() if k.startswith("served_mode"))
    assert served == res.stats["served"]
    assert "queue_wait_us" in snap["histograms"]
    assert "response_latency_us" in snap["histograms"]
    shed_traces = [t for t in snap["traces"]
                   if t["meta"].get("mode") == "shed"]
    assert shed_traces, "shed decisions must leave a trace"
    assert shed_traces[0]["spans"]["children"][0]["name"] == "admission"


def test_degraded_mode_counters_under_tight_budget(fitted):
    """A tight budget exercises the trim/skip path; the telemetry
    counters must agree with the batch stats."""
    corpus, index, ql, _, _ = fitted
    sysm = _system(fitted, telemetry=TEL, budget=10.0)
    res = sysm.serve(ql.terms, ql.mask, ql.topic)
    b = res.stats["budget"]
    assert b["stage2_trimmed"] + b["stage2_skipped"] > 0
    snap = sysm.snapshot()
    assert snap["counters"].get("stage2_trimmed", 0) == b["stage2_trimmed"]
    assert snap["counters"].get("stage2_skipped", 0) == b["stage2_skipped"]
    if b["stage2_skipped"]:
        skipped = [t for t in snap["traces"] for s in
                   t["spans"]["children"]
                   if s["name"] == "stage2"
                   and s.get("meta", {}).get("skipped")]
        assert skipped


def test_cache_hit_traces_and_hit_ratio_gauge(fitted):
    corpus, index, ql, _, _ = fitted
    sysm = _system(fitted, telemetry=TEL,
                   cache=CacheSpec(enabled=True, l1_entries=256,
                                   l2_entries=256))
    # 2 x 14 = 28 offers < the 32-slot reservoir: every trace is kept,
    # including the fast L1 hits (which never outrank cold serves)
    n = 14
    sysm.serve(ql.terms[:n], ql.mask[:n], ql.topic[:n])   # cold fill
    sysm.serve(ql.terms[:n], ql.mask[:n], ql.topic[:n])   # warm: L1 hits
    snap = sysm.snapshot()
    assert snap["gauges"]["cache_hit_ratio"] > 0
    assert snap["counters"]['cache_level{key="hits",level="l1"}'] > 0
    hits = [t for t in snap["traces"] if t["meta"].get("cache") == "l1"]
    assert hits and any(s["name"] == "cache_lookup"
                        and s.get("meta", {}).get("hit")
                        for t in hits for s in t["spans"]["children"])


def test_fault_retry_traces_and_counters(fitted):
    """A dead replica: retries surface in the faults counters and the
    per-shard spans carry the failed-attempt accounting."""
    corpus, index, ql, _, _ = fitted
    fault = FaultSpec(crashes=((0, 0, 0.0, INF),))
    sysm = _system(fitted, telemetry=TEL, fault=fault, failover=15.0,
                   retries=2)
    sysm.serve(ql.terms, ql.mask, ql.topic)
    snap = sysm.snapshot()
    assert snap["counters"]['faults{key="retries"}'] > 0
    retried = [s for t in snap["traces"]
               for c in t["spans"]["children"] if c["name"] == "stage1"
               for s in c["children"]
               if s["name"] == "shard" and "retry_wait_us" in s["meta"]]
    assert retried and all(s["meta"]["attempts_failed"] >= 1
                           for s in retried)
    assert all("coverage" in t["meta"] for t in snap["traces"])


def test_periodic_snapshots_on_virtual_clock(fitted):
    corpus, index, ql, _, _ = fitted
    tel = TelemetrySpec(enabled=True, snapshot_every_us=50.0,
                        max_snapshots=16)
    sysm = _system(fitted, telemetry=tel)
    res = sysm.serve_online(ql.terms, ql.mask, ql.topic,
                            traffic=TrafficSpec(arrival="poisson",
                                                qps=150.0, seed=3))
    snaps = sysm.telemetry.snapshots
    assert 0 < len(snaps) <= 16
    assert res.stats["telemetry"]["snapshots"] == len(snaps)
    clocks = [s["clock_us"] for s in snaps]
    assert clocks == sorted(clocks)


def test_legacy_stats_view_unit():
    reg = MetricsRegistry()
    reg.counter("scheduler", key="served").set_total(12)
    reg.gauge("scheduler", key="fill").set(0.5)
    reg.counter("other", key="x").set_total(1)
    view = legacy_stats_view(reg.snapshot(), "scheduler")
    assert view == {"served": 12, "fill": 0.5}
    assert isinstance(view["served"], int)


# ---------------------------------------------------------------------------
# bench schema + obs_diff rules
# ---------------------------------------------------------------------------

def test_bench_payload_schema():
    from benchmarks.common import (BENCH_SCHEMA_VERSION, bench_payload,
                                   validate_bench_payload)
    p = bench_payload("tail", config={"seed": 1}, rows=[{"a": 1}],
                      parity={"ok": True}, gates={"g": np.bool_(True)},
                      extra={"capacity": 3.0})
    assert p["schema_version"] == BENCH_SCHEMA_VERSION
    assert p["capacity"] == 3.0 and p["rows"] == [{"a": 1}]
    validate_bench_payload(p)
    with pytest.raises(ValueError, match="collides"):
        bench_payload("x", config={}, extra={"rows": []})
    with pytest.raises(ValueError, match="gates"):
        bench_payload("x", config={}, gates={"g": 1})
    with pytest.raises(ValueError, match="config"):
        validate_bench_payload({"schema_version": 1, "name": "x",
                                "rows": []})
    with pytest.raises(ValueError, match="timestamp"):
        validate_bench_payload({"schema_version": 1, "name": "x",
                                "config": {}, "rows": [], "parity": None,
                                "timestamp": 3})
    assert "timestamp" not in bench_payload("x", config={})
    assert bench_payload("x", config={},
                         timestamp="2026-08-08")["timestamp"]


def _fake_snap(p99=100.0, violations=0, shed=0, hit=0.5):
    return {
        "counters": {"budget_violations": violations,
                     'shed_queries{where="arrival"}': shed,
                     "queries_served": 100},
        "gauges": {"cache_hit_ratio": hit},
        "histograms": {"service_latency_us": {
            "count": 100, "sum": 5000.0, "min": 1.0, "max": p99 * 1.2,
            "p50": p99 / 2, "p95": p99 * 0.9, "p99": p99,
            "p99.99": p99 * 1.1}},
    }


def test_obs_diff_rules():
    from benchmarks.obs_diff import (diff_snapshots, format_findings,
                                     inject_regression)
    base = _fake_snap()
    assert diff_snapshots(base, base) == []
    # faster + fewer sheds never fails
    assert diff_snapshots(base, _fake_snap(p99=50.0)) == []
    # latency blow-up is flagged with the latency rule
    f = diff_snapshots(base, _fake_snap(p99=200.0))
    assert f and all(x["rule"] == "latency" for x in f)
    # 0 -> nonzero violations hard-fails even within rel tolerance
    f = diff_snapshots(base, _fake_snap(violations=1))
    assert [x["rule"] for x in f] == ["zero_to_nonzero"]
    # shed growth beyond slack
    f = diff_snapshots(_fake_snap(shed=10), _fake_snap(shed=20))
    assert [x["rule"] for x in f] == ["count"]
    assert diff_snapshots(_fake_snap(shed=10), _fake_snap(shed=12)) == []
    # hit-ratio collapse
    f = diff_snapshots(base, _fake_snap(hit=0.1))
    assert [x["rule"] for x in f] == ["hit_ratio"]
    # a latency histogram vanishing from the export is itself a failure
    gone = _fake_snap()
    gone["histograms"] = {}
    assert [x["rule"] for x in diff_snapshots(base, gone)] == ["missing"]
    # the injected-regression self check trips both rule families
    rules = {x["rule"] for x in diff_snapshots(base,
                                               inject_regression(base))}
    assert {"latency", "zero_to_nonzero"} <= rules
    assert "regression" in format_findings(f)
