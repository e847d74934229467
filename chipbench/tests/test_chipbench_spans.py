"""The program's spans in a trace: idle gaps named by the innermost span
that holds them, and the five span readers, on hand-made host lists and
on a trace the profiler wrote."""

import gzip
import json
from pathlib import Path

import jax
import pytest
from jax.profiler import TraceAnnotation

import devtrace as T
import hostspans as H
from metrics import (host_syncs, serve_idle_ms, stage0_ms, stage1_ms,
                     stage2_ms)

DATA = Path(__file__).resolve().parent / "data"

# device ops (name, start ns, duration ns) over two served batches
EVENTS = [["fusion.1", 120, 20], ["blockmax_score_batched.3", 150, 100],
          ["fusion.2", 330, 30], ["qd_feature_gather_lanes.1", 360, 20],
          ["blockmax_score_batched.3", 560, 60],
          ["qd_feature_gather_lanes.1", 700, 40]]
# the harness's spans with the program's inside them
HOST = [["window", 0, 1000], ["form_batch", 50, 40],
        ["serve", 100, 400], ["cascade.serve", 101, 398],
        ["cascade.stage0", 102, 40], ["cascade.sync", 110, 30],
        ["cascade.stage1", 142, 150], ["cascade.sync", 250, 40],
        ["python.gc", 255, 30],
        ["cascade.stage2", 292, 200], ["cascade.sync", 380, 10],
        ["serve", 520, 400], ["cascade.serve", 521, 398],
        ["cascade.stage0", 522, 30], ["cascade.sync", 530, 20],
        ["cascade.stage1", 552, 100], ["cascade.sync", 620, 30],
        ["cascade.stage2", 652, 200], ["cascade.sync", 740, 10]]


def test_gaps_are_named_by_the_innermost_span():
    # gaps, longest first: [740, 1000] after the second batch's Stage-2;
    # [380, 560] mostly in the first batch's Stage-2; [0, 120] mostly in
    # form_batch (no span holds half of it); [250, 330] in a read-back;
    # [620, 700] in Stage-2; [140, 150] in Stage-1
    gaps = H.idle_gaps(EVENTS, HOST, 0, 1000, n=10)
    assert [name for name, _ in gaps] == [
        "cascade.serve", "cascade.stage2", "form_batch", "cascade.sync",
        "cascade.stage2", "cascade.stage1"]
    assert [round(sec * 1e9) for _, sec in gaps] == [260, 180, 120, 80,
                                                     80, 10]
    in_gc = [["window", 0, 1000], ["cascade.stage1", 200, 300],
             ["python.gc", 240, 40]]
    assert H.idle_gaps([["fusion", 0, 240], ["fusion", 280, 720]],
                       in_gc, 0, 1000) == [["python.gc",
                                            pytest.approx(40e-9)]]


@pytest.mark.parametrize("source", ["hand-made", "v5e slice"])
def test_harness_spans_keep_their_labels(source):
    """With only the harness's disjoint spans, the innermost-span rule
    names every gap as ``devtrace.idle_gaps`` does."""
    if source == "hand-made":
        evs = EVENTS
        host = [ev for ev in HOST if not H.is_program_span(ev[0])]
    else:
        with gzip.open(DATA / "v5e_trace_slice.json.gz", "rt") as f:
            t = json.load(f)
        (_, evs), = t["device"].items()
        host = t["host"]
    lo, hi = T.window(host)
    assert H.idle_gaps(evs, host, lo, hi) == T.idle_gaps(evs, host, lo, hi)


def test_span_readers_on_a_hand_made_run():
    ctx = {"host": HOST, "events": EVENTS, "window_ns": (0, 1000)}
    assert stage0_ms.read(ctx) == pytest.approx((40 + 30) / 2 * 1e-6)
    assert stage1_ms.read(ctx) == pytest.approx((150 + 100) / 2 * 1e-6)
    assert stage2_ms.read(ctx) == pytest.approx((200 + 200) / 2 * 1e-6)
    assert host_syncs.read(ctx) == 3.0
    # busy inside [101, 499]: 120-140, 150-250, 330-380; inside [521, 919]:
    # 560-620, 700-740
    idle = ((398 - 20 - 100 - 50) + (398 - 60 - 40)) / 2
    assert serve_idle_ms.read(ctx) == pytest.approx(idle * 1e-6)
    # a run with no program spans (the parent's) reads nothing
    bare = {"host": [ev for ev in HOST if not H.is_program_span(ev[0])],
            "events": EVENTS, "window_ns": (0, 1000)}
    for m in (stage0_ms, stage1_ms, stage2_ms, serve_idle_ms, host_syncs):
        assert m.read(bare) is None


def test_readers_find_the_run_trace_by_its_window(tmp_path, monkeypatch):
    """Without ``ctx["host"]`` the readers load the newest trace under
    ``traces/`` and use it only when its ``window`` is the run's."""
    with jax.profiler.trace(str(tmp_path / "traces" / "cell")):
        with TraceAnnotation("window"):
            for _ in range(2):
                with TraceAnnotation("cascade.serve"):
                    with TraceAnnotation("cascade.stage0"):
                        with TraceAnnotation("cascade.sync"):
                            jax.numpy.ones(4).block_until_ready()
                    with TraceAnnotation("cascade.stage1"):
                        pass
    monkeypatch.setattr(H, "HERE", tmp_path)
    spans = H.load(H.newest(tmp_path / "traces"))
    lo, hi = T.window(spans)
    ctx = {"events": [], "window_ns": (lo, hi)}
    assert host_syncs.read(ctx) == 1.0
    assert stage0_ms.read(ctx) > 0 and stage2_ms.read(ctx) == 0.0
    assert serve_idle_ms.read(ctx) > 0
    assert [ev[0] for ev in ctx["host"]].count("cascade.serve") == 2
    assert host_syncs.read({"events": [], "window_ns": (lo, hi + 1)}) is None
