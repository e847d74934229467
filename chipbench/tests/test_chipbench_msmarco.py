"""The MS MARCO passage cell on the CPU at the rehearsal size: Algorithm 2
at depth 1,000 builds every program in the warm-up and passes the
reference; and the deep top-k select's readers on hand-made traces."""

import json
import re

import pytest

import run
from metrics import topk_select_ms, topk_select_roofline

CELL = "msmarco.dev.steady"
ARGS = ["--workload", CELL, "--seed", "3000000019", "--seconds", "2",
        "--trace", "0", "--rehearse"]


def test_rehearsal_at_depth_1000(capsys, monkeypatch):
    real = run.run_window
    recs = []

    def keep(*a, **kw):
        recs.append(real(*a, **kw))
        return recs[-1]
    monkeypatch.setattr(run, "run_window", keep)
    assert run.main(ARGS) == 0
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert re.search(r"window closed after [0-9.]+s: [0-9]+ batches, "
                     r"0 compiles", err)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    rec, = recs
    assert sum(b["jass"] for b in rec["batches"]) > 0
    assert sum(b["bmw"] for b in rec["batches"]) > 0
    # Stage-1 answers 1,000 candidates a query, past one 128-doc tile
    assert {len(a["topk"]) for a in rec["answers"].values()} == {1000}


def test_topk_select_readers_on_a_hand_made_run():
    batches = [{"jass": 3, "bmw": 0}, {"jass": 0, "bmw": 5},
               {"jass": 2, "bmw": 1}]
    shapes = {"n_tiles": 2048, "tile_d": 128, "k_serve": 1000}
    ctx = {"rec": {"batches": batches}, "shapes": shapes,
           "peaks": {"hbm_bytes_per_s": 819e9},
           "select_events": [["sort.2", 0, 2e6], ["fusion.3", 0, 1e6]]}
    assert topk_select_ms.read(ctx) == pytest.approx(3.0 / 3)
    # JASS selects once a call, BMW twice: 3 + 2 + 2 * (5 + 1) rows
    rows = 3 + 2 + 2 * 6
    want = 100 * 4 * rows * (2048 * 128 + 10 * 1000) / 819e9 / 3e-3
    assert topk_select_roofline.read(ctx) == pytest.approx(want)
    ctx["select_events"] = []
    assert topk_select_ms.read(ctx) is None
    assert topk_select_roofline.read(ctx) is None


def test_topk_select_reads_nothing_without_this_runs_trace(tmp_path,
                                                           monkeypatch):
    """No trace of this run (the parent's program, or a stale trace): the
    readers return None and do not raise."""
    monkeypatch.setattr(topk_select_ms.hostspans, "HERE", tmp_path)
    ctx = {"rec": {"batches": [{"jass": 1, "bmw": 1}]},
           "window_ns": (0.0, 1.0),
           "shapes": {"n_tiles": 64, "tile_d": 128, "k_serve": 1000},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert topk_select_ms.read(ctx) is None
    assert topk_select_roofline.read(ctx) is None
