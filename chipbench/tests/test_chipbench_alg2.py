"""The Algorithm-2 cell on the CPU at the rehearsal size: Stage-0 routes
queries to both engines, every program is built in the warm-up, the served
JASS lists pass the reference, and a JASS list that is wrong, or right but
with its ties in the other order, fails the check.  Also the two engine
span readers on a hand-made run."""

import json
import re

import numpy as np
import pytest

import run
from metrics import bmw_ms, jass_ms

CELL = "cw09b.mq09.alg2"
ARGS = ["--workload", CELL, "--seed", "4000000013", "--seconds", "2",
        "--trace", "0", "--rehearse"]


def last_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def test_rehearsal_routes_to_both_engines(capsys, monkeypatch):
    real = run.run_window
    recs = []

    def keep(*a, **kw):
        recs.append(real(*a, **kw))
        return recs[-1]
    monkeypatch.setattr(run, "run_window", keep)
    assert run.main(ARGS) == 0
    out, err = capsys.readouterr()
    line = last_line(out)
    assert re.search(r"window closed after [0-9.]+s: [0-9]+ batches, "
                     r"0 compiles", err)
    assert line["correct"] is True and line["failed"] == 0
    rec, = recs
    jass = sum(b["jass"] for b in rec["batches"])
    bmw = sum(b["bmw"] for b in rec["batches"])
    assert jass > 0 and bmw > 0
    engines = [a.get("engine") for a in rec["answers"].values()]
    assert "jass" in engines and "bmw" in engines


def _break_jass(monkeypatch):
    """JASS returns its top-1's neighbour doc in place of the top-1."""
    from repro.serving import system
    real = system.saat_serve

    def broken(*a, **kw):
        res = real(*a, **kw)
        ids = res.topk_docs
        return res._replace(topk_docs=ids.at[:, 0].set(
            (ids[:, 0] + 1) % kw["n_docs"]))
    monkeypatch.setattr(system, "saat_serve", broken)


def _break_jass_ties(monkeypatch):
    """JASS lists keep their scores but put the higher doc id first among
    equal scores."""
    from repro.serving import system
    real = system.saat_serve

    def broken(*a, **kw):
        res = real(*a, **kw)
        sc, ids = np.asarray(res.topk_scores), np.asarray(res.topk_docs)
        order = np.lexsort((-ids, -sc), axis=1)
        return res._replace(topk_docs=np.take_along_axis(ids, order, 1))
    monkeypatch.setattr(system, "saat_serve", broken)


@pytest.mark.parametrize("fault", [_break_jass, _break_jass_ties],
                         ids=["jass_answer", "jass_tie_order"])
def test_broken_jass_is_not_correct(fault, capsys, monkeypatch):
    fault(monkeypatch)
    assert run.main(ARGS) == 0
    line = last_line(capsys.readouterr().out)
    assert line["correct"] is False
    chk = line["checks"]["jass_mismatch"]
    assert chk["value"] > chk["limit"]


# two served batches: the first runs both engines, the second BMW alone
HOST = [["window", 0, 1000],
        ["cascade.serve", 100, 400], ["cascade.stage1", 150, 200],
        ["cascade.jass", 160, 80], ["cascade.sync", 220, 15],
        ["cascade.bmw", 250, 90], ["cascade.sync", 320, 15],
        ["cascade.serve", 520, 400], ["cascade.stage1", 560, 100],
        ["cascade.bmw", 570, 60], ["cascade.sync", 610, 15]]


def test_engine_span_readers_on_a_hand_made_run():
    ctx = {"host": HOST, "events": [], "window_ns": (0, 1000)}
    assert jass_ms.read(ctx) == pytest.approx(80 / 2 * 1e-6)
    assert bmw_ms.read(ctx) == pytest.approx((90 + 60) / 2 * 1e-6)
    # a window with no JASS span, and a program with no engine spans (the
    # parent's), read nothing
    no_jass = {"host": [ev for ev in HOST if ev[0] != "cascade.jass"],
               "events": [], "window_ns": (0, 1000)}
    assert jass_ms.read(no_jass) is None
    assert bmw_ms.read(no_jass) == pytest.approx((90 + 60) / 2 * 1e-6)
    bare = {"host": [ev for ev in HOST
                     if ev[0] not in ("cascade.jass", "cascade.bmw")],
            "events": [], "window_ns": (0, 1000)}
    assert jass_ms.read(bare) is None and bmw_ms.read(bare) is None
