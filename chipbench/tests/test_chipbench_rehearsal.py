"""A whole run on the CPU at the rehearsal size: the result line, the
correctness check against the reference, the check failing when the timed
path is broken underneath, and the bfloat16 control failing it."""

import dataclasses
import json
import re

import numpy as np
import pytest

import control
import run
import sweep

CELL = "cw09b.mq09.bmw"
ARGS = ["--workload", CELL, "--seed", "4000000007", "--seconds", "2",
        "--trace", "0", "--rehearse"]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_rehearsal_line(capsys):
    assert run.main(ARGS) == 0
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    # every program the window uses was built in the warm-up
    assert re.search(r"window closed after [0-9.]+s: [0-9]+ batches, "
                     r"0 compiles", err)
    assert list(line) == KEYS
    assert line["correct"] is True
    assert line["metrics"] == {}            # no device metric off the chip
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["checks"]) == {"route_mismatch", "jass_mismatch",
                                   "bmw_gap", "final_gap"}


def test_no_chip_no_result(capsys):
    assert run.main(ARGS[:-1]) == 2
    assert capsys.readouterr().out == ""


def _wrap_ids(module, name, monkeypatch):
    """Replace the top-1 id an engine returns with its neighbour doc."""
    real = getattr(module, name)

    def broken(*a, **kw):
        res = real(*a, **kw)
        ids = res.topk_docs
        return res._replace(topk_docs=ids.at[:, 0].set(
            (ids[:, 0] + 1) % kw["n_docs"]))
    monkeypatch.setattr(module, name, broken)


def _break_bmw(monkeypatch):
    from repro.serving import system
    _wrap_ids(system, "daat_serve", monkeypatch)


def _algorithm2(monkeypatch):
    """The cell's deployment with the paper's Algorithm-2 routing, which
    sends some queries to JASS (the cell itself routes none there)."""
    real = run.load_cell

    def load(name, *a, **kw):
        c = real(name, *a, **kw)
        c["config"]["overrides"]["routing"] = {"adapt_every": 0}
        return c
    monkeypatch.setattr(run, "load_cell", load)


def _break_jass(monkeypatch):
    from repro.serving import system
    _algorithm2(monkeypatch)
    _wrap_ids(system, "saat_serve", monkeypatch)


def _break_jass_ties(monkeypatch):
    """JASS lists keep their scores but put the higher doc id first among
    equal scores."""
    from repro.serving import system
    _algorithm2(monkeypatch)
    real = system.saat_serve

    def broken(*a, **kw):
        res = real(*a, **kw)
        sc, ids = np.asarray(res.topk_scores), np.asarray(res.topk_docs)
        order = np.lexsort((-ids, -sc), axis=1)
        return res._replace(topk_docs=np.take_along_axis(ids, order, 1))
    monkeypatch.setattr(system, "saat_serve", broken)


def _break_final(monkeypatch):
    from repro.serving import system
    real = system.rerank_batched

    def broken(*a, **kw):
        res = real(*a, **kw)
        return dataclasses.replace(res, final=res.final[:, ::-1].copy())
    monkeypatch.setattr(system, "rerank_batched", broken)


def _break_route(monkeypatch):
    """Algorithm 2 sends each query to the other engine."""
    from repro.core import hybrid
    real = hybrid.route_algorithm2

    def broken(*a):
        r = real(*a)
        return np.where(r == hybrid.ROUTE_BMW, hybrid.ROUTE_JASS,
                        hybrid.ROUTE_BMW)
    monkeypatch.setattr(hybrid, "route_algorithm2", broken)


@pytest.mark.parametrize("fault,number", [
    (_break_bmw, "bmw_gap"), (_break_jass, "jass_mismatch"),
    (_break_jass_ties, "jass_mismatch"), (_break_final, "final_gap"),
    (_break_route, "route_mismatch")],
    ids=["bmw_answer", "jass_answer", "jass_tie_order", "final_answer",
         "route"])
def test_broken_timed_path_is_not_correct(fault, number, capsys,
                                          monkeypatch):
    fault(monkeypatch)
    assert run.main(ARGS) == 0
    line = last_line(capsys)
    assert line["correct"] is False
    chk = line["checks"][number]
    assert chk["value"] > chk["limit"]


def test_bfloat16_control_fails(capsys):
    assert control.main(["--workload", CELL, "--seeds", "5", "6", "7",
                         "--rehearse"]) == 0
    for row in capsys.readouterr().out.strip().splitlines():
        assert json.loads(row)["control_fails"]


def test_sweep_in_rehearsal(capsys):
    assert sweep.main(["--workload", CELL, "--seed", "4000000011",
                       "--seconds", "2", "--windows", "2", "--rates", "8",
                       "4", "--rehearse"]) == 0
    rows = [json.loads(r) for r in capsys.readouterr().out.splitlines()]
    assert [(r["rate_qps"], r["window"]) for r in rows] == [
        (4, 0), (4, 1), (8, 0), (8, 1)]
    for r in rows:
        assert r["offered"] == r["answered"] == 2 * r["rate_qps"]
        assert r["compiles"] == 0
        assert r["sustained"] is True
