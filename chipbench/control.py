#!/usr/bin/env python3
"""The control of the correctness check: the reference computed in bfloat16
(per-posting scores, impacts and forest inputs; the configuration states
float32), put in the program's place on a cell's own inputs, and compared
with the float32 reference by the numbers ``run.py`` compares.

    python chipbench/control.py --workload <name> --seeds 1 2 3

It needs no chip (the reference is host numpy) and prints one JSON line per
seed with each number beside its limit.  The check's limits must sit below
what the control reads: a later PR that stored scores in bfloat16 would
fail them.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run
import reference as R


def control_answers(inp: run.Inputs, low: R.Reference) -> dict:
    """What a bfloat16 cascade would serve for the sampled queries."""
    q = inp.queries
    qids = np.asarray(sorted(inp.sample))
    is_jass, k, rho, _, _ = low.route(inp.s0_parts, inp.s0_edges,
                                      q.terms[qids], q.mask[qids], inp.sched)
    out = {}
    for i, qid in enumerate(qids):
        terms, mask = q.terms[qid], q.mask[qid]
        if is_jass[i]:
            ids, sc = low.jass_list(terms, mask, int(rho[i]), inp.k_serve)
        else:
            acc = low.bm25_acc(terms, mask)
            ids = R.topk_ties(acc, inp.k_serve)
            sc = acc[ids]
        used = int(min(k[i], inp.k_serve))
        ltr = low.ltr_scores(inp.s2_parts, inp.s2_edges, terms, mask,
                            int(q.topic[qid]), ids[:used])
        order = np.lexsort((np.arange(used), -ltr))[:inp.t_final]
        final = np.full(inp.t_final, -1, np.int64)
        final[:len(order)] = ids[:used][order]
        out[int(qid)] = {"engine": "jass" if is_jass[i] else "bmw",
                         "ids": ids, "scores": sc, "topk": ids,
                         "final": final, "used": used}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    c = run.load_cell(args.workload)
    c["seconds"] = json.loads((run.ROOT / "BENCHMARK.json").read_text())[
        "run_seconds"]
    limits = c["traffic"]["check"]["limits"]
    failed_all = True
    for seed in args.seeds:
        inp = run.Inputs(c, seed, args.rehearse)
        low = R.Reference(inp.corpus, inp.spec.index.stop_k,
                          precision="bfloat16")
        numbers = run.compare(inp, control_answers(inp, low), inp.ref)
        chk = run.checks(numbers, limits)
        fails = [n for n, v in chk.items() if v["value"] > v["limit"]]
        failed_all &= bool(fails)
        print(json.dumps({"seed": seed, "control_fails": fails,
                          "checks": chk}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
