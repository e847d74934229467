#!/usr/bin/env python3
"""Find a cell's knee once: the highest fixed Poisson rate at which the
queue does not grow over a window.

    python chipbench/sweep.py --workload <name> --seed <n> --seconds 10 \
        --windows 2 --rates 100 200 400

One process makes the cell's set-up (``run.Cell``) with a query set drawn
as the cell draws it, at the largest rate, and warms every program that
set can use; then it serves ``--windows`` open-loop windows per rate, in
ascending order, each with ``rate * seconds`` of those queries in another
seeded order.  Each window drains before the next, and counts the
programs built in it, so a rate inherits neither queue nor programs from
the one before.  Every window prints one JSON line.  A rate is sustained
when, in every window, the queue at the window's close holds at most one
full batch of due, undispatched queries, and the mean wait of the last
third of the window's queries is within one mean batch service time of
the first third's.  The knee goes into the traffic file by hand, as a
number; the benchmark itself never searches.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def sweep(args) -> int:
    import jax

    import gen
    import run
    import traffic

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        run.say("the sweep measures the chip; no TPU found")
        return 2
    rates = sorted(args.rates)
    c = run.load_cell(args.workload)
    c["seconds"] = args.seconds
    c["traffic"]["arrivals"]["rate_qps"] = rates[-1]
    if not args.rehearse:
        run.compile_cache()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(secs)
        if name == run.COMPILE_EVENT else None)
    cell = run.Cell(c, args.seed, args.rehearse)
    cell.warm_up()
    fixed = c["config"]["corpus"]["collection_seed"]
    pool = cell.queries
    mb = cell.spec.online.max_batch
    close_ms = args.seconds * 1e3
    for rate in rates:
        for w in range(args.windows):
            seed = args.seed + w
            cell.due = traffic.poisson_due_ms(rate, args.seconds, fixed, seed)
            pick = gen.rng_for(seed, 6).permutation(
                len(pool.terms))[:len(cell.due)]
            cell.queries = type(pool)(pool.terms[pick], pool.mask[pick],
                                      pool.topic[pick], pool.lengths[pick])
            n_warm = len(compiles)
            rec = run.run_window(cell, c["traffic"]["drain_s"], set())
            print(json.dumps(window_row(rate, w, rec, close_ms, mb,
                                        len(compiles) - n_warm)), flush=True)
    return 0


def window_row(rate, w, rec, close_ms, mb, n_compiles) -> dict:
    due, disp = rec["due"], rec["dispatch"]
    wait = disp - due
    third = max(len(due) // 3, 1)
    serve = np.mean([b["serve_s"] for b in rec["batches"]]) * 1e3
    backlog = int(np.sum((due <= close_ms) & ~(disp <= close_ms)))
    growth = float(np.nanmean(wait[-third:]) - np.nanmean(wait[:third]))
    resp = rec["done"] - due
    return {"rate_qps": rate, "window": w, "offered": len(due),
            "answered": int(np.sum(~np.isnan(resp))),
            "p50_ms": float(np.nanpercentile(resp, 50)),
            "p95_ms": float(np.nanpercentile(resp, 95)),
            "backlog_at_close": backlog, "wait_growth_ms": growth,
            "serve_ms_mean": float(serve),
            "batch_mean": float(np.mean([len(b["rows"])
                                         for b in rec["batches"]])),
            "drain_s": rec["window_s"] - close_ms / 1e3,
            "compiles": n_compiles,
            "sustained": bool(backlog <= mb and growth <= serve)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="the tiny CPU size, for the benchmark's own tests")
    return sweep(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
