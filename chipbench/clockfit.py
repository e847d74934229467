"""One shift that puts a trace's device ops on the clock of the program's
host spans, and the idle time of ``SearchSystem.serve`` split on that
clock.

The profiler stamps host spans and device ops with clocks that disagree by
about a millisecond, so a span shorter than that cannot be split into busy
and idle time as recorded.  Causality fixes the shift from the program's
own spans: a ``cascade.dispatch`` enqueues device work and the
``cascade.sync`` after it waits for that work.  On one clock, no device op
of a launch starts before its dispatch starts, and the device work a sync
waits for ends before the sync ends.  :func:`fit` scans shifts over
±5 ms in steps of 10 µs and keeps the one that ends the most syncs just
after a busy run (the device not busy at the sync's end, and its last
busy run of the launch ending at most ``TIGHT_NS`` before it) while
starting the fewest device ops outside the launch windows
[dispatch start, sync end]; among equal scores the latest, which puts each
launch's work as close to its read-back as causality allows.

    python chipbench/clockfit.py <trace dir>

prints one JSON object for a traced run: the shift, each kernel's share of
device time inside its stage before and after the shift, ``cascade.serve``'s
chip-idle time per call split by the innermost span holding it (on the
shifted clock; ``cascade.serve`` itself is time outside every span below
it), the longest idle gaps named by ``hostspans.label``'s rule, and the
values of the per-layer readers built on the spans and the shift.
"""

from __future__ import annotations

import bisect
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import devtrace as T  # noqa: E402
import hostspans as H  # noqa: E402

DISPATCH = "cascade.dispatch"
STAGE2 = "cascade.stage2"
SPAN_NS = 5_000_000
STEP_NS = 10_000
# how long after its launch's last device op a read-back may end and count
# as ending just after it: the copy to the host and the wake-up
TIGHT_NS = 100_000
# the Stage-2 program's name in its ops' HLO op names and module
RERANK_SCOPE = "_rerank_program"


def serve_children(spans: list, lo: float, hi: float) -> tuple:
    """The ``cascade.serve`` intervals inside [lo, hi], and for each the
    other program spans inside it as (name, start, end), by start."""
    sv, _ = H.serve_spans(spans, lo, hi)
    starts = [s for s, _ in sv]
    kids: list = [[] for _ in sv]
    for name, s, d in spans:
        if name == H.SERVE or not H.is_program_span(name):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s + d <= sv[i][1]:
            kids[i].append((name, s, s + d))
    return sv, [sorted(k, key=lambda x: x[1]) for k in kids]


def launches(spans: list, lo: float, hi: float) -> list:
    """(dispatch start, sync end) of each read-back inside a
    ``cascade.serve`` in [lo, hi] with a ``cascade.dispatch`` after the
    previous read-back of its call: the latest such dispatch is its
    launch."""
    out = []
    for _, kids in zip(*serve_children(spans, lo, hi)):
        start = None
        for name, s, e in kids:
            if name == DISPATCH:
                start = s
            elif name == H.SYNC and start is not None:
                out.append((start, e))
                start = None
    return out


def fit(spans: list, events: list, lo: float, hi: float):
    """The shift (ns) to add to ``events`` to put them on ``spans``'
    clock, or None without a launch or a device op (see the module
    docstring)."""
    wins = np.asarray(sorted(launches(spans, lo, hi)), np.float64)
    if not len(wins) or not events:
        return None
    starts = np.sort(np.asarray([s for _, s, _ in events], np.float64))
    busy = np.asarray(T.union((s, s + d) for _, s, d in events), np.float64)
    deltas = np.arange(-SPAN_NS, SPAN_NS + STEP_NS / 2, STEP_NS)
    best, best_score = None, None
    for delta in deltas:
        # device ops starting inside a launch window (windows are disjoint)
        inside = (np.searchsorted(starts, wins[:, 1] - delta, "right")
                  - np.searchsorted(starts, wins[:, 0] - delta, "left"))
        outside = len(starts) - int(inside.sum())
        # the last busy run starting before each sync end, shifted
        j = np.searchsorted(busy[:, 0], wins[:, 1] - delta, "right") - 1
        end = np.where(j >= 0, busy[np.maximum(j, 0), 1] + delta, -np.inf)
        tight = int(np.sum((end <= wins[:, 1])
                           & (wins[:, 1] - end <= TIGHT_NS)
                           & (end >= wins[:, 0])))
        score = tight - outside
        if best_score is None or score >= best_score:
            best, best_score = float(delta), score
    return best


def offset_ns(ctx: dict):
    """The run's shift (ns) for its device events (``ctx["events"]``) onto
    the program spans' clock, kept in ``ctx``; None without program spans
    or a ``cascade.dispatch``, as in a program that marks none."""
    if "clock_offset_ns" not in ctx:
        ctx["clock_offset_ns"] = fit(H.host(ctx), ctx["events"],
                                     *ctx["window_ns"])
    return ctx["clock_offset_ns"]


def shifted(events: list, delta: float) -> list:
    return [[name, s + delta, d] for name, s, d in events]


def sync_idle_ms(ctx: dict):
    """Chip-idle time inside the ``cascade.sync`` spans per
    ``cascade.serve``, device events shifted by :func:`offset_ns`; None
    without the shift."""
    delta = offset_ns(ctx)
    if delta is None:
        return None
    sv, kids = serve_children(H.host(ctx), *ctx["window_ns"])
    busy = H.Busy(shifted(ctx["events"], delta))
    idle = sum(e - s - busy.ns(s, e)
               for ks in kids for name, s, e in ks if name == H.SYNC)
    return idle * 1e-6 / len(sv)


def idle_by_span(spans: list, events: list, lo: float, hi: float) -> dict:
    """Chip-idle time (ms per ``cascade.serve``) inside the window's
    ``cascade.serve`` spans, each idle stretch charged to the innermost
    program span holding it (the latest started of those holding it);
    ``cascade.serve`` for time outside every span below it."""
    sv, kids = serve_children(spans, lo, hi)
    busy = H.Busy(events)
    out: dict = {}
    for (s0, e0), ks in zip(sv, kids):
        cuts = sorted({s0, e0} | {t for _, s, e in ks for t in (s, e)
                                  if s0 < t < e0})
        for a, b in zip(cuts, cuts[1:]):
            idle = (b - a) - busy.ns(a, b)
            if idle <= 0:
                continue
            holders = [(s, name) for name, s, e in ks if s <= a and b <= e]
            name = max(holders)[1] if holders else H.SERVE
            out[name] = out.get(name, 0.0) + idle
    return {k: v * 1e-6 / len(sv) for k, v in sorted(out.items())} \
        if sv else {}


def kernel_shares(events: list, rerank: list, spans: list) -> dict:
    """Share of each kernel's device time inside its stage span
    (``hostspans.KERNEL_STAGE``), and of the Stage-2 program's
    (``rerank``, its ops) inside ``cascade.stage2``."""
    stages: dict = {}
    for name, s, d in spans:
        stages.setdefault(name, []).append((s, s + d))
    out = {k: H.share_inside(events, (k,), stages.get(st, []))
           for k, st in H.KERNEL_STAGE.items()}
    out["jit_" + RERANK_SCOPE] = H.share_inside(rerank, ("",),
                                                stages.get(STAGE2, []))
    return out


def report(trace_dir) -> dict:
    """Everything the module docstring lists, for one traced run."""
    import hloscope
    from metrics import account_ms, dispatch_ms, gc_ms, sync_idle_ms as si

    path = H.newest(trace_dir)
    spans = H.load(path)
    lo, hi = T.window(spans)
    tred = T.load(str(trace_dir))
    plane = sorted(tred["device"])[0]
    events = [ev for ev in tred["device"][plane] if lo <= ev[1] <= hi]
    rerank = hloscope.scoped_ops(hloscope.load(path), RERANK_SCOPE, lo, hi)
    ctx = {"host": spans, "events": events, "window_ns": (lo, hi)}
    delta = offset_ns(ctx)
    out = {"offset_ns": delta,
           "kernel_inside_stage": kernel_shares(events, rerank, spans)}
    if delta is None:
        return out
    moved = shifted(events, delta)
    out["kernel_inside_stage_shifted"] = kernel_shares(
        moved, shifted(rerank, delta), spans)
    split = idle_by_span(spans, moved, lo, hi)
    out["serve_idle_ms"] = sum(split.values())
    out["serve_idle_ms_by_span"] = split
    out["idle_gaps"] = H.idle_gaps(moved, spans, lo, hi)
    out["metrics"] = {"dispatch_ms": dispatch_ms.read(ctx),
                      "account_ms": account_ms.read(ctx),
                      "sync_idle_ms": si.read(ctx),
                      "gc_ms": gc_ms.per_batch(ctx)}
    return out


if __name__ == "__main__":
    print(json.dumps(report(sys.argv[1]), indent=1))
