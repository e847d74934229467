"""The program's own spans in a JAX profiler trace, and what the per-layer
readers take from them.

``SearchSystem.serve`` marks itself with ``cascade.*`` spans on the
profiler's host plane (``repro.serving.telemetry.spans``): ``cascade.serve``
around each call, disjoint stage spans inside it, and ``cascade.sync``
around each blocking read of device results.  ``python.gc`` marks a garbage
collection where the run turned ``gc_spans`` on.  The spans share the
profiler's clock with the device ops, so the idle time of the chip can be
split by what the host was doing.

    python chipbench/hostspans.py <trace dir>

prints one JSON object for a traced run: the per-batch time of each span,
the device-idle time inside each, how much of each kernel's device time
lies inside its stage, how much of ``cascade.serve`` its stage spans cover,
and the longest idle gaps, each named by the span that holds it.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import devtrace as T  # noqa: E402

SERVE = "cascade.serve"
SYNC = "cascade.sync"
GC = "python.gc"
# kernel (device op name) -> the stage span its launches lie in
KERNEL_STAGE = {"blockmax_score_batched": "cascade.stage1",
                "impact_accumulate_batched": "cascade.stage1",
                "qd_feature_gather_lanes": "cascade.stage2"}


def is_program_span(name: str) -> bool:
    return name.startswith("cascade.") or name == GC


def newest(trace_dir) -> str | None:
    """The newest ``.xplane.pb`` under ``trace_dir``, or None."""
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> list:
    """Host events ``[[name, start_ns, dur_ns], ...]`` of one ``.xplane.pb``:
    the program's spans and the harness's (``devtrace.HOST_SPANS``)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if is_program_span(ev.name) or ev.name in T.HOST_SPANS]


def host(ctx: dict) -> list:
    """The traced run's host spans: ``ctx["host"]`` if it is there, else
    read from the newest trace under ``chipbench/traces`` when that trace's
    ``window`` span is the run's (kept in ``ctx["host"]`` for the next
    reader); an empty list when there is no such trace."""
    if "host" not in ctx:
        path = newest(HERE / "traces")
        spans = load(path) if path else []
        win = [(s, s + d) for name, s, d in spans if name == "window"]
        ctx["host"] = spans if win == [tuple(ctx["window_ns"])] else []
    return ctx["host"]


def serve_spans(spans: list, lo: float, hi: float) -> tuple:
    """The ``cascade.serve`` intervals inside [lo, hi], and for every other
    program span name the durations (ns) of its spans inside one of them."""
    sv = sorted((s, s + d) for name, s, d in spans
                if name == SERVE and lo <= s and s + d <= hi)
    starts = [s for s, _ in sv]
    inside: dict = {}
    for name, s, d in spans:
        if name == SERVE or not is_program_span(name):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s + d <= sv[i][1]:
            inside.setdefault(name, []).append(d)
    return sv, inside


def per_batch(ctx: dict, name: str, count: bool = False):
    """Summed time (ms) of the ``name`` spans per ``cascade.serve`` in the
    window, or their number per call (``count``); None without spans."""
    sv, inside = serve_spans(host(ctx), *ctx["window_ns"])
    if not sv:
        return None
    ds = inside.get(name, [])
    return len(ds) / len(sv) if count else sum(ds) * 1e-6 / len(sv)


class Busy:
    """The union of device-op intervals, for busy time inside any span."""

    def __init__(self, events):
        self.iv = T.union((s, s + d) for _, s, d in events)
        self.ends = [e for _, e in self.iv]

    def ns(self, lo: float, hi: float) -> float:
        t, i = 0.0, bisect.bisect_right(self.ends, lo)
        while i < len(self.iv) and self.iv[i][0] < hi:
            s, e = self.iv[i]
            t += min(e, hi) - max(s, lo)
            i += 1
        return t


def serve_idle_ms(ctx: dict):
    """Mean over the window's ``cascade.serve`` spans of their length minus
    the device-busy time inside them (ms); None without spans."""
    sv, _ = serve_spans(host(ctx), *ctx["window_ns"])
    if not sv:
        return None
    busy = Busy(ctx["events"])
    return sum(e - s - busy.ns(s, e) for s, e in sv) * 1e-6 / len(sv)


def label(s: float, e: float, spans: list) -> str:
    """The span that holds the gap [s, e]: the shortest one covering at
    least half of it, else the one overlapping it most (``window`` left
    out; ``idle`` when none overlaps).  Among the harness's disjoint spans
    this is the one overlapping most; with the program's nested spans it is
    the innermost stage."""
    best, most, out = None, 0.0, "idle"
    for name, hs, hd in spans:
        if name == "window":
            continue
        ov = min(e, hs + hd) - max(s, hs)
        if ov <= 0:
            continue
        if 2 * ov >= e - s and (best is None or hd < best[0]):
            best = (hd, name)
        if ov > most:
            most, out = ov, name
    return best[1] if best else out


def idle_gaps(events, spans, lo: float, hi: float, n: int = 10) -> list:
    """The ``n`` longest gaps with no device op inside [lo, hi], longest
    first, each named by :func:`label`: [[label, seconds], ...]."""
    gaps, t = [], lo
    for s, e in T.clip(T.union((s, s + d) for _, s, d in events), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return [[label(s, e, spans), (e - s) * 1e-9]
            for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]]


def share_inside(events, names, intervals) -> float | None:
    """Share of the device time of the ops named ``names`` (by prefix) that
    lies inside the union of ``intervals``; None when they never ran."""
    evs = T.kernel_events(events, names)
    total = sum(d for _, _, d in evs)
    if not total:
        return None
    cover = Busy([["", s, e - s] for s, e in intervals])
    return sum(cover.ns(s, s + d) for _, s, d in evs) / total


def report(trace_dir) -> dict:
    """Everything the module docstring lists, for one traced run."""
    tred = T.load(str(trace_dir))
    spans = load(newest(trace_dir))
    lo, hi = T.window(spans)
    plane = sorted(tred["device"])[0]
    events = [ev for ev in tred["device"][plane] if lo <= ev[1] <= hi]
    sv, _ = serve_spans(spans, lo, hi)
    if not sv:
        return {"batches": 0}
    busy = Busy(events)
    by_name: dict = {}
    for name, s, d in spans:
        if is_program_span(name) and lo <= s and s + d <= hi:
            row = by_name.setdefault(name, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += d
            row[2] += d - busy.ns(s, s + d)
    n = len(sv)
    out = {"batches": n, "window_s": (hi - lo) * 1e-9,
           "device_idle_s": (hi - lo - busy.ns(lo, hi)) * 1e-9,
           "spans": {name: {"per_batch": c / n, "ms_per_batch": t * 1e-6 / n,
                            "idle_ms_per_batch": i * 1e-6 / n}
                     for name, (c, t, i) in sorted(by_name.items())}}
    stages = {}
    for name, s, d in spans:
        if name.startswith("cascade.") and name not in (SERVE, SYNC):
            stages.setdefault(name, []).append((s, s + d))
    out["kernel_inside_stage"] = {
        k: share_inside(events, (k,), stages.get(st, []))
        for k, st in KERNEL_STAGE.items()}
    kids = Busy([["", s, e - s] for iv in stages.values() for s, e in iv])
    out["stages_cover_serve"] = sum(kids.ns(s, e) for s, e in sv) / sum(
        e - s for s, e in sv)
    out["idle_gaps"] = idle_gaps(events, spans, lo, hi)
    return out


if __name__ == "__main__":
    print(json.dumps(report(sys.argv[1]), indent=1))
