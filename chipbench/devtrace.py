"""Reduction of a JAX profiler trace to device busy/idle time, kernel time
by name and idle gaps attributed to the harness's host spans.

``load`` reads the ``.xplane.pb`` the profiler wrote into plain event lists
(``{"device": {plane: [[name, start_ns, dur_ns], ...]}, "host": [...]}``);
everything else works on those lists, so a small recorded trace can be kept
as JSON and checked without a chip.
"""

from __future__ import annotations

import glob
import os

DEVICE_LINE = "XLA Ops"
HOST_SPANS = ("window", "serve", "form_batch", "wait_arrival")


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"device": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            evs = [[op_name(ev.name), float(ev.start_ns),
                    float(ev.duration_ns)]
                   for line in plane.lines if line.name == DEVICE_LINE
                   for ev in line.events]
            if evs:
                out["device"][plane.name] = evs
        elif plane.name.startswith("/host:"):
            out["host"] += [[ev.name, float(ev.start_ns),
                             float(ev.duration_ns)]
                            for line in plane.lines for ev in line.events
                            if ev.name in HOST_SPANS]
    return out


def op_name(text: str) -> str:
    """The HLO instruction name of a device op event (``%fusion.6 = f32[..]
    fusion(..)`` gives ``fusion.6``); a Pallas kernel's op carries its
    kernel function's name (``blockmax_score_batched.3``)."""
    if text.startswith("%"):
        return text[1:].split(" = ", 1)[0]
    return text


def union(intervals) -> list:
    """Merged [start, end] intervals of (start, end) pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_ns(events, lo: float, hi: float) -> float:
    """Length of the union of device-op intervals inside [lo, hi]."""
    return sum(e - s for s, e in clip(
        union((s, s + d) for _, s, d in events), lo, hi))


def kernel_events(events, names) -> list:
    """Events whose name starts with one of ``names``."""
    return [ev for ev in events if ev[0].startswith(tuple(names))]


def top_ops(events, n: int = 10) -> list:
    """[[op name, seconds], ...] of the ``n`` ops with the most device time
    (numbered instances of one op, ``fusion.12``, count as ``fusion``)."""
    tot: dict = {}
    for name, _, d in events:
        base = name.rsplit(".", 1)[0] if name.rsplit(".", 1)[-1].isdigit() \
            else name
        tot[base] = tot.get(base, 0.0) + d * 1e-9
    return sorted(([k, v] for k, v in tot.items()), key=lambda r: -r[1])[:n]


def idle_gaps(events, host, lo: float, hi: float, n: int = 10) -> list:
    """The ``n`` longest gaps with no device op inside [lo, hi], each
    labelled by the host span that overlaps it most (``idle`` when none
    does): [[label, seconds], ...]."""
    busy = clip(union((s, s + d) for _, s, d in events), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, label = 0.0, "idle"
        for name, hs, hd in host:
            if name == "window":
                continue
            ov = min(e, hs + hd) - max(s, hs)
            if ov > best:
                best, label = ov, name
        out.append([label, (e - s) * 1e-9])
    return out


def window(host) -> tuple:
    """(start, end) of the harness's ``window`` span."""
    (s, d), = [(s, d) for name, s, d in host if name == "window"]
    return s, s + d
