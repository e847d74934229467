"""Python runtime: time per served batch in ``python.gc`` spans (one per
garbage collection) inside the harness's ``window`` span.  A collection
anywhere in the loop, in ``SearchSystem.serve`` or between calls, delays
the next dispatch.  0 where no collection ran in the window; None where
the program does not mark every collection (no ``gc.callbacks`` hook of
``repro.serving.telemetry.spans`` in this process) or the run has no
``cascade.serve`` spans."""

import gc

import hostspans

PROGRAM = "repro.serving.telemetry.spans"


def per_batch(ctx):
    """Summed ``python.gc`` time (ms) inside the window per
    ``cascade.serve`` in it; None without ``cascade.serve`` spans."""
    lo, hi = ctx["window_ns"]
    spans = hostspans.host(ctx)
    sv, _ = hostspans.serve_spans(spans, lo, hi)
    if not sv:
        return None
    t = sum(d for name, s, d in spans
            if name == hostspans.GC and lo <= s and s + d <= hi)
    return t * 1e-6 / len(sv)


def read(ctx):
    if not any(getattr(cb, "__module__", None) == PROGRAM
               for cb in gc.callbacks):
        return None
    return per_batch(ctx)
