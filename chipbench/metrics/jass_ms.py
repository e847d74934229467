"""Stage-1 engines: host time per batch in ``cascade.jass`` spans (the JASS
calls over every segment and their read-back, inside ``cascade.stage1``),
from the program's spans in the profiler trace, over every served batch;
None where the program marks no such span."""

import hostspans


def span_ms(ctx, name):
    """Summed time (ms) of the ``name`` spans per ``cascade.serve`` in the
    window; None when the window holds no such span."""
    sv, inside = hostspans.serve_spans(hostspans.host(ctx),
                                       *ctx["window_ns"])
    if not sv or name not in inside:
        return None
    return hostspans.per_batch(ctx, name)


def read(ctx):
    return span_ms(ctx, "cascade.jass")
