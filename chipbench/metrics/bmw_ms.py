"""Stage-1 engines: host time per batch in ``cascade.bmw`` spans (the BMW
calls over every segment and their read-back, inside ``cascade.stage1``),
from the program's spans in the profiler trace, over every served batch;
None where the program marks no such span."""

from metrics.jass_ms import span_ms


def read(ctx):
    return span_ms(ctx, "cascade.bmw")
