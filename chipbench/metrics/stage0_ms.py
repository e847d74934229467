"""Stage-0 predict and route: host time per batch in ``cascade.stage0``
spans (features, the stacked forest and its read-back, routing, modality),
from the program's spans in the profiler trace."""

import hostspans


def read(ctx):
    return hostspans.per_batch(ctx, "cascade.stage0")
