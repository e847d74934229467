"""Stage-2 featurize and forest: host time per batch in ``cascade.stage2``
spans (``stage2_afford``, the lane budget, the batched re-rank and its
read-back, the skip rows), from the program's spans in the profiler
trace."""

import hostspans


def read(ctx):
    return hostspans.per_batch(ctx, "cascade.stage2")
