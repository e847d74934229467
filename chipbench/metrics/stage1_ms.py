"""Stage-1 engines: host time per batch in ``cascade.stage1`` spans (lane
budgets, every engine call per shard, the merge and its read-back), from
the program's spans in the profiler trace; the device's Stage-1 kernel
time (``stage1_kernel_ms``) lies inside it."""

import hostspans


def read(ctx):
    return hostspans.per_batch(ctx, "cascade.stage1")
