"""Stage-1 engines: device time per served batch of BMW's block-bounds
kernel (``bmw_block_bounds``, ``repro.kernels.block_bounds``: each query's
block upper bounds and candidate counts, summed from its terms' block-max
entries before phase 1).  None where no op of that name ran, as in a
program whose bounds are XLA gathers and scatter-adds."""

import devtrace

# the name the kernel's ops carry in the device trace
NAMES = ("bmw_block_bounds",)


def read(ctx):
    evs = devtrace.kernel_events(ctx["events"], NAMES)
    n = len(ctx["rec"]["batches"])
    if not evs or not n:
        return None
    return sum(d for _, _, d in evs) * 1e-6 / n
