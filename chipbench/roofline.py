"""Roofline share of a kernel: the least time the chip could take for the
bytes the kernel's calls need, over the kernel's device time.

The bytes of a call are counted from shapes by the metric's own
``bytes_per_call``: the shard arrays the call must read once, plus its
inputs and outputs.  The kernels' essential arithmetic (one add or compare
per posting) is far below the chip's compute peak at these sizes, so the
bound is the memory one: least time = bytes / peak HBM bandwidth.
"""

from __future__ import annotations

import devtrace


def share(ctx, names, calls) -> float | None:
    """100 * (sum of call bytes / peak bandwidth) / kernel device time."""
    evs = devtrace.kernel_events(ctx["events"], names)
    busy = sum(d for _, _, d in evs) * 1e-9
    if not evs or busy <= 0 or not calls:
        return None
    return 100.0 * sum(calls) / ctx["peaks"]["hbm_bytes_per_s"] / busy
