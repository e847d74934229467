"""``SearchSystem.serve``: blocking device-to-host reads per call, counted
as ``cascade.sync`` spans (``repro.serving.telemetry.spans.fetch``) inside
each ``cascade.serve`` span."""

import hostspans


def read(ctx):
    return hostspans.per_batch(ctx, "cascade.sync", count=True)
