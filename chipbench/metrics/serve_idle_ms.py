"""``SearchSystem.serve``: time per call in which the chip ran nothing —
the length of each ``cascade.serve`` span minus the union of device ops
inside it, averaged over the window's calls."""

import hostspans


def read(ctx):
    return hostspans.serve_idle_ms(ctx)
