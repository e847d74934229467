"""``SearchSystem.serve``: chip-idle time per call inside its
``cascade.sync`` spans, with the device ops shifted onto the host spans'
clock by ``clockfit.offset_ns`` (fitted on the ``cascade.dispatch`` and
``cascade.sync`` spans): what the read-backs cost beyond the device work
they wait for.  None where the program marks no ``cascade.dispatch``."""

import clockfit


def read(ctx):
    return clockfit.sync_idle_ms(ctx)
