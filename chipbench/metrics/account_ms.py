"""``SearchSystem.serve``: host time per call in ``cascade.account`` spans,
the virtual-clock bookkeeping (cost-model times, the batch's stats), those
between the stages and those nested in a stage alike; None where the
program marks no such span."""

from metrics.jass_ms import span_ms


def read(ctx):
    return span_ms(ctx, "cascade.account")
