"""``SearchSystem.serve``: host time per call in ``cascade.dispatch`` spans,
where the program builds a launch's arguments, copies them to the device
and enqueues its programs (Stage-0's features and forest, each engine
branch's segment calls and merge, Stage-2's re-rank program), each ending
before the read-back it feeds; None where the program marks no such
span."""

from metrics.jass_ms import span_ms


def read(ctx):
    return span_ms(ctx, "cascade.dispatch")
