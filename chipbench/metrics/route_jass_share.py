"""Stage-0 routing: share of routed rows (pads included) sent to JASS/SAAT,
from the scheduler's ``jass``/``bmw`` counters."""


def read(ctx):
    b = ctx["rec"]["batches"]
    j = sum(x["jass"] for x in b)
    n = j + sum(x["bmw"] for x in b)
    return 100.0 * j / n if n else None
