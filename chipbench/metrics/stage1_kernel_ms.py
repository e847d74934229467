"""Stage-1 engines: device time of the BMW (``blockmax_score``) and JASS
(``impact_accumulate``) kernels per served batch."""

import devtrace
from metrics import blockmax_score_roofline as bmw
from metrics import impact_accumulate_roofline as jass


def read(ctx):
    evs = devtrace.kernel_events(ctx["events"], bmw.NAMES + jass.NAMES)
    n = len(ctx["rec"]["batches"])
    if not evs or not n:
        return None
    return sum(d for _, _, d in evs) * 1e-6 / n
