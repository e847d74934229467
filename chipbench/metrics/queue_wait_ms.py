"""Front door: mean wait from a query's due time to the dispatch of its
batch (harness clock), over every query dispatched in the run."""

import numpy as np


def read(ctx):
    rec = ctx["rec"]
    wait = rec["dispatch"] - rec["due"]
    wait = wait[~np.isnan(wait)]
    return float(wait.mean()) if len(wait) else None
