"""``SearchSystem.serve``: mean host-clock time of one call, from the call
to ``block_until_ready`` on its answers, over the batches served."""

import numpy as np


def read(ctx):
    b = ctx["rec"]["batches"]
    return float(np.mean([x["serve_s"] for x in b]) * 1e3) if b else None
