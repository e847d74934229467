"""Stage-2: mean candidates re-ranked per answered query
(``PipelineResult.candidates_used``)."""

import numpy as np


def read(ctx):
    used = [u for x in ctx["rec"]["batches"] for u in x["used"]]
    return float(np.mean(used)) if used else None
