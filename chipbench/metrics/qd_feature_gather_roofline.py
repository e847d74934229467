"""Kernel ``qd_feature_gather`` (Stage-2 per-(query, candidate) BM25 sum,
max and match count over the query's posting lanes): roofline share,
memory bound."""

import numpy as np

import roofline

# names the kernel's ops carry in the device trace
NAMES = ("qd_feature_gather_lanes",)


def bytes_per_call(shapes: dict, lanes: int, q: int) -> int:
    """The batch's real posting lanes in (int32 doc id + float32 score
    each), the (Q, C) candidate grid in and three (Q, C) float32 outputs."""
    return lanes * 8 + 4 * q * shapes["k_serve"] * 4


def read(ctx):
    shapes, qs = ctx["shapes"], ctx["queries"]
    calls = []
    for b in ctx["rec"]["batches"]:
        rows = np.asarray(b["rows"])
        need = (shapes["df"][qs.terms[rows]] * (qs.mask[rows] > 0)).sum()
        # pad rows repeat the batch's first query
        need += (b["q"] - len(rows)) * (
            shapes["df"][qs.terms[rows[0]]] * (qs.mask[rows[0]] > 0)).sum()
        calls.append(bytes_per_call(shapes, int(need), b["q"]))
    return roofline.share(ctx, NAMES, calls)
