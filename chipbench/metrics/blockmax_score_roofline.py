"""Kernel ``blockmax_score`` (BMW/DAAT exact pass over the bucketed shard
mirror): roofline share, memory bound."""

import roofline

# names the kernel's ops carry in the device trace
NAMES = ("blockmax_score_batched",)


def bytes_per_call(shapes: dict, q: int) -> int:
    """Mirror read once (tile-local doc ids, term ids, float32 scores), the
    per-doc survival mask in, the (Q, docs) float32 accumulator out, and
    the query terms in."""
    docs = shapes["n_tiles"] * shapes["tile_d"]
    mirror = 3 * shapes["n_tiles"] * shapes["tile_cap"] * 4
    return mirror + 2 * q * docs * 4 + q * shapes["slots"] * 4


def read(ctx):
    calls = [bytes_per_call(ctx["shapes"], b["bmw"])
             for b in ctx["rec"]["batches"] if b["bmw"]]
    return roofline.share(ctx, NAMES, calls)
