"""Kernel ``impact_accumulate`` (JASS/SAAT impact-ordered accumulation over
the bucketed shard mirror): roofline share, memory bound."""

import roofline

# names the kernel's ops carry in the device trace
NAMES = ("impact_accumulate_batched",)


def bytes_per_call(shapes: dict, q: int) -> int:
    """Mirror read once (tile-local doc ids, term ids, int32 impacts), the
    query terms and level cuts in, the (Q, docs) int32 accumulator out."""
    docs = shapes["n_tiles"] * shapes["tile_d"]
    mirror = 3 * shapes["n_tiles"] * shapes["tile_cap"] * 4
    return mirror + q * docs * 4 + q * (shapes["slots"] + 1) * 4


def read(ctx):
    calls = [bytes_per_call(ctx["shapes"], b["jass"])
             for b in ctx["rec"]["batches"] if b["jass"]]
    return roofline.share(ctx, NAMES, calls)
