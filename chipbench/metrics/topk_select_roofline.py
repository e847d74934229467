"""The deep top-k select (``deep_topk_select``, ``repro.kernels.topk``):
roofline share, memory bound.  Its device time is that of every op the
select runs (``topk_select_ms``), so the share says how far its sorts and
gathers sit from one read of the accumulator."""

from metrics.topk_select_ms import select_events


def bytes_per_call(shapes: dict, q: int) -> int:
    """The (Q, docs) float32 or int32 accumulator read once, the ``8 k``
    group members in and the ``k`` (value, position) pairs out."""
    docs = shapes["n_tiles"] * shapes["tile_d"]
    k = shapes["k_serve"]
    return 4 * q * (docs + 8 * k + 2 * k)


def read(ctx):
    """100 * bytes / peak HBM bandwidth / the select's device time; BMW
    selects twice a call (phase 1's threshold and the final list), JASS
    once."""
    evs = select_events(ctx)
    busy = sum(d for _, _, d in evs) * 1e-9
    calls = [bytes_per_call(ctx["shapes"], b["jass"])
             for b in ctx["rec"]["batches"] if b["jass"]]
    calls += [bytes_per_call(ctx["shapes"], b["bmw"])
              for b in ctx["rec"]["batches"] if b["bmw"] for _ in range(2)]
    if not evs or busy <= 0 or not calls:
        return None
    return 100.0 * sum(calls) / ctx["peaks"]["hbm_bytes_per_s"] / busy
