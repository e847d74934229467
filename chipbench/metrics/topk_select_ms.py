"""Stage-1 engines: device time per served batch of the deep top-k select
(``deep_topk_select`` in ``repro.kernels.topk``: its level pass, its two
sorts and its member gathers), which ranks Stage-1 where ``k_serve`` is
deeper than one accumulator tile.  The select is XLA ops inside the
engines' programs, not a kernel: it is one jitted function, so each of its
ops carries ``jit(deep_topk_select)`` in the op name of its HLO metadata,
which ``hloscope`` reads from the traced run's own profile (as
``hostspans`` reads the program's spans).  None where no op of the select
ran."""

import hloscope
import hostspans

# the name every op of the select carries in its HLO op name
SCOPE = "deep_topk_select"


def select_events(ctx) -> list:
    """``[[op name, start_ns, dur_ns], ...]`` of the select's device ops in
    the window (kept in ``ctx``); empty when the newest trace is not this
    run's."""
    if "select_events" not in ctx:
        ctx["select_events"] = []
        if hostspans.host(ctx):
            space = hloscope.load(hostspans.newest(hostspans.HERE / "traces"))
            ctx["select_events"] = hloscope.scoped_ops(space, SCOPE,
                                                       *ctx["window_ns"])
    return ctx["select_events"]


def read(ctx):
    evs = select_events(ctx)
    n = len(ctx["rec"]["batches"])
    if not evs or not n:
        return None
    return sum(d for _, _, d in evs) * 1e-6 / n
