"""The shift that aligns device ops with the program's host spans, found on
hand-made runs shaped like served batches, and the four readers of
``SearchSystem.serve``'s host work that rest on the spans and the shift."""

import pytest

import clockfit as C
import hostspans as H
import repro.serving.telemetry.spans  # noqa: F401  (marks every collection)
from metrics import account_ms, dispatch_ms, gc_ms, sync_idle_ms

US = 1_000
N_BATCHES = 5


def batch(t0):
    """One call at ``t0`` (ns): its host spans, and its device ops on the
    host spans' clock.  Each launch's first op starts 75-120 us after its
    dispatch starts, and its last op ends 10 us before its read-back ends."""
    def at(name, s, d):
        return [name, t0 + s * US, d * US]
    host = [at("serve", -5, 1010), at("cascade.serve", 0, 1000),
            at("cascade.stage0", 10, 190), at("cascade.dispatch", 20, 60),
            at("cascade.sync", 100, 85),
            at("cascade.account", 200, 60),
            at("cascade.stage1", 260, 360), at("cascade.bmw", 270, 340),
            at("cascade.dispatch", 280, 100), at("cascade.sync", 390, 185),
            at("cascade.account", 580, 20),
            at("cascade.stage2", 620, 280), at("cascade.account", 625, 15),
            at("cascade.dispatch", 645, 55), at("cascade.sync", 705, 105),
            at("cascade.account", 900, 90), at("python.gc", 920, 30),
            at("python.gc", 1100, 20)]
    ops = [at("fusion.1", 120, 40), at("copy.2", 163, 12),
           at("blockmax_score_batched.3", 400, 100),
           at("sort.4", 505, 60),
           at("qd_feature_gather_lanes.5", 720, 80)]
    return host, ops


def run(shift_ns, program=True):
    """A ctx of ``N_BATCHES`` calls whose device ops were recorded on a
    clock ``shift_ns`` behind the host spans' (so adding ``shift_ns``
    aligns them); ``program=False`` keeps the harness's spans alone."""
    host, ops = [["window", 0, 20_000 * US]], []
    for k in range(N_BATCHES):
        h, o = batch(2_000 * US + k * 1_500 * US)
        host += h
        ops += o
    if not program:
        host = [ev for ev in host if not H.is_program_span(ev[0])]
    return {"host": host, "events": [[n, s - shift_ns, d] for n, s, d in ops],
            "window_ns": (0, 20_000 * US)}


@pytest.mark.parametrize("shift_us", [1_000, -1_000, 0])
def test_offset_recovers_a_known_shift(shift_us):
    got = C.offset_ns(run(shift_us * US))
    assert abs(got - shift_us * US) <= 20 * US


def test_offset_reads_nothing_without_dispatch_spans():
    ctx = run(1_000 * US)
    ctx["host"] = [ev for ev in ctx["host"] if ev[0] != "cascade.dispatch"]
    assert C.offset_ns(ctx) is None
    assert sync_idle_ms.read(ctx) is None
    assert C.offset_ns(run(1_000 * US, program=False)) is None


def test_readers_on_a_hand_made_run():
    ctx = run(1_000 * US)
    assert dispatch_ms.read(ctx) == pytest.approx((60 + 100 + 55) * 1e-3)
    assert account_ms.read(ctx) == pytest.approx((60 + 20 + 15 + 90) * 1e-3)
    # each read-back waits (85 - 52) + (185 - 160) + (105 - 80) us idle
    assert sync_idle_ms.read(ctx) == pytest.approx(83e-3)
    # one collection in the call and one between calls
    assert gc_ms.read(ctx) == pytest.approx(50e-3)
    bare = run(1_000 * US, program=False)
    for m in (dispatch_ms, account_ms, sync_idle_ms, gc_ms):
        assert m.read(bare) is None


def test_idle_split_and_kernel_shares_on_the_shifted_clock():
    ctx = run(1_000 * US)
    lo, hi = ctx["window_ns"]
    moved = C.shifted(ctx["events"], C.offset_ns(ctx))
    split = C.idle_by_span(ctx["host"], moved, lo, hi)
    assert sum(split.values()) == pytest.approx(
        H.serve_idle_ms(dict(ctx, events=moved)))
    assert split["cascade.sync"] == pytest.approx(83e-3)
    assert split["python.gc"] == pytest.approx(30e-3)
    # Stage-0's 10 us before its dispatch; the idle time between the
    # stage's and the engine span's starts
    assert split["cascade.stage0"] == pytest.approx(
        (10 + 20 + 15) * 1e-3)
    assert split["cascade.serve"] == pytest.approx((10 + 10) * 1e-3)
    before = C.kernel_shares(ctx["events"], [], ctx["host"])
    after = C.kernel_shares(moved, [], ctx["host"])
    assert before["blockmax_score_batched"] < 0.95
    assert after["blockmax_score_batched"] == 1.0
    assert after["qd_feature_gather_lanes"] == 1.0
    assert after["impact_accumulate_batched"] is None
