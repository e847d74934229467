"""Trace reduction: busy union, idle share, kernel time by name and
idle-gap attribution, on a hand-made trace and on a slice recorded on a
v5e chip."""

import gzip
import json
from pathlib import Path

import pytest

import devtrace as T
from metrics import blockmax_score_roofline as bmw
from metrics import impact_accumulate_roofline as jass
from metrics import qd_feature_gather_roofline as qd

DATA = Path(__file__).resolve().parent / "data"

# device ops (name, start ns, duration ns) and host spans in one window
EVENTS = [["fusion.1", 100, 50], ["blockmax_score_batched.3", 120, 80],
          ["fusion.2", 400, 100], ["impact_accumulate_batched.1", 700, 100]]
HOST = [["window", 0, 1000], ["serve", 90, 260], ["form_batch", 350, 40],
        ["serve", 395, 500], ["wait_arrival", 900, 100]]


def test_busy_union_and_idle_share():
    lo, hi = T.window(HOST)
    assert (lo, hi) == (0, 1000)
    # [100, 200] + [400, 500] + [700, 800]
    assert T.busy_ns(EVENTS, lo, hi) == 300
    assert T.busy_ns(EVENTS, 150, 450) == 50 + 50


def test_kernel_time_by_name():
    evs = T.kernel_events(EVENTS, bmw.NAMES + jass.NAMES)
    assert sum(d for _, _, d in evs) == 180
    assert T.top_ops(EVENTS, 1) == [["fusion", pytest.approx(150e-9)]]


def test_idle_gaps_are_labelled_by_host_span():
    # gaps [200, 400], [500, 700], [800, 1000] and [0, 100], longest first
    # (ties in time order), each named by the span overlapping it most
    gaps = T.idle_gaps(EVENTS, HOST, 0, 1000, n=4)
    assert gaps == [["serve", pytest.approx(200e-9)],
                    ["serve", pytest.approx(200e-9)],
                    ["wait_arrival", pytest.approx(200e-9)],
                    ["serve", pytest.approx(100e-9)]]
    assert T.idle_gaps(EVENTS, [["window", 0, 1000]], 0, 1000, n=1) == [
        ["idle", pytest.approx(200e-9)]]


def test_recorded_v5e_slice():
    with gzip.open(DATA / "v5e_trace_slice.json.gz", "rt") as f:
        t = json.load(f)
    lo, hi = T.window(t["host"])
    (plane, evs), = t["device"].items()
    busy = T.busy_ns(evs, lo, hi)
    assert 0 < busy <= hi - lo
    assert T.kernel_events(evs, bmw.NAMES + jass.NAMES + qd.NAMES)
    gaps = T.idle_gaps(evs, t["host"], lo, hi)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert sum(g[1] for g in gaps) <= (hi - lo - busy) * 1e-9 + 1e-12
