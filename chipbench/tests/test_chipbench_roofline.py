"""Roofline byte counts against a hand count for a tiny shard, and the
share they give over recorded kernel time."""

import numpy as np
import pytest

import roofline
from metrics import blockmax_score_roofline as bmw
from metrics import impact_accumulate_roofline as jass
from metrics import qd_feature_gather_roofline as qd

# 2 tile groups of 8 tiles, 128 docs a tile, 256 lanes a tile, 8 slots
SHAPES = {"n_tiles": 16, "tile_d": 128, "tile_cap": 256, "slots": 8,
          "k_serve": 128, "n_docs": 2000,
          "df": np.array([0, 10, 20, 30])}


def test_blockmax_bytes_by_hand():
    mirror = 3 * 16 * 256 * 4            # doc ids, term ids, f32 scores
    keep = out = 4 * (16 * 128) * 4       # (Q, docs) f32 mask in, acc out
    terms = 4 * 8 * 4
    assert bmw.bytes_per_call(SHAPES, 4) == mirror + keep + out + terms


def test_impact_accumulate_bytes_by_hand():
    mirror = 3 * 16 * 256 * 4
    out = 2 * (16 * 128) * 4
    inputs = 2 * (8 + 1) * 4              # terms and the level cut
    assert jass.bytes_per_call(SHAPES, 2) == mirror + out + inputs


def test_qd_feature_gather_bytes_by_hand():
    # 60 lanes (doc id + score), a (2, 128) candidate grid, 3 outputs
    assert qd.bytes_per_call(SHAPES, 60, 2) == 60 * 8 + 2 * 128 * 4 * 4


def test_share_is_least_time_over_kernel_time():
    ctx = {"events": [["blockmax_score_batched.2", 0, 1e6], ["fusion", 0, 5e6]],
           "peaks": {"hbm_bytes_per_s": 1e9}}
    # 1e5 bytes at 1 GB/s = 0.1 ms against 1 ms of kernel time
    assert roofline.share(ctx, bmw.NAMES, [1e5]) == pytest.approx(10.0)
    assert roofline.share(ctx, jass.NAMES, [1e5]) is None
