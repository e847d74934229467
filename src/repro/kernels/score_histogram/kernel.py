"""Quantized-score histogram — the TPU-native top-k primitive for retrieval.

JASS scores are small integers (sum of ≤ L quantized impacts ≤ L·255), so
*exact* top-k selection over a shard's accumulator does not need a sort:
histogram the scores, scan the histogram from the top to find the k-th
score threshold, then take docs with score ≥ threshold.  The histogram is
the only O(N) pass, and on TPU it becomes — once again — a one-hot matmul:

    hist_tile = onesᵀ (1 × TILE_N) @ onehot(score_bin) (TILE_N × n_bins)

Grid steps accumulate partial histograms into a single VMEM block (the
output block index_map is constant, a standard Pallas reduction idiom).
The wrapper (`ops.py`) does the tiny (n_bins,) cumulative scan and the
final masked selection.  This replaces `jax.lax.top_k`'s O(N log N) sort
with O(N) streaming work — one of the beyond-paper optimizations evaluated
in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hist_kernel(scores_ref, hist_ref, *, n_bins: int, tile_n: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    s = scores_ref[0, :]
    live = (s >= 0).astype(jnp.float32)
    sb = jnp.clip(s, 0, n_bins - 1)
    onehot = (sb[:, None] == jax.lax.broadcasted_iota(jnp.int32, (1, n_bins), 1)
              ).astype(jnp.float32) * live[:, None]
    part = jax.lax.dot_general(jnp.ones((1, tile_n), jnp.float32), onehot,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    hist_ref[0, :] += part[0, :].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_bins", "tile_n", "interpret"))
def score_histogram(scores: jnp.ndarray, *, n_bins: int = 2048,
                    tile_n: int = 2048, interpret: bool) -> jnp.ndarray:
    """scores: (N,) int32 (N multiple of tile_n; pad with -1) -> (n_bins,)."""
    n = scores.shape[0]
    assert n % tile_n == 0
    kern = functools.partial(_hist_kernel, n_bins=n_bins, tile_n=tile_n)
    return pl.pallas_call(
        kern,
        grid=(n // tile_n,),
        in_specs=[pl.BlockSpec((1, tile_n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, n_bins), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n_bins), jnp.int32),
        interpret=interpret,
    )(scores.reshape(n // tile_n, tile_n))[0]
