"""Histogram-based exact top-k for integer (quantized) score accumulators."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.score_histogram.kernel import score_histogram
from repro.kernels.score_histogram.ref import score_histogram_ref


@functools.partial(jax.jit, static_argnames=("k", "n_bins", "interpret"))
def histogram_topk(scores: jnp.ndarray, *, k: int, n_bins: int = 2048,
                   interpret: bool):
    """Exact top-k of an int32 score vector via histogram thresholding.

    Returns (values, indices) like jax.lax.top_k (ties broken by index).
    Cost: one O(N) histogram pass + one O(N) selection pass, no sort.
    """
    n = scores.shape[0]
    tile = 2048 if n % 2048 == 0 else 512 if n % 512 == 0 else 1
    if tile == 1:
        hist = score_histogram_ref(scores, n_bins)
    else:
        hist = score_histogram(scores, n_bins=n_bins, tile_n=tile,
                               interpret=interpret)
    # threshold: smallest score t with count(score >= t) >= k
    ge = jnp.cumsum(hist[::-1])[::-1]          # ge[t] = #scores >= t
    t = jnp.argmin(jnp.where(ge >= k, jnp.arange(n_bins), n_bins)[::-1])
    t = n_bins - 1 - t                          # largest t with ge[t] >= k
    t = jnp.where(ge[0] < k, 0, t)
    # selection: strict > t always included; == t filled by index order
    key = jnp.where(scores > t, scores.astype(jnp.int64) + n_bins, 0)
    key = jnp.where(scores == t, scores.astype(jnp.int64), key)
    vals, idx = jax.lax.top_k(key, k)           # small-k partial select
    return scores[idx], idx


__all__ = ["histogram_topk", "score_histogram", "score_histogram_ref"]
