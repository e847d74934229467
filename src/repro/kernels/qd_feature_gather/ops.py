"""jit'd wrappers: lane padding/layout -> Pallas qd-feature gather — the
entry point the Stage-2 batched re-ranker imports (mirrors the other
serving kernels' ops layer)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.qd_feature_gather.kernel import qd_feature_gather_lanes
from repro.kernels.qd_feature_gather.ref import qd_feature_gather_ref

LANE_MULTIPLE = 128   # TPU lane width: candidate axis is the minor dim


@functools.partial(jax.jit, static_argnames=("p_tile", "interpret"))
def qd_feature_gather(lane_docs: jnp.ndarray, lane_scores: jnp.ndarray,
                      cand: jnp.ndarray, *, p_tile: int = 512,
                      interpret: bool):
    """Pad lanes/candidates to kernel-friendly shapes and dispatch.

    The lane axis is padded to a multiple of ``p_tile`` with dead lanes and
    the candidate axis to the TPU lane width with -1 (never matched); both
    paddings are sliced back off, so the result matches
    ``qd_feature_gather_ref`` on the original shapes.
    """
    q, p = lane_docs.shape
    c = cand.shape[1]
    p_pad = (-p) % p_tile if p else p_tile
    c_pad = (-c) % LANE_MULTIPLE if c else LANE_MULTIPLE
    if p_pad:
        lane_docs = jnp.pad(lane_docs, ((0, 0), (0, p_pad)),
                            constant_values=-1)
        lane_scores = jnp.pad(lane_scores, ((0, 0), (0, p_pad)))
    if c_pad:
        cand = jnp.pad(cand, ((0, 0), (0, c_pad)), constant_values=-1)
    bm25, mx, cnt = qd_feature_gather_lanes(
        lane_docs, lane_scores, cand, p_tile=p_tile, interpret=interpret)
    return bm25[:, :c], mx[:, :c], cnt[:, :c]


__all__ = ["qd_feature_gather", "qd_feature_gather_ref"]
