"""jit'd wrappers: shape padding -> dense top-k kernel dispatch — the entry
point the dense Stage-1 engine imports, with the same ``pallas | interpret
| jnp`` switch as the other serving kernels."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dense_topk.kernel import dense_score_tiles
from repro.kernels.dense_topk.ref import dense_topk_ref
from repro.kernels.topk import topk_from_tiles

LANE_MULTIPLE = 128   # TPU lane width: the embed dim lives on the minor axis


@functools.partial(jax.jit, static_argnames=("k", "tile_d", "backend"))
def dense_topk(q_emb: jnp.ndarray, doc_emb: jnp.ndarray, k: int, *,
               tile_d: int = 512, backend: str = "jnp"):
    """Top-k of ``q_emb @ doc_embᵀ``: (scores, ids), each (Q, k).

    ``backend="jnp"`` runs the dense reference (full score matrix +
    ``lax.top_k``); ``"pallas"`` / ``"interpret"`` run the tiled scoring
    kernel compiled / in interpreter mode, then the tiled top-k merge.  The
    embed dim is zero-padded to the lane width (zero products are exact —
    no parity cost) and the doc axis to a ``tile_d`` multiple; ghost docs
    are masked in-kernel.  All backends agree bitwise on grid-quantized
    embeddings (see ``kernels/dense_topk/ref.py``).
    """
    q_emb = jnp.asarray(q_emb, jnp.float32)
    doc_emb = jnp.asarray(doc_emb, jnp.float32)
    n, d = doc_emb.shape
    if not 1 <= k <= n:
        raise ValueError(f"k={k} must be in [1, n_docs={n}]")
    if backend == "jnp":
        return dense_topk_ref(q_emb, doc_emb, k)
    if tile_d % LANE_MULTIPLE:
        raise ValueError(f"tile_d={tile_d} must be a multiple of "
                         f"{LANE_MULTIPLE}")
    d_pad = (-d) % LANE_MULTIPLE
    if d_pad:
        q_emb = jnp.pad(q_emb, ((0, 0), (0, d_pad)))
        doc_emb = jnp.pad(doc_emb, ((0, 0), (0, d_pad)))
    n_pad = (-n) % tile_d
    if n_pad:
        doc_emb = jnp.pad(doc_emb, ((0, n_pad), (0, 0)))
    sc = dense_score_tiles(q_emb, doc_emb, tile_d=tile_d, n_docs=n,
                           interpret=(backend != "pallas"))
    # ids stay int32 on device (x64 is disabled); hosts widen as needed
    return topk_from_tiles(sc, k, n_docs=n)


__all__ = ["dense_topk", "dense_topk_ref"]
