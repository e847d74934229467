"""Dense Stage-1 retrieval: batched tiled query×doc scoring on the MXU.

The dense modality's serving kernel: one (query blocks, doc tiles) grid
streams the (n_docs, d) embedding matrix through VMEM in ``tile_d``-doc
tiles.  Each grid step scores its tile against a block of query rows with
one MXU matmul (``(QB, d) @ (d, tile_d)``) and writes a ``(QB, tile_d)``
score tile; ghost lanes in the ragged tail tile score ``float32 min``.  The
top-k is the tiled hierarchical merge outside the kernel
(``repro.isn.backend.topk_from_tiles``) — per-tile ``lax.top_k`` then a
merge over per-tile candidates — which keeps the cascade-wide tie policy
(equal scores → lower doc id).  Mosaic has no in-kernel ``top_k``.

VMEM per step is O(QB · d + tile_d · d), independent of n_docs.  The
matmul runs at ``Precision.HIGHEST``: the embeddings are grid-quantized,
so the float32 dot products are exact (see ``ref.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.blocks import pad_axis, query_rows, round_up


def _dense_score_kernel(q_ref, emb_ref, sc_ref, *, tile_d: int, n_docs: int):
    """One (query block, doc tile) grid step: score the tile."""
    part = jax.lax.dot_general(q_ref[...], emb_ref[...],
                               (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    ids = (pl.program_id(1) * tile_d
           + jax.lax.broadcasted_iota(jnp.int32, part.shape, 1))
    sc_ref[...] = jnp.where(ids < n_docs, part, jnp.finfo(jnp.float32).min)


@functools.partial(jax.jit, static_argnames=("tile_d", "n_docs",
                                             "interpret"))
def dense_score_tiles(q_emb: jnp.ndarray, doc_emb: jnp.ndarray, *,
                      tile_d: int, n_docs: int, interpret: bool):
    """Tiled ``q_emb @ doc_embᵀ``.

    Args:
      q_emb: (Q, d) float32 query embeddings; d a lane multiple.
      doc_emb: (n_tiles·tile_d, d) float32, rows past ``n_docs`` are pad.
    Returns:
      (Q, n_tiles, tile_d) float32 scores; ghost docs score float32-min.
    """
    q, d = q_emb.shape
    n_tiles = doc_emb.shape[0] // tile_d
    assert doc_emb.shape[0] == n_tiles * tile_d, (doc_emb.shape, tile_d)
    qb = query_rows(q)
    qp = round_up(q, qb)
    sc = pl.pallas_call(
        functools.partial(_dense_score_kernel, tile_d=tile_d, n_docs=n_docs),
        grid=(qp // qb, n_tiles),
        in_specs=[
            pl.BlockSpec((qb, d), lambda qi, t: (qi, 0)),
            pl.BlockSpec((tile_d, d), lambda qi, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((qb, tile_d), lambda qi, t: (qi, t)),
        out_shape=jax.ShapeDtypeStruct((qp, n_tiles * tile_d), jnp.float32),
        interpret=interpret,
        name="dense_score_tiles",
    )(pad_axis(q_emb, 0, qp, 0.0), doc_emb)
    return sc[:q].reshape(q, n_tiles, tile_d)
