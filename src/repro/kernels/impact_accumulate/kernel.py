"""SAAT impact accumulation as an MXU matmul — the TPU adaptation of JASS's
scatter loop (`acc[doc] += impact`).

Hardware mapping
----------------
A scalar scatter-add is hostile to the TPU's vector/matrix units, so the
postings are *bucketed by document tile* at index-build time (the JASS ρ
budget is an impact-level mask, so processing order inside a bucket is
irrelevant) and each bucket is reduced with a one-hot matmul shared by a
block of queries:

    acc[q, tile] += V (QB × CH)  @  onehot(local_doc)ᵀ (CH × TILE_D)

where ``V[q, lane]`` is the lane's impact if its term is one of query
``q``'s terms and its impact reaches the query's level cut, else 0.

Grid and blocks (see ``repro.kernels.blocks``): (query blocks, tile groups,
lane chunks).  A step reads ``TILES_PER_STEP`` tile buckets over one
``CH``-lane chunk and adds into the ``(QB, TILES_PER_STEP · TILE_D)``
output block, which stays resident across the innermost lane-chunk axis.
VMEM per step is O(QB·CH + TILE_D·CH), independent of the shard's
``tile_cap``.  Impacts are 8-bit integers, exact in bfloat16, and the
partial sums stay below 2^24, so the bf16 matmul with f32 accumulation is
exact.

The ρ budget appears as the per-query cut ``lstar``: lanes with impact <
lstar contribute zero, and the *grid itself* is sized by the bucketed
layout, so compiled cost is a deterministic function of the shard — the
structural version of the paper's 200 ms guarantee.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.blocks import (TILES_PER_STEP, check_mirror, lane_chunk,
                                  pad_axis, query_rows, round_up)


def _accumulate_kernel(qterms_ref, lstar_ref, docs_ref, terms_ref, imps_ref,
                       acc_ref, *, tile_d: int):
    """One (query block, tile group, lane chunk) grid step."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    qt = qterms_ref[...]                          # (QB, L) query terms, -1 pad
    cut = lstar_ref[...]                          # (QB, 1) level cuts
    ch = docs_ref.shape[1]
    for i in range(TILES_PER_STEP):
        local = docs_ref[i:i + 1, :]              # (1, CH) tile-local, -1 pad
        tterm = terms_ref[i:i + 1, :]             # (1, CH) term ids, -1 pad
        imps = imps_ref[i:i + 1, :]               # (1, CH)
        match = tterm == qt[:, 0:1]
        for l in range(1, qt.shape[1]):
            match = match | (tterm == qt[:, l:l + 1])
        live = match & (imps >= cut) & (local >= 0)          # (QB, CH)
        v = jnp.where(live, imps, 0).astype(jnp.bfloat16)
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (tile_d, ch), 0)
                  == local).astype(jnp.bfloat16)             # (TILE_D, CH)
        part = jax.lax.dot_general(v, onehot, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        cols = slice(i * tile_d, (i + 1) * tile_d)
        acc_ref[:, cols] += part.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("tile_d", "interpret"))
def impact_accumulate_batched(tile_docs: jnp.ndarray, tile_terms: jnp.ndarray,
                              tile_imps: jnp.ndarray, qterms: jnp.ndarray,
                              lstar: jnp.ndarray, *, tile_d: int,
                              interpret: bool) -> jnp.ndarray:
    """Batched impact accumulation over the shard's bucketed mirror.

    Args:
      tile_docs/tile_terms/tile_imps: (n_tiles, CAP) build-time bucketed
        shard mirror (``pack_tiles``: whole tile groups and lane multiples)
        — read in place and shared across the query batch.
      qterms: (Q, L) query term ids, -1 in masked-out slots.
      lstar: (Q,) int32 per-query impact-level cuts from the ρ budgets.
    Returns:
      (Q, n_tiles, tile_d) int32 accumulator tiles.
    """
    check_mirror(tile_docs)
    nt, cap = tile_docs.shape
    q, L = qterms.shape
    qb = query_rows(q)
    qp = round_up(q, qb)
    ch = lane_chunk(cap)
    # padded query rows match no term, so they accumulate nothing
    qt = pad_axis(qterms.astype(jnp.int32), 0, qp, -1)
    cut = pad_axis(lstar.astype(jnp.int32).reshape(q, 1), 0, qp, 0)
    mirror = pl.BlockSpec((TILES_PER_STEP, ch), lambda a, t, c: (t, c))
    acc = pl.pallas_call(
        functools.partial(_accumulate_kernel, tile_d=tile_d),
        grid=(qp // qb, nt // TILES_PER_STEP, cap // ch),
        in_specs=[
            pl.BlockSpec((qb, L), lambda a, t, c: (a, 0)),
            pl.BlockSpec((qb, 1), lambda a, t, c: (a, 0)),
            mirror, mirror, mirror,
        ],
        out_specs=pl.BlockSpec((qb, TILES_PER_STEP * tile_d),
                               lambda a, t, c: (a, t)),
        out_shape=jax.ShapeDtypeStruct((qp, nt * tile_d), jnp.int32),
        interpret=interpret,
        name="impact_accumulate_batched",
    )(qt, cut, tile_docs, tile_terms, tile_imps)
    return acc[:q].reshape(q, nt, tile_d)
