"""BMW's per-block upper bounds read from the block-max CSR in place.

BMW prunes with two numbers per (query, doc block): ``ub``, the sum of the
query terms' block maxima, and ``ccnt``, the count of their postings in the
block.  A term's block-max entries (block id, block max, posting count) lie
contiguous in the shard's term-major CSR, block ids ascending, so the
kernel reads them in place: the CSR's three columns, padded to whole
1,024-entry blocks at shard build time, are viewed as ``(rows, 128)``
tables, and a step reads one ``(8, 128)`` tile of each.

The grid is (queries, steps), as ``qd_feature_gather``'s.  Step ``s`` of
query ``q`` covers one tile of one term slot's entry range; a query's steps
walk its slots in order and each slot's tiles in order
(``repro.kernels.qd_feature_gather.ops.csr_steps`` builds the tables).
The tile row of every step is scalar-prefetched into SMEM and the entry
``BlockSpec``'s ``index_map`` reads it, so the kernel DMAs only the tiles
the batch's terms own.  Steps past a query's last tile repeat its index (no
new DMA) with an empty range and skip compute under ``pl.when``: pad rows
and masked slots are all such steps.

A live step masks the tile to its slot's ``[lo, hi)`` entries and folds
each of the tile's 8 rows of 128 entries into the query's blocks, one
128-block column chunk at a time, over the chunks between the row's first
and last block id only::

    onehot[b, e] = id[e] == chunk · 128 + b        (128 × 128, bfloat16)
    parts        = [hi; mid; lo; cnt] · onehotᵀ    — one bf16 MXU pass
    ub[chunk]   += (hi + mid) + lo;   ccnt[chunk] += cnt

``hi``, ``mid`` and ``lo`` split the float32 block maxima exactly into
bfloat16 values (``split_bf16``); a count is at most ``block_size`` (64),
exact in bfloat16.  One term's block ids are unique, so a step puts at
most one entry into any block: each MXU sum is that entry's part or 0
exactly, and ``(hi + mid) + lo`` is its block max bit for bit.  Steps run
in slot order, so ``ub`` accumulates each block's maxima in slot order in
float32, as a numpy loop over the slots does, bit for bit; the counts are
exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blockmax_score.kernel import split_bf16
from repro.kernels.blocks import LANES, SUBLANES

_NO_BLOCK = 1 << 30             # above every block id: a masked entry's min


def _bounds_kernel(blk_ref, lo_ref, hi_ref, bid_ref, bmax_ref, bcnt_ref,
                   ub_ref, cnt_ref, *, n_steps: int):
    """One (query, step) grid step: fold one tile of one term's block-max
    entries into the query's ``(1, n_chunks, 128)`` bounds and counts."""
    s = pl.program_id(1)
    i = pl.program_id(0) * n_steps + s
    lo, hi = lo_ref[i], hi_ref[i]          # tile-local entry bounds

    @pl.when(s == 0)
    def _init():
        ub_ref[...] = jnp.zeros(ub_ref.shape, ub_ref.dtype)
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, cnt_ref.dtype)

    @pl.when(lo < hi)
    def _fold():
        k = (jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0) * LANES
             + jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1))
        live = (k >= lo) & (k < hi)
        ids = jnp.where(live, bid_ref[...], -1)        # -1 matches no block
        hi_p, mid_p, lo_p = split_bf16(jnp.where(live, bmax_ref[...], 0.0))
        cnt = jnp.where(live, bcnt_ref[...], 0).astype(jnp.float32)
        for r in range(SUBLANES):
            @pl.when((lo < (r + 1) * LANES) & (hi > r * LANES))
            def _row(r=r):
                row = ids[r:r + 1, :]                              # (1, 128)
                first = jnp.min(jnp.where(row >= 0, row, _NO_BLOCK))
                last = jnp.max(row)
                v = jnp.concatenate([hi_p[r:r + 1], mid_p[r:r + 1],
                                     lo_p[r:r + 1], cnt[r:r + 1]]
                                    ).astype(jnp.bfloat16)         # (4, 128)

                def chunk(c, carry):
                    onehot = (jax.lax.broadcasted_iota(
                        jnp.int32, (LANES, LANES), 0) + c * LANES
                        == row).astype(jnp.bfloat16)               # (blk, e)
                    parts = jax.lax.dot_general(
                        v, onehot, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)        # (4, blk)
                    ub_ref[0, pl.ds(c, 1), :] += (
                        (parts[0:1] + parts[1:2]) + parts[2:3])
                    cnt_ref[0, pl.ds(c, 1), :] += parts[3:4].astype(
                        jnp.int32)
                    return carry
                jax.lax.fori_loop(first // LANES, last // LANES + 1, chunk, 0)


@functools.partial(jax.jit, static_argnames=("n_chunks", "interpret"))
def block_bounds_csr(tab_id: jnp.ndarray, tab_max: jnp.ndarray,
                     tab_cnt: jnp.ndarray, blk: jnp.ndarray, lo: jnp.ndarray,
                     hi: jnp.ndarray, *, n_chunks: int, interpret: bool):
    """Per-(query, block) upper bounds and candidate counts read from the
    block-max CSR.

    Args:
      tab_id/tab_max/tab_cnt: (rows, 128) int32/float32/int32 block ids,
        block maxima and posting counts of the CSR, rows a multiple of 8;
        read in place, tile by tile.
      blk: (Q, S) int32 tile of each step (``csr_steps``).
      lo/hi: (Q, S) int32 tile-local ``[lo, hi)`` entry bounds of each
        step; ``lo == hi`` marks a dead step.
      n_chunks: 128-block column chunks covering the shard's blocks.
    Returns:
      (ub, ccnt): (Q, n_chunks, 128) float32 / int32.
    """
    q, n_steps = blk.shape
    tile = pl.BlockSpec((SUBLANES, LANES),
                        lambda qi, s, b, *_: (b[qi * n_steps + s], 0))
    out = pl.BlockSpec((1, n_chunks, LANES), lambda qi, s, *_: (qi, 0, 0))
    return pl.pallas_call(
        functools.partial(_bounds_kernel, n_steps=n_steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(q, n_steps),
            in_specs=[tile, tile, tile],
            out_specs=[out, out]),
        out_shape=[
            jax.ShapeDtypeStruct((q, n_chunks, LANES), jnp.float32),
            jax.ShapeDtypeStruct((q, n_chunks, LANES), jnp.int32),
        ],
        interpret=interpret,
        name="bmw_block_bounds",
    )(blk.reshape(-1), lo.reshape(-1), hi.reshape(-1), tab_id, tab_max,
      tab_cnt)
