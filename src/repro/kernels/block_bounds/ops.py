"""Layout and dispatch of BMW's block-bounds kernel — what the DAAT engine
imports on the kernel backends.

The shard keeps its block-max CSR padded to whole 1,024-entry blocks
(``shard_from_index``), so the kernel's ``(rows, 128)`` tables are views of
the shard's own arrays and nothing is padded or copied per call.  The step
count is static, from the segment's per-term entry cap ``bcap``: a range of
at most ``bcap`` entries spans at most ⌈bcap/1024⌉ + 1 tiles.  The jnp
backend's ``repro.isn.daat._block_bounds_batched`` is the reference the
tests hold the kernel to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.block_bounds.kernel import block_bounds_csr
from repro.kernels.blocks import CSR_BLOCK, LANES
from repro.kernels.qd_feature_gather.ops import csr_steps


def bound_steps(n_slots: int, bcap: int) -> int:
    """Steps per query that cover ``n_slots`` term slots of at most
    ``bcap`` block-max entries each."""
    return n_slots * (-(-bcap // CSR_BLOCK) + 1)


def check_tables(bm_block_id) -> None:
    """The kernel views the CSR's columns as (rows, 128) tables of whole
    tiles; padding them inside a jitted call would copy the shard's CSR on
    every call, so they must be built whole, as ``shard_from_index`` does."""
    if bm_block_id.shape[0] % CSR_BLOCK:
        raise ValueError(
            f"block-max CSR of {bm_block_id.shape[0]} entries is not whole "
            f"{CSR_BLOCK}-entry tiles; build the shard with shard_from_index")


@functools.partial(jax.jit, static_argnames=("n_blocks", "bcap", "interpret"))
def block_bounds(bm_offsets: jnp.ndarray, bm_block_id: jnp.ndarray,
                 bm_block_max: jnp.ndarray, bm_block_cnt: jnp.ndarray,
                 terms: jnp.ndarray, mask: jnp.ndarray, *, n_blocks: int,
                 bcap: int, interpret: bool):
    """Each query's block upper bounds and candidate counts.

    ``bm_*``: the shard's block-max CSR (``IndexShard``); ``terms``/``mask``:
    (Q, L) query term slots, a slot live where ``mask > 0``; ``bcap``: the
    segment's ``max_blocks_per_term``.  Returns (ub, ccnt): (Q, n_blocks)
    float32 sums of the live slots' block maxima, in slot order, and int32
    sums of their posting counts.
    """
    check_tables(bm_block_id)
    q, n_slots = terms.shape
    on = mask > 0
    lo = jnp.where(on, bm_offsets[terms], 0).astype(jnp.int32)
    hi = jnp.where(on, bm_offsets[terms + 1], 0).astype(jnp.int32)
    blk, step_lo, step_hi = csr_steps(lo, hi, bound_steps(n_slots, bcap))
    n_chunks = -(-n_blocks // LANES)
    ub, ccnt = block_bounds_csr(
        *(x.reshape(-1, LANES)
          for x in (bm_block_id, bm_block_max, bm_block_cnt)),
        blk, step_lo, step_hi, n_chunks=n_chunks, interpret=interpret)
    return (ub.reshape(q, n_chunks * LANES)[:, :n_blocks],
            ccnt.reshape(q, n_chunks * LANES)[:, :n_blocks])


__all__ = ["block_bounds", "bound_steps", "check_tables"]
