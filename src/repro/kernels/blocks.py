"""Block-shape rules shared by the serving kernels.

Pallas on TPU tiles the last two dimensions of every block by (8, 128):
each must be a multiple of that or span the whole array dimension.  The
serving kernels therefore take query rows in sublane groups
(``query_rows``), doc tiles ``TILES_PER_STEP`` at a time, and the lane axis
of a tile bucket in fixed-width chunks (``lane_chunk``) accumulated into a
revisited output block — so VMEM per step and compile time do not grow with
the shard's ``tile_cap``.
"""

from __future__ import annotations

import jax.numpy as jnp

SUBLANES = 8
LANES = 128
TILES_PER_STEP = 8        # doc tiles per grid step (a sublane group)
MAX_QUERY_ROWS = 64       # query rows sharing one pass over the buckets
LANE_CHUNKS = (2048, 1024, 512, 256, 128)
# entries of a CSR read in place as (rows, 128) tables, one (8, 128) tile a
# grid step (``qd_feature_gather``'s postings, ``block_bounds``' block maxima)
CSR_BLOCK = SUBLANES * LANES


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def query_rows(q: int) -> int:
    """Query rows per grid step: a sublane multiple, at most 64."""
    return min(round_up(max(q, 1), SUBLANES), MAX_QUERY_ROWS)


def lane_chunk(cap: int) -> int:
    """Widest chunk in ``LANE_CHUNKS`` that divides a lane-aligned ``cap``."""
    for c in LANE_CHUNKS:
        if cap % c == 0:
            return c
    raise ValueError(f"lane capacity {cap} is not a multiple of {LANES}")


def mirror_tiles(n_docs: int, tile_d: int) -> int:
    """Rows of a shard's bucketed mirror: its ``tile_d``-doc tiles rounded
    up to whole tile groups (the rows past the last tile are dead)."""
    return round_up(max(1, -(-n_docs // tile_d)), TILES_PER_STEP)


def pad_axis(x: jnp.ndarray, axis: int, size: int, value) -> jnp.ndarray:
    """Right-pad ``x`` along ``axis`` to ``size`` (no copy when equal)."""
    extra = size - x.shape[axis]
    if extra == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, extra)
    return jnp.pad(x, widths, constant_values=value)


def check_mirror(tile_docs) -> None:
    """The kernels read the ``(n_tiles, cap)`` bucketed mirror in place:
    padding it inside a jitted call would copy the whole shard-resident
    mirror on every call, so it must already be whole tile groups by whole
    lane multiples, as ``pack_tiles`` builds it."""
    n_tiles, cap = tile_docs.shape
    if n_tiles % TILES_PER_STEP or cap % LANES:
        raise ValueError(
            f"bucketed mirror {tile_docs.shape} is not {TILES_PER_STEP}-tile "
            f"groups by {LANES}-lane multiples; build it with pack_tiles")
