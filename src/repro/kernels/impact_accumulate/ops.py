"""jit'd wrappers: flat postings -> bucketed layout -> Pallas accumulate,
and the batched shard-mirror entry point used by the serving pipeline."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.blocks import LANES, mirror_tiles, round_up
from repro.kernels.impact_accumulate.kernel import impact_accumulate_batched
from repro.kernels.impact_accumulate.ref import impact_accumulate_ref


@functools.partial(jax.jit, static_argnames=("tile_d", "interpret"))
def impact_accumulate_tiles(tile_docs: jnp.ndarray, tile_terms: jnp.ndarray,
                            tile_imps: jnp.ndarray, qterms: jnp.ndarray,
                            lstar: jnp.ndarray, *, tile_d: int,
                            interpret: bool) -> jnp.ndarray:
    """Batched SAAT accumulation over the shard's bucketed mirror.

    Thin dispatch onto ``impact_accumulate_batched``; exists so the engines
    depend on the ops layer (mirroring ``blockmax_score_tiles``) rather than
    on kernel internals.  Returns (Q, n_tiles, tile_d) int32 tiles.
    """
    return impact_accumulate_batched(tile_docs, tile_terms, tile_imps,
                                     qterms, lstar.astype(jnp.int32),
                                     tile_d=tile_d, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_docs", "tile_d", "cap",
                                             "interpret"))
def impact_accumulate(docs: jnp.ndarray, imps: jnp.ndarray,
                      lstar: jnp.ndarray, *, n_docs: int, tile_d: int = 128,
                      cap: int | None = None,
                      interpret: bool) -> jnp.ndarray:
    """Accumulate postings (docs, imps) with impact >= lstar into a dense
    (n_docs,) accumulator via the bucketed MXU kernel.

    The postings are bucketed per call and served as a one-query batch of
    the shard-mirror kernel: every live lane carries term 0 and the query
    asks for term 0.

    `cap` must be >= the max postings per doc tile.  For unique (term, doc)
    postings of an L-term query, cap = tile_d * L is a hard bound; callers
    with tighter knowledge (e.g. ρ_max ≪ tile budget) may pass less and the
    wrapper falls back to the jnp scatter for overflow lanes (exactness is
    never sacrificed).
    """
    p = docs.shape[0]
    n_tiles = -(-n_docs // tile_d)
    nt = mirror_tiles(n_docs, tile_d)                       # kernel rows
    cap = round_up(cap if cap is not None else tile_d * 8, LANES)

    live = docs >= 0
    tile = jnp.where(live, docs // tile_d, n_tiles)         # pad -> ghost tile
    order = jnp.argsort(tile)
    tile_s = tile[order]
    docs_s = jnp.where(live[order], docs[order] - tile_s * tile_d, -1)
    imps_s = imps[order]

    counts = jnp.zeros((n_tiles + 1,), jnp.int32).at[tile_s].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    pos = jnp.arange(p, dtype=jnp.int32) - starts[tile_s]

    fits = (pos < cap) & (tile_s < n_tiles)
    slot = jnp.where(fits, tile_s * cap + pos, nt * cap)
    docs_b = jnp.full((nt * cap + 1,), -1, jnp.int32
                      ).at[slot].set(jnp.where(fits, docs_s, -1))
    imps_b = jnp.zeros((nt * cap + 1,), jnp.int32
                       ).at[slot].set(jnp.where(fits, imps_s, 0))

    docs_b = docs_b[:-1].reshape(nt, cap)
    acc_t = impact_accumulate_batched(
        docs_b, jnp.where(docs_b >= 0, 0, -1),
        imps_b[:-1].reshape(nt, cap), jnp.zeros((1, 1), jnp.int32),
        jnp.reshape(lstar, (1,)), tile_d=tile_d, interpret=interpret)
    acc = acc_t.reshape(nt * tile_d)[:n_docs]

    # overflow fallback (cap exceeded): exact jnp scatter of the residue
    over = live[order] & ~fits & (tile_s < n_tiles)
    d_of = jnp.where(over, docs[order], 0)
    v_of = jnp.where(over & (imps_s >= lstar), imps_s, 0)
    acc = acc.at[d_of].add(v_of)
    return acc


__all__ = ["impact_accumulate", "impact_accumulate_ref",
           "impact_accumulate_tiles"]
