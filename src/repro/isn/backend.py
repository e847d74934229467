"""Shared plumbing for the batched first-stage serving pipeline.

Backends
--------
The engines (``daat_serve`` / ``saat_serve``) dispatch their hot loop
through one of three backends:

* ``"pallas"``   — compiled Pallas kernels over the shard's bucketed
  postings mirror (the TPU production path);
* ``"interpret"``— the same kernels under the Pallas interpreter; bit-wise
  the kernel code path, runnable on CPU — this is what the parity tests
  exercise so the kernel program itself is covered without hardware;
* ``"jnp"``      — a vectorized pure-jnp pipeline (batched gather + one
  fused scatter over the CSR mirrors) producing identical results; the
  portable fast path on CPU hosts.

``resolve_backend(None)`` picks ``"pallas"`` on TPU and ``"jnp"`` elsewhere,
so tests/CPU hosts never accidentally pay the interpreter cost and TPUs
never fall back to scatter-adds.

Tiled top-k
-----------
``topk_from_tiles`` (re-exported from ``repro.kernels.topk``) reduces the
(Q, n_tiles, tile_d) accumulator tiles the kernels emit to an exact top-k;
integer accumulators rank on packed (score, doc) keys, so their ties go to
the lower doc id.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.index.postings import IndexShard, IndexShardSpec
from repro.kernels.topk import takes_deep_select
from repro.kernels.topk import topk_from_tiles  # noqa: F401  (re-export)

BACKENDS = ("pallas", "interpret", "jnp")


def query_lane_budget(df, terms, mask, round_to: int = 1024,
                      floor: int = 256) -> int:
    """Static per-query posting-lane budget for a batch (host-side helper).

    The batched jnp backend compacts each query's ragged per-term postings
    into a dense (Q, qcap) lane buffer before the fused scatter, so its cost
    tracks the *actual* postings of the batch instead of L x max_df padding.
    Callers size qcap from the batch they are about to serve (like length
    bucketing in LM serving); rounding bounds jit recompiles.  The budget
    is a whole number of ``round_to`` blocks, rounded up to the geometric
    series 1, 2, 3, 4, 6, 8, 12, ... (powers of two and three halves of
    them): at most 1.5 times what the batch needs, and a few dozen budgets
    cover every batch of a collection.
    """
    eff = np.asarray(df)[np.asarray(terms)] * (np.asarray(mask) > 0)
    need = int(eff.sum(axis=1).max()) if eff.size else 0
    blocks = -(-max(need, 1) // round_to)
    step = 1 << (blocks - 1).bit_length()          # next power of two
    if step >= 4 and blocks <= 3 * step // 4:
        step = 3 * step // 4
    return max(step * round_to, floor)


def resolve_backend(backend: str | None) -> str:
    """Default the serving backend from the platform; validate overrides."""
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    return backend


def map_query_blocks(fn, args, pad_values, q_block: int):
    """Stream a query batch through ``fn`` in q_block-sized chunks.

    ``fn(*args)`` must accept per-query arrays (leading axis Q) and return a
    pytree of per-query arrays.  Batches up to ``q_block`` run in one call;
    larger ones are padded with ``pad_values`` (one scalar per arg, chosen
    so padded queries are degenerate no-ops), reshaped to (chunks, q_block,
    ...), mapped sequentially with ``lax.map`` — keeping accumulator memory
    O(q_block · n_docs) — and truncated back to Q rows.
    """
    q = args[0].shape[0]
    if q <= q_block:
        return fn(*args)
    nb = -(-q // q_block)
    pad = nb * q_block - q
    padded = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                      constant_values=pv)
              for a, pv in zip(args, pad_values)]
    out = jax.lax.map(lambda xs: fn(*xs),
                      tuple(a.reshape((nb, q_block) + a.shape[1:])
                            for a in padded))
    return jax.tree.map(
        lambda o: o.reshape((nb * q_block,) + o.shape[2:])[:q], out)


def compact_lanes(base: jnp.ndarray, dfs: jnp.ndarray, qcap: int):
    """Compact ragged per-term posting ranges into (Q, qcap) dense lanes.

    ``base``/``dfs`` are (Q, L): the start offset and live lane count of
    each query term's postings slice.  Lane ``j`` of query ``q`` maps to the
    ``j``-th posting of the concatenated per-term prefixes — located with a
    searchsorted over the prefix cumsum, i.e. pure gathers, no sort.  Lanes
    past the query's total are dead.  This is what lets the fused scatter
    touch O(actual postings) lanes instead of O(L · max_df) padding.

    Returns (pos, live): (Q, qcap) global posting positions + live mask.
    """
    cum = jnp.cumsum(dfs, axis=1)                            # (Q, L)
    start = cum - dfs
    j = jnp.arange(qcap, dtype=jnp.int32)
    term = jax.vmap(lambda c: jnp.searchsorted(c, j, side="right"))(cum)
    term = jnp.minimum(term, dfs.shape[1] - 1).astype(jnp.int32)
    within = j[None, :] - jnp.take_along_axis(start, term, axis=1)
    pos = jnp.take_along_axis(base, term, axis=1) + within
    live = j[None, :] < cum[:, -1:]
    return pos, live


def merge_shard_topk(scores: list, ids: list, k: int, drop=None):
    """Scatter-gather merge of per-shard top-k candidate lists.

    ``scores[s]`` / ``ids[s]`` are the (Q, k_s) ranked candidates of shard
    ``s`` with ids already global to the collection.  The merge sorts the
    small (Q, Σ k_s) grid on two keys, score desc then global doc id asc,
    so equal scores go to the *lower global doc id* — the tie rule of a
    single-shard top-k over the dense accumulator — for either score dtype
    and whatever order ``lax.top_k`` would give equal keys.  Returns (ids,
    scores) of shape (Q, k).

    ``drop`` (optional, (n_shards, Q) bool) masks out shards whose response
    was lost for a query (fault injection / partial coverage): a dropped
    shard's candidates score dtype-min and surface with id ``-1``, so a
    degraded query's list is exactly the merge over its surviving shards,
    padded with ``-1`` when fewer than ``k`` candidates survive.  With
    ``drop=None`` the computation (and result) is bit-identical to the
    three-line merge this started as.
    """
    sc = jnp.concatenate(scores, axis=1)
    di = jnp.concatenate(ids, axis=1)
    if drop is not None:
        dead = jnp.concatenate(
            [jnp.broadcast_to(jnp.asarray(drop[s])[:, None],
                              scores[s].shape) for s in range(len(scores))],
            axis=1)
        fill = (jnp.finfo(sc.dtype).min
                if jnp.issubdtype(sc.dtype, jnp.floating)
                else jnp.iinfo(sc.dtype).min)
        sc = jnp.where(dead, fill, sc)
        di = jnp.where(dead, -1, di)
    # ascending (score, -id), reversed, is (score desc, id asc)
    sc, neg = jax.lax.sort((sc, -di), dimension=1, num_keys=2)
    kk = min(k, sc.shape[1])
    return -neg[:, ::-1][:, :kk], sc[:, ::-1][:, :kk]


class Segment(NamedTuple):
    """One doc-range segment of a Stage-1 fan-out: a sealed shard or the
    live delta, with the host tables Stage-1 reads for it."""
    shard: IndexShard              # device mirrors
    spec: IndexShardSpec           # their static shapes
    doc_lo: int                    # global id of the segment's doc 0
    level_cum: np.ndarray | None = None   # (V, n_levels) impact-level
                                          # table: the global JASS cut
    df: np.ndarray | None = None   # (V,) df: the jnp lane budget (None:
                                   # the spec-static L * max_df)


class SegmentLists(NamedTuple):
    """One engine's fan-out over a segment list: the per-segment top-k
    (ids global), their merge ((ids, scores), or None for one segment and
    no drop), the per-segment work counts (and blocks, for BMW) and whether
    a segment's top-k took the deep select."""
    scores: list
    ids: list
    merged: tuple | None
    work: list
    blocks: list | None = None
    deep_select: bool = False


def deep_select_ran(segments, k: int, backend: str) -> bool:
    """Whether an engine's fan-out at depth ``k`` ranks some segment with
    the deep select: the kernel backends take ``topk_from_tiles`` over the
    segment's tiles (the ``jnp`` backends rank the whole row at once)."""
    return backend != "jnp" and any(
        takes_deep_select(k, g.spec.tile_d, g.spec.n_tiles * g.spec.tile_d)
        for g in segments)


def merge_segments(segments, results, k: int, drop=None):
    """(scores, ids, merged) of per-segment engine ``results``: ids offset
    to global by each segment's ``doc_lo``, merged by ``merge_shard_topk``
    (``drop`` rows follow segment order) unless one segment has no drop."""
    scores = [r.topk_scores for r in results]
    ids = [r.topk_docs + g.doc_lo for g, r in zip(segments, results)]
    merged = (None if len(results) == 1 and drop is None
              else merge_shard_topk(scores, ids, k, drop=drop))
    return scores, ids, merged


def tiled_topk(acc: jnp.ndarray, k: int, tile_d: int = 128,
               max_score: int | None = None):
    """Tiled top-k over a dense (Q, n_docs) accumulator (``max_score`` as
    ``topk_from_tiles`` takes it, for an integer one).  The ragged tail
    tile's padding lies past ``n_docs``, so it never enters the top-k."""
    q, n = acc.shape
    n_tiles = -(-n // tile_d)
    acc = jnp.pad(acc, ((0, 0), (0, n_tiles * tile_d - n)))
    return topk_from_tiles(acc.reshape(q, n_tiles, tile_d), k, n_docs=n,
                           max_score=max_score)
