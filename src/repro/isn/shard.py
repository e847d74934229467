"""Document-sharded distributed ISN — the paper's architecture on a mesh.

Documents shard over the "model" axis (each model-rank is one ISN index
partition holding BOTH mirrors); query batches shard over ("pod", "data").
One serve step runs the full Stage-0 pipeline *inside* the compiled program:

  features (term-stat gather) → GBRT predictions (k̂, ρ̂, t̂) → route →
  JASS mirror (ρ̂ capped at ρ_max) ∥ BMW mirror (rank-safe) →
  per-shard top-k → all-gather over "model" → global top-k merge.

The all-gather payload is k·(score, docid) per shard — a few hundred KB per
query batch, which is why the collective term in §Roofline is negligible
for retrieval serving (latency lives in the per-shard scan, where the ρ
budget bounds it).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import trees as T
from repro.index.postings import IndexShard
from repro.isn.daat import daat_serve
from repro.isn.saat import saat_serve
from repro.kernels.blocks import mirror_tiles

SDS = jax.ShapeDtypeStruct


class ForestArrays(NamedTuple):
    """Flat GBRT ensemble for in-step Stage-0 inference (3 targets)."""
    feat: jnp.ndarray       # (3, T, D, W) int32
    thresh: jnp.ndarray     # (3, T, D, W) int32
    leaf: jnp.ndarray       # (3, T, 2**D) float32
    base: jnp.ndarray       # (3,) float32
    bin_edges: jnp.ndarray  # (147, B-1) float32


def forest_specs(n_targets=3, n_trees=64, depth=5, n_feats=147, n_bins=64):
    w = 2 ** (depth - 1)
    return ForestArrays(
        feat=SDS((n_targets, n_trees, depth, w), jnp.int32),
        thresh=SDS((n_targets, n_trees, depth, w), jnp.int32),
        leaf=SDS((n_targets, n_trees, 2 ** depth), jnp.float32),
        base=SDS((n_targets,), jnp.float32),
        bin_edges=SDS((n_feats, n_bins - 1), jnp.float32),
    )


def _forest_predict(fa: ForestArrays, x, target: int, depth: int):
    """x: (Q, F) raw features -> (Q,) prediction of model ``target``."""
    forest = T.Forest(fa.feat[target], fa.thresh[target], fa.leaf[target])
    return fa.base[target] + T.forest_predict_binned(
        forest, T.apply_bins(x, fa.bin_edges), depth)


def _stage0(fa, term_stats, df, terms, mask, depth=5):
    """147 features + three GBRT predictions, all in-graph."""
    from repro.core import features as F
    x = F.extract(term_stats, df, terms, mask)
    pk = jnp.expm1(_forest_predict(fa, x, 0, depth))
    prho = jnp.expm1(_forest_predict(fa, x, 1, depth))
    pt = jnp.expm1(_forest_predict(fa, x, 2, depth))
    return pk, prho, pt


def hybrid_serve_fn(mesh, *, n_docs_shard: int, n_model: int, k_shard: int,
                    k_global: int, rho_max: int, daat_cap: int,
                    daat_bcap: int, n_blocks: int, block_size: int,
                    t_k: float, t_time: float, forest_depth: int = 5,
                    tile_d: int = 128, backend: str | None = None):
    """Builds the shard_map'ed hybrid serve step.

    Both engines run their batched kernel-backed pipelines inside the
    compiled program; ``backend=None`` resolves per-platform (compiled
    Pallas on TPU, fused-jnp elsewhere) — see ``repro.isn.backend``.
    """

    def serve(index: IndexShard, fa: ForestArrays, term_stats, terms, mask):
        shard = jax.tree.map(lambda a: a[0], index)   # strip stacked dim
        pk, prho, pt = _stage0(fa, term_stats[0], shard.df, terms, mask,
                               forest_depth)
        route_jass = (pk > t_k) | (pt > t_time)       # Algorithm 2
        rho = jnp.clip(prho, 1024, rho_max).astype(jnp.int32)

        saat = saat_serve(shard, terms, mask, rho, n_docs=n_docs_shard,
                          k=k_shard, cap=rho_max, tile_d=tile_d,
                          backend=backend)
        theta = jnp.ones((terms.shape[0],), jnp.float32)
        daat = daat_serve(shard, terms, mask, theta, n_docs=n_docs_shard,
                          n_blocks=n_blocks, block_size=block_size,
                          k=k_shard, cap=daat_cap, bcap=daat_bcap,
                          tile_d=tile_d, backend=backend)

        ids = jnp.where(route_jass[:, None], saat.topk_docs, daat.topk_docs)
        sc = jnp.where(route_jass[:, None], saat.topk_scores,
                       daat.topk_scores)
        work = jnp.where(route_jass, saat.work, daat.work)

        # globalize doc ids and merge across ISN shards
        rank = jax.lax.axis_index("model")
        gids = ids + rank * n_docs_shard
        all_sc = jax.lax.all_gather(sc, "model", axis=1, tiled=True)
        all_ids = jax.lax.all_gather(gids, "model", axis=1, tiled=True)
        top_sc, pos = jax.lax.top_k(all_sc, k_global)
        top_ids = jnp.take_along_axis(all_ids, pos, axis=1)
        return top_ids, top_sc, work, route_jass

    axes = mesh.axis_names
    qspec = P(tuple(a for a in ("pod", "data") if a in axes))
    index_spec = IndexShard(*[P("model")] * len(IndexShard._fields))
    in_specs = (index_spec, ForestArrays(*[P()] * 5), P("model"),
                P(*qspec, None) if qspec else P(None, None),
                P(*qspec, None) if qspec else P(None, None))
    out_specs = (P(*qspec, None), P(*qspec, None), P(*qspec), P(*qspec))
    return shard_map(serve, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def _stacked_index_specs(cfg, n_model: int):
    """ShapeDtypeStructs for the per-shard index, stacked over "model"."""
    v, p, pb = cfg.vocab, cfg.postings_per_shard, cfg.block_entries_per_shard
    m = n_model
    n_docs_shard = cfg.n_docs // n_model
    nt = mirror_tiles(n_docs_shard, cfg.tile_d)
    tc = cfg.tile_cap

    def s(shape, dt=jnp.int32):
        return SDS((m,) + shape, dt)

    return IndexShard(
        df=s((v,)), offsets=s((v + 1,)),
        docs_imp=s((p,)), imp=s((p,)), level_cum=s((v, cfg.n_levels)),
        docs=s((p,)), score=s((p,), jnp.float32),
        bm_offsets=s((v + 1,)), bm_block_id=s((pb,)),
        bm_block_max=s((pb,), jnp.float32), bm_block_cnt=s((pb,)),
        tile_docs=s((nt, tc)), tile_terms=s((nt, tc)),
        tile_scores=s((nt, tc), jnp.float32), tile_imps=s((nt, tc)),
    )


def build_serve_cell(arch_id, cfg, cell, mesh, rules, CellCls):
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_model = axes.get("model", 1)
    n_docs_shard = cfg.n_docs // n_model
    n_blocks = n_docs_shard // cfg.block_size
    # daat_cap bounds the gather backends' per-term lane budget (memory):
    # terms with shard df above it are TRUNCATED there, while the kernel
    # backends' bucketed mirror always scores every posting of a matched
    # term.  On shards where max_df can exceed this cap the two backends
    # therefore differ on ultra-dense terms — the kernel path being the
    # exact one; keep cap >= shard max_df wherever parity matters (the
    # servers and tests do).
    daat_cap = min(n_docs_shard, 1 << 19)
    daat_bcap = min(n_blocks, 1 << 14)

    fn = hybrid_serve_fn(
        mesh, n_docs_shard=n_docs_shard, n_model=n_model,
        k_shard=min(cfg.k_max // 4, 1024), k_global=cfg.k_max,
        rho_max=cfg.rho_max, daat_cap=daat_cap, daat_bcap=daat_bcap,
        n_blocks=n_blocks, block_size=cfg.block_size,
        t_k=1000.0, t_time=150.0, tile_d=cfg.tile_d)

    q = cfg.queries_per_step
    index = _stacked_index_specs(cfg, n_model)
    fa = forest_specs()
    term_stats = SDS((n_model, cfg.vocab, 36), jnp.float32)
    terms = SDS((q, cfg.query_len), jnp.int32)
    mask = SDS((q, cfg.query_len), jnp.float32)

    qaxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    qsh = NamedSharding(mesh, P(qaxes, None))
    q1 = NamedSharding(mesh, P(qaxes))
    ish = IndexShard(*[NamedSharding(mesh, P("model"))
                       if True else None] * len(IndexShard._fields))
    fsh = ForestArrays(*[NamedSharding(mesh, P())] * 5)
    tsh = NamedSharding(mesh, P("model"))

    meta = {"n_docs": cfg.n_docs, "postings": cfg.postings_per_shard * n_model,
            "rho_max": cfg.rho_max, "queries": q}
    return CellCls(arch_id, cell.name, "isn", "serve", fn,
                   (index, fa, term_stats, terms, mask),
                   (ish, fsh, tsh, qsh, qsh),
                   (qsh, qsh, q1, q1), (), meta)
