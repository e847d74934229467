"""Pallas kernels for the first-stage retrieval hot loops.

Each package holds ``kernel.py`` (the Pallas program), ``ops.py`` (jit'd
layout/dispatch wrappers — what the engines import), and ``ref.py`` (a
pure-jnp oracle the tests hold the kernel to; ``qd_feature_gather`` is held
to the numpy Stage-2 feature loop instead).

Serving kernels share one **bucketed postings layout**: at index-build
time every posting of a shard is tiled into the ``(n_tiles, tile_cap)``
bucket of its ``tile_d``-doc tile (``IndexShard.tile_docs/terms/scores/
imps`` — see ``repro.index.postings``), doc ids rebased tile-locally and
buckets lane-padded.  A batched kernel then runs a (query blocks, tile
groups, lane chunks) grid: the tile buckets are indexed by the tile
coordinates only, so the whole query batch reads the same shard-resident
blocks zero-copy; term matching happens in-register and each step reduces
a lane chunk of a few buckets into a ``(query rows, tile_d)`` accumulator
tile per bucket with a one-hot MXU matmul shared by the block's queries.
Every block obeys the TPU (8, 128) tiling rule (``blocks.py``), and the
lane chunk keeps VMEM and compile time independent of ``tile_cap``.

* ``blockmax_score`` — DAAT/BMW exact scoring.  Per-block survival rides
  in as a per-doc 0/1 mask; tiles pruned for every query of a query block
  (up to 64 rows) skip their matmul via ``pl.when``, so time follows the
  union of the block's surviving work, not each query's own.
* ``impact_accumulate`` — SAAT/JASS accumulation.  The ρ budget arrives as
  the per-query impact-level cut ``lstar``; compiled cost is a
  deterministic function of the layout (the structural 200 ms guarantee).
* ``qd_feature_gather`` — Stage-2 LTR featurization: per-(query,
  candidate) term-score aggregates {Σ score, max, match count}, read
  straight from the doc-ordered CSR kept as ``(rows, 128)`` tables: a
  (queries, steps) grid whose scalar-prefetched step tables name the
  1,024-posting block of each query term's range that a step reads (the
  ragged-block pattern of paged attention), accumulating into the query's
  output column.  Its oracle is the numpy ``repro.ltr.ranker.qd_features``
  loop, which it matches bit for bit.
* ``block_bounds`` — BMW's per-(query, block) upper bounds and candidate
  counts, read from the shard's block-max CSR in place as ``(rows, 128)``
  tables with ``qd_feature_gather``'s step tables: each step folds one
  1,024-entry tile of one query term's entries into the query's blocks
  with bf16 one-hot MXU passes over the 128-block chunks it touches.  The
  tests hold it to a numpy loop over the slots, bit for bit, and to the
  jnp backend's ``repro.isn.daat._block_bounds_batched``.
* ``dense_topk`` — dense Stage-1: tiled query×doc scores on the MXU, then
  the tiled top-k merge (``repro.kernels.topk.topk_from_tiles``).
* ``topk`` — the tiled top-k merge over the kernels' accumulator tiles.
* ``score_histogram`` — histogram-based top-k over quantized accumulators.
* ``flash_attention`` — attention kernels for the stage-2/LM workloads.

Backend dispatch: the engines (``repro.isn.daat`` / ``repro.isn.saat``)
select ``backend="pallas"`` (compiled, TPU), ``"interpret"`` (same kernel
program under the Pallas interpreter — CPU tests), or ``"jnp"`` (fused
batched gather/scatter fast path for CPU hosts) via
``repro.isn.backend.resolve_backend``; parity across all three is enforced
by ``tests/test_serving_pipeline.py``.
"""
