"""AOT compiles of the four serving kernels for a described TPU v5e chip.

Interpret-mode tests cannot see what the TPU compiler refuses (block
shapes off the (8, 128) tiling, VMEM overflow, primitives Mosaic cannot
lower).  These tests compile each serving kernel with ``interpret=False``
against a described ``v5e:2x2`` topology — no chip attached — at the
widths ``chip_smoke.py`` serves: a 2^20-doc shard's bucketed mirror, the
dense embedding width, and a 64-query batch; Stage-2 at the benchmark
cell's shapes (a 32-query batch at its largest lane budget over a
ClueWeb09B shard's 10.4M postings).  Each compile takes seconds; one that
takes minutes means a kernel's lane axis is no longer split into
fixed-width chunks.
"""

import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import gbrt
from repro.core import trees as T
from repro.kernels.blockmax_score.ops import blockmax_score_tiles
from repro.kernels.dense_topk.ops import dense_topk
from repro.kernels.impact_accumulate.ops import impact_accumulate_tiles
from repro.kernels.qd_feature_gather.ops import qd_feature_gather, step_budget
from repro.ltr.cascade import _rerank_program
from repro.ltr.ranker import Stage2Arrays

Q, L = 64, 8                      # served batch, padded query width
N_DOCS, TILE_D, BLOCK = 1 << 20, 128, 64
N_TILES = N_DOCS // TILE_D
TILE_CAP = 16384                  # lane capacity of that shard's tiles
C = 128                           # Stage-2 candidates (k_serve)
EMB_D, DENSE_TILE = 32, 512       # dense embedding width, doc tile
# Stage-2 in the benchmark cell: batch, largest lane budget, the shard's
# postings, terms and docs
S2_Q, S2_QCAP = 32, 15360
S2_POSTINGS, S2_VOCAB, S2_DOCS = 10_368_464, 2_328_791, 32768
S2_ROWS = -(-(S2_POSTINGS + 1) // 1024) * 8   # (rows, 128) posting tables
# the forests: Stage-2 48 trees of depth 4 over 8 features, Stage-0 three
# stacked ensembles of 48 trees of depth 5 over 147 features
S2_TREES, S2_DEPTH, S2_FEATS = 48, 4, 8
S0_MODELS, S0_TREES, S0_DEPTH, S0_FEATS, N_BINS = 3, 48, 5, 147, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip can write the persistent cache but never read it
    # back; keep it off so the compiles stay silent
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, kernel, one_chip, *shapes):
    """Compile ``fn`` and check that the kernel's custom call carries the
    stable name ``kernel`` (the device trace and the benchmark's kernel
    metrics match ops by it)."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert re.search(rf"%{kernel}(\.\d+)? = [^\n]*"
                     r'custom_call_target="tpu_custom_call"', text), kernel


def test_impact_accumulate_compiles(one_chip):
    def fn(td, tt, ti, qt, lstar):
        return impact_accumulate_tiles(td, tt, ti, qt, lstar, tile_d=TILE_D,
                                       interpret=False)
    mirror = ((N_TILES, TILE_CAP), jnp.int32)
    _compile(fn, "impact_accumulate_batched", one_chip, mirror, mirror,
             mirror, ((Q, L), jnp.int32), ((Q,), jnp.int32))


def test_blockmax_score_compiles(one_chip):
    n_blocks = N_DOCS // BLOCK

    def fn(td, tt, ts, qt, survive):
        return blockmax_score_tiles(td, tt, ts, qt, survive, tile_d=TILE_D,
                                    block_size=BLOCK, n_blocks=n_blocks,
                                    interpret=False)
    _compile(fn, "blockmax_score_batched", one_chip,
             ((N_TILES, TILE_CAP), jnp.int32),
             ((N_TILES, TILE_CAP), jnp.int32),
             ((N_TILES, TILE_CAP), jnp.float32), ((Q, L), jnp.int32),
             ((Q, n_blocks), jnp.bool_))


def test_qd_feature_gather_compiles(one_chip):
    def fn(docs, scores, lo, hi, cand):
        return qd_feature_gather(docs, scores, lo, hi, cand,
                                 n_steps=step_budget(S2_QCAP, L),
                                 interpret=False)
    _compile(fn, "qd_feature_gather_lanes", one_chip,
             ((S2_ROWS, 128), jnp.int32), ((S2_ROWS, 128), jnp.float32),
             ((S2_Q, L), jnp.int32), ((S2_Q, L), jnp.int32),
             ((S2_Q, C), jnp.int32))


def test_stage2_program_reads_postings_in_place(one_chip):
    """The served Stage-2 program (featurize, bin, forest, mask and top-k
    in one launch) still carries the ``qd_feature_gather_lanes`` kernel
    under its stable name, and reads postings only through it: no
    lane-compaction loop and no gather of (Q, qcap) lanes from the CSR's
    flat ``docs``/``score``."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    p, n = S2_POSTINGS, S2_DOCS
    arrs = Stage2Arrays(
        offsets=sds((S2_VOCAB + 1,), jnp.int32), docs=sds((p,), jnp.int32),
        score=sds((p,), jnp.float32),
        blk_docs=sds((S2_ROWS, 128), jnp.int32),
        blk_score=sds((S2_ROWS, 128), jnp.float32),
        doclen=sds((n,), jnp.float32), log1p_doclen=sds((n,), jnp.float32),
        doc_topics=sds((n, 32), jnp.float32),
        doc_topics_max=sds((n,), jnp.float32))
    nodes = (S2_TREES, S2_DEPTH, 2 ** (S2_DEPTH - 1))
    forest = T.Forest(sds(nodes, jnp.int32), sds(nodes, jnp.int32),
                      sds((S2_TREES, 2 ** S2_DEPTH), jnp.float32))
    text = _rerank_program.lower(
        arrs, forest, sds((), jnp.float32),
        sds((S2_FEATS, N_BINS - 1), jnp.float32),
        sds((S2_Q, L), jnp.int32), sds((S2_Q, L), jnp.float32),
        sds((S2_Q,), jnp.int32), sds((S2_Q, C), jnp.int32),
        sds((S2_Q,), jnp.int32),
        params=gbrt.GBRTParams(n_trees=S2_TREES, depth=S2_DEPTH,
                               n_bins=N_BINS, loss="l2", learning_rate=0.2),
        t_final=10, n_iter=20, backend="pallas",
        qcap=S2_QCAP).compile().as_text()
    assert re.search(r"%qd_feature_gather_lanes(\.\d+)? = [^\n]*"
                     r'custom_call_target="tpu_custom_call"', text)
    assert not re.search(r" while\(", text)
    gathers = re.findall(r"= \w+\[([\d,]*)\][^\n]* gather\(", text)
    assert gathers and all(S2_QCAP not in map(int, g.split(","))
                           for g in gathers), gathers


@pytest.mark.parametrize("stage", ["stage2", "stage0"])
def test_forest_program_has_no_gather(one_chip, stage):
    """The forest is evaluated without a data-dependent gather at both
    serving shapes: Stage-2's (Q x C) candidate rows and Stage-0's fused
    k/ρ/t call over a Q-query batch."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def forest(*lead, depth):
        nodes = (*lead, depth, 2 ** (depth - 1))
        return T.Forest(sds(nodes, jnp.int32), sds(nodes, jnp.int32),
                        sds((*lead, 2 ** depth), jnp.float32))
    if stage == "stage2":
        lowered = T.forest_predict_binned.lower(
            forest(S2_TREES, depth=S2_DEPTH),
            sds((S2_Q * C, S2_FEATS), jnp.uint8), depth=S2_DEPTH)
    else:
        stacked = gbrt.StackedGBRT(
            forest(S0_MODELS, S0_TREES, depth=S0_DEPTH),
            sds((S0_MODELS,), jnp.float32),
            sds((S0_MODELS, S0_FEATS, N_BINS - 1), jnp.float32))
        lowered = gbrt.predict_stacked.lower(
            stacked, sds((S2_Q, S0_FEATS), jnp.float32), depth=S0_DEPTH)
    text = lowered.compile().as_text()
    assert re.search(r" (convolution|dot)\(", text)
    assert " gather(" not in text


def test_dense_topk_compiles(one_chip):
    def fn(q_emb, doc_emb):
        return dense_topk(q_emb, doc_emb, C, tile_d=DENSE_TILE,
                          backend="pallas")
    _compile(fn, "dense_score_tiles", one_chip, ((Q, EMB_D), jnp.float32),
             ((N_DOCS, EMB_D), jnp.float32))



# ---------------------------------------------------------------------------
# where the persistent compilation cache goes
# ---------------------------------------------------------------------------

def test_compile_cache_defaults_to_checkout(monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = Path(__file__).resolve().parents[1]
    was = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was
