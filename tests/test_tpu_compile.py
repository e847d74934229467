"""AOT compiles of the four serving kernels for a described TPU v5e chip.

Interpret-mode tests cannot see what the TPU compiler refuses (block
shapes off the (8, 128) tiling, VMEM overflow, primitives Mosaic cannot
lower).  These tests compile each serving kernel with ``interpret=False``
against a described ``v5e:2x2`` topology — no chip attached — at the
widths ``chip_smoke.py`` serves: a 2^20-doc shard's bucketed mirror,
Stage-2 lane budget and candidate depth, the dense embedding width, and a
64-query batch.  Each compile takes seconds; one that takes minutes means
a kernel's lane axis is no longer split into fixed-width chunks.
"""

import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.blockmax_score.ops import blockmax_score_tiles
from repro.kernels.dense_topk.ops import dense_topk
from repro.kernels.impact_accumulate.ops import impact_accumulate_tiles
from repro.kernels.qd_feature_gather.ops import qd_feature_gather

Q, L = 64, 8                      # served batch, padded query width
N_DOCS, TILE_D, BLOCK = 1 << 20, 128, 64
N_TILES = N_DOCS // TILE_D
TILE_CAP = 16384                  # lane capacity of that shard's tiles
QCAP = 1 << 20                    # Stage-2 posting-lane budget (bound)
C = 128                           # Stage-2 candidates (k_serve)
EMB_D, DENSE_TILE = 32, 512       # dense embedding width, doc tile


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
    def fn(docs, scores, cand):
        return qd_feature_gather(docs, scores, cand, interpret=False)
    _compile(fn, "qd_feature_gather_lanes", one_chip, ((Q, QCAP), jnp.int32),
             ((Q, QCAP), jnp.float32), ((Q, C), jnp.int32))


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
