import os
import sys

# tests see the real (single) device — the 512-device flag is dryrun-only
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest

from repro.index.builder import build_index
from repro.index.corpus import CorpusParams, build_corpus, build_queries
from repro.serving.spec import (BackendSpec, CascadeSpec, DeploySpec,
                                IndexSpec, Stage2Spec)
from repro.serving.system import build_system, routing_spec


@pytest.fixture(scope="session")
def small_collection():
    corpus = build_corpus(CorpusParams(n_docs=4096, vocab=2048,
                                       avg_doclen=80, zipf_a=1.05, seed=3))
    index = build_index(corpus, stop_k=8)
    ql = build_queries(corpus, 96, stop_k=8, seed=11)
    return corpus, index, ql


@pytest.fixture(scope="session")
def one_shard_system():
    """Builds a one-shard system from a runtime ``SchedulerConfig``: one
    partition with ``replicas=2``, so it holds one replica of each mirror
    (a 1-replica pool is JASS-only), no rebalancing, and Stage-2 on only
    when an LTR model is given."""
    def build(index, models, cfg, *, corpus=None, ltr=None, k_serve=128,
              t_final=10, cost=None, backend=None):
        spec = CascadeSpec(
            index=IndexSpec(block_size=index.block_size),
            routing=routing_spec(cfg),
            stage2=Stage2Spec(enabled=ltr is not None, k_serve=k_serve,
                              t_final=t_final),
            backend=BackendSpec(backend=backend),
            deploy=DeploySpec(n_shards=1, replicas=2, rebalance_every=0),
            name="one_shard")
        return build_system(spec, index, corpus=corpus, models=models,
                            ltr=ltr, cost=cost)
    return build
