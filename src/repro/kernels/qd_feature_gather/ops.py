"""Layout and dispatch of the Stage-2 qd-feature gather — the entry point
the Stage-2 batched re-ranker imports (mirrors the other serving kernels'
ops layer).

``csr_blocks`` builds the kernel's ``(rows, 128)`` posting tables once per
index; ``csr_steps`` turns a batch's per-term posting ranges into the
kernel's (Q, S) step tables inside the caller's jit; ``step_budget`` is the
static S that covers any batch whose per-query posting total fits a lane
budget ``qcap``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.blocks import LANES, SUBLANES, pad_axis, round_up
from repro.kernels.qd_feature_gather.kernel import (BLOCK,
                                                    qd_feature_gather_csr)


def csr_blocks(docs: np.ndarray, score: np.ndarray):
    """The CSR's postings as the kernel's ``(rows, 128)`` tables, padded
    with doc -1 and score 0 to whole blocks past one extra posting, so the
    block of every offset up to and including the CSR's end exists."""
    n = round_up(len(docs) + 1, BLOCK)
    blk_docs = np.full(n, -1, np.int32)
    blk_docs[:len(docs)] = docs
    blk_score = np.zeros(n, np.float32)
    blk_score[:len(score)] = score
    return (jnp.asarray(blk_docs.reshape(-1, LANES)),
            jnp.asarray(blk_score.reshape(-1, LANES)))


def step_budget(qcap: int, n_slots: int) -> int:
    """Steps per query that cover every query of at most ``qcap`` postings
    over ``n_slots`` term slots: a range of ``df`` postings spans at most
    ⌈df/1024⌉ + 1 blocks, so Σ_l blocks ≤ qcap/1024 + 2 L."""
    return -(-qcap // BLOCK) + 2 * n_slots


def csr_steps(lo: jnp.ndarray, hi: jnp.ndarray, n_steps: int):
    """(Q, S) step tables of the term slots' posting ranges.

    ``lo``/``hi`` are (Q, L) CSR offsets of each slot's postings, with
    ``hi == lo`` for masked slots.  Steps walk the slots in order and each
    slot's 1,024-posting blocks in order.  Returns (blk, step_lo, step_hi):
    the block of each step and its block-local ``[lo, hi)`` bounds; steps
    past the query's last block repeat that block with ``lo == hi == 0``.
    Blocks past ``n_steps`` are dropped: size it with ``step_budget``.
    """
    first = lo // BLOCK
    n_blk = jnp.where(hi > lo, (hi - 1) // BLOCK - first + 1, 0)   # (Q, L)
    end = jnp.cumsum(n_blk, axis=1)
    total = end[:, -1:]
    j = jnp.arange(n_steps, dtype=jnp.int32)[None, :]
    jj = jnp.minimum(j, jnp.maximum(total - 1, 0))                  # (Q, S)
    slot = jnp.sum(end[:, None, :] <= jj[:, :, None], axis=2)
    slot = jnp.minimum(slot, lo.shape[1] - 1)

    def at(x):
        return jnp.take_along_axis(x, slot, axis=1)

    blk = at(first) + jj - at(end - n_blk)
    live = j < total
    base = blk * BLOCK
    step_lo = jnp.where(live, jnp.clip(at(lo) - base, 0, BLOCK), 0)
    step_hi = jnp.where(live, jnp.clip(at(hi) - base, 0, BLOCK), 0)
    return (blk.astype(jnp.int32), step_lo.astype(jnp.int32),
            step_hi.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("n_steps", "interpret"))
def qd_feature_gather(blk_docs: jnp.ndarray, blk_score: jnp.ndarray,
                      lo: jnp.ndarray, hi: jnp.ndarray, cand: jnp.ndarray,
                      *, n_steps: int, interpret: bool):
    """Σ score, max score and match count of each (query, candidate) over
    the query's term slots, read from the ``csr_blocks`` tables.

    ``lo``/``hi``: (Q, L) slot posting ranges (``hi == lo`` when masked);
    ``cand``: (Q, C) doc ids, -1 padding; ``n_steps``: ``step_budget`` of
    the batch's lane budget.  The candidate axis is padded to a sublane
    multiple with -1 (never matched) and sliced back off.
    """
    c = cand.shape[1]
    cand = pad_axis(cand.astype(jnp.int32), 1, round_up(max(c, 1), SUBLANES),
                    -1)
    blk, step_lo, step_hi = csr_steps(lo.astype(jnp.int32),
                                      hi.astype(jnp.int32), n_steps)
    bm25, mx, cnt = qd_feature_gather_csr(blk_docs, blk_score, blk, step_lo,
                                          step_hi, cand, interpret=interpret)
    return bm25[:, :c], mx[:, :c], cnt[:, :c]


__all__ = ["csr_blocks", "csr_steps", "qd_feature_gather", "step_budget"]
