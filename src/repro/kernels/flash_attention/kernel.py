"""Tiled online-softmax attention kernels (FlashAttention-style) for TPU.

Two kernels:

* ``flash_attention``  — prefill/training: grid (B, H, S/TQ, S/TK), causal
  tiles above the diagonal are skipped whole (grid-level work skipping, the
  same predication idiom as the retrieval kernels).  Running max / sum /
  accumulator live in VMEM scratch across the innermost (key) grid axis.
* ``flash_decode``     — single-token decode with a split-KV grid
  (FlashDecoding): each grid step reduces one KV chunk to partial
  (acc, m, l) statistics; the wrapper merges splits with a stable
  log-sum-exp combine.  This is the kernel behind the ``decode_32k`` and
  ``long_500k`` shapes, where the KV cache is sequence-sharded and each
  shard reduces its local splits before a cross-shard merge.

GQA is handled in the BlockSpec index maps (kv head = q head // group) so
no KV duplication ever materializes.

VMEM at defaults (TQ=TK=128, D=128, fp32 accum): q/k/v tiles 3·64 KB +
acc 64 KB + stats ≈ 1 KB — comfortably double-bufferable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, tq: int, tk: int, n_tk: int):
    jq = pl.program_id(2)
    jk = pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = (not causal) or (jk * tk <= jq * tq + tq - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)          # (TQ, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (TK, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (TK, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jq * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            cols = jk * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = (acc_scr[...] * alpha[:, None]
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(jk == n_tk - 1)
    def _fini():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "tq", "tk", "scale",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, tq: int = 128,
                    tk: int = 128, scale: float | None = None,
                    interpret: bool):
    """q: (B, H, S, D); k, v: (B, Hkv, S, D) -> (B, H, S, D)."""
    b, h, s, d = q.shape
    hkv = k.shape[1]
    group = h // hkv
    scale = scale if scale is not None else d ** -0.5
    tq, tk = min(tq, s), min(tk, s)
    n_tq, n_tk = s // tq, s // tk
    kern = functools.partial(_flash_kernel, scale=scale, causal=causal,
                             tq=tq, tk=tk, n_tk=n_tk)
    return pl.pallas_call(
        kern,
        grid=(b, h, n_tq, n_tk),
        in_specs=[
            pl.BlockSpec((1, 1, tq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, tk, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, tk, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tq, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tq,), jnp.float32),
            pltpu.VMEM((tq,), jnp.float32),
            pltpu.VMEM((tq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# split-KV decode
# ---------------------------------------------------------------------------

def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_ref, l_ref, *,
                   scale: float, tk: int):
    sp = pl.program_id(2)
    q = q_ref[0].astype(jnp.float32)                 # (1, D)
    k = k_ref[0, 0].astype(jnp.float32)              # (TK, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)[0] * scale
    pos = sp * tk + jax.lax.broadcasted_iota(jnp.int32, (tk,), 0)
    s = jnp.where(pos < len_ref[0], s, NEG_INF)
    m = jnp.max(s)
    p = jnp.exp(s - m)
    l = jnp.sum(p)
    acc = jax.lax.dot_general(p[None, :], v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[0, 0, 0] = acc[0]
    m_ref[0, 0, 0] = m
    l_ref[0, 0, 0] = l


@functools.partial(jax.jit, static_argnames=("tk", "scale", "interpret"))
def flash_decode(q, k, v, kv_len, *, tk: int = 512, scale: float | None = None,
                 interpret: bool):
    """q: (B, H, D); k, v: (B, Hkv, S, D); kv_len: (B,) -> (B, H, D).

    Returns the attention output after merging the per-split partials.
    """
    b, h, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    group = h // hkv
    scale = scale if scale is not None else d ** -0.5
    tk = min(tk, s)
    n_sp = s // tk
    kern = functools.partial(_decode_kernel, scale=scale, tk=tk)
    out, m, l = pl.pallas_call(
        kern,
        grid=(b, h, n_sp),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda bi, hi, si: (bi, hi, 0)),
            pl.BlockSpec((1, 1, tk, d),
                         lambda bi, hi, si: (bi, hi // group, si, 0)),
            pl.BlockSpec((1, 1, tk, d),
                         lambda bi, hi, si: (bi, hi // group, si, 0)),
            pl.BlockSpec((1,), lambda bi, hi, si: (bi,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda bi, hi, si: (bi, hi, si, 0)),
            pl.BlockSpec((1, 1, 1), lambda bi, hi, si: (bi, hi, si)),
            pl.BlockSpec((1, 1, 1), lambda bi, hi, si: (bi, hi, si)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, n_sp, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, n_sp), jnp.float32),
            jax.ShapeDtypeStruct((b, h, n_sp), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, kv_len)

    # stable cross-split merge: softmax over all splits =
    #   Σ_i e^{m_i - m*} acc_i  /  Σ_i e^{m_i - m*} l_i
    m_star = jnp.max(m, axis=-1, keepdims=True)          # (B, H, 1)
    scale_sp = jnp.exp(m - m_star)                       # (B, H, n_sp)
    denom = jnp.maximum(jnp.sum(scale_sp * l, axis=-1, keepdims=True), 1e-30)
    merged = jnp.sum(out * scale_sp[..., None], axis=2) / denom
    return merged.astype(q.dtype)
