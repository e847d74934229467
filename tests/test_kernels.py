"""Pallas kernels vs pure-jnp oracles (interpret=True shape/dtype sweeps)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAS_HYPOTHESIS = True
except ImportError:              # deterministic sweeps still run without it
    HAS_HYPOTHESIS = False

from repro.kernels.blockmax_score.ops import blockmax_score, blockmax_score_ref
from repro.kernels.flash_attention.kernel import flash_attention, flash_decode
from repro.kernels.flash_attention.ref import attention_ref, decode_ref
from repro.kernels.impact_accumulate.ops import (impact_accumulate,
                                                 impact_accumulate_ref)
from repro.kernels.score_histogram.ops import histogram_topk
from repro.kernels.score_histogram.kernel import score_histogram
from repro.kernels.score_histogram.ref import score_histogram_ref


# ---------------------------------------------------------------------------
# impact_accumulate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_docs,p,tile_d,cap", [
    (512, 2048, 128, 256),
    (1000, 5000, 128, 128),     # exercises overflow fallback + ragged tail
    (4096, 512, 256, 512),
    (128, 128, 128, 1024),
])
@pytest.mark.parametrize("lstar", [0, 128])
def test_impact_accumulate_matches_ref(n_docs, p, tile_d, cap, lstar):
    rng = np.random.RandomState(n_docs + p + lstar)
    docs = rng.randint(0, n_docs, p).astype(np.int32)
    docs[rng.random_sample(p) < 0.15] = -1
    imps = rng.randint(1, 256, p).astype(np.int32)
    ref = impact_accumulate_ref(jnp.asarray(docs), jnp.asarray(imps),
                                jnp.int32(lstar), n_docs)
    out = impact_accumulate(jnp.asarray(docs), jnp.asarray(imps),
                            jnp.asarray(lstar, jnp.int32), n_docs=n_docs,
                            tile_d=tile_d, cap=cap, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


if HAS_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_impact_accumulate_property(seed):
        """Total accumulated mass == sum of surviving impacts."""
        rng = np.random.RandomState(seed)
        n_docs, p = 256, 1024
        docs = rng.randint(0, n_docs, p).astype(np.int32)
        imps = rng.randint(1, 256, p).astype(np.int32)
        lstar = int(rng.randint(0, 256))
        out = impact_accumulate(jnp.asarray(docs), jnp.asarray(imps),
                                jnp.asarray(lstar, jnp.int32), n_docs=n_docs,
                                tile_d=128, cap=256, interpret=True)
        assert int(np.asarray(out).sum()) == int(imps[imps >= lstar].sum())
else:
    def test_impact_accumulate_property():
        pytest.skip("hypothesis not installed "
                    "(pip install -r requirements-dev.txt)")


# ---------------------------------------------------------------------------
# blockmax_score
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_docs,p,bs,survive_frac", [
    (1024, 4096, 64, 0.3),
    (2000, 2000, 64, 1.0),
    (512, 8192, 128, 0.05),
])
def test_blockmax_score_matches_ref(n_docs, p, bs, survive_frac):
    rng = np.random.RandomState(p)
    docs = rng.randint(0, n_docs, p).astype(np.int32)
    docs[rng.random_sample(p) < 0.1] = -1
    scores = (rng.random_sample(p) * 8).astype(np.float32)
    nb = (n_docs + bs - 1) // bs
    survive = jnp.asarray(rng.random_sample(nb) < survive_frac)
    ref = blockmax_score_ref(jnp.asarray(docs), jnp.asarray(scores), survive,
                             n_docs, bs)
    out = blockmax_score(jnp.asarray(docs), jnp.asarray(scores), survive,
                         n_docs=n_docs, block_size=bs, tile_d=128, cap=256,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-4)


def _full_mantissa(rng, n, lo_exp, hi_exp):
    """float32 values in [2^lo_exp, 2^hi_exp) with all 24 mantissa bits
    random (the low bit set), so no shorter float rounds them exactly."""
    m = rng.randint(0, 1 << 23, n) | 1
    e = rng.randint(lo_exp, hi_exp, n)
    return ((1.0 + m / 2.0 ** 23) * 2.0 ** e).astype(np.float32)


@pytest.mark.parametrize("lo_exp,hi_exp", [
    (-20, -10),       # 1e-6 .. 1e-3
    (-10, 0),
    (0, 6),           # BM25's usual range, up to 64
    (-20, 6),         # all of it
])
def test_score_split_is_exact(lo_exp, hi_exp):
    """The kernel's three bfloat16 parts sum back to each float32 score bit
    for bit, and each part is a bfloat16 value."""
    from repro.kernels.blockmax_score.kernel import split_bf16
    rng = np.random.RandomState(100 * (lo_exp + 30) + hi_exp)
    x = _full_mantissa(rng, 1 << 16, lo_exp, hi_exp)
    x = np.concatenate([x, np.float32([2.0 ** -20, 64.0 - 2.0 ** -18,
                                       np.nextafter(np.float32(1), 2)])])
    parts = split_bf16(jnp.asarray(x))
    for p in parts:
        np.testing.assert_array_equal(
            np.asarray(p.astype(jnp.bfloat16).astype(jnp.float32)),
            np.asarray(p))
    hi, mid, lo = (np.asarray(p) for p in parts)
    np.testing.assert_array_equal((hi + mid) + lo, x)
    assert not np.array_equal(hi, x)      # the split is needed at all


@pytest.mark.parametrize("q", [3, 12])
def test_blockmax_tiles_float32_exact(q):
    """Scores that need all 24 mantissa bits come out as the float32 sum
    of the surviving postings: no bfloat16 rounding reaches the
    accumulator."""
    from repro.index.builder import pack_tiles
    from repro.kernels.blockmax_score.ops import blockmax_score_tiles
    rng = np.random.RandomState(q)
    n_docs, vocab, p, tile_d, bs, L = 700, 24, 6000, 128, 64, 8
    pairs = rng.permutation(n_docs * vocab)[:p]          # unique (term, doc)
    terms = (pairs // n_docs).astype(np.int32)
    docs = (pairs % n_docs).astype(np.int32)
    scores = _full_mantissa(rng, p, -12, 5)
    td, tt, (ts,), _ = pack_tiles(docs, terms, [(scores, 0.0, np.float32)],
                                  n_docs, tile_d)
    qterms = np.full((q, L), -1, np.int32)
    for i in range(q):
        qterms[i, :6] = rng.choice(vocab, 6, replace=False)
    n_blocks = -(-n_docs // bs)
    survive = rng.random_sample((q, n_blocks)) < 0.6
    acc = np.asarray(blockmax_score_tiles(
        jnp.asarray(td), jnp.asarray(tt), jnp.asarray(ts),
        jnp.asarray(qterms), jnp.asarray(survive), tile_d=tile_d,
        block_size=bs, n_blocks=n_blocks, interpret=True)
    ).reshape(q, -1)[:, :n_docs]
    for i in range(q):
        keep = np.isin(terms, qterms[i][qterms[i] >= 0]) \
            & survive[i][docs // bs]
        ref = np.zeros(n_docs, np.float32)
        np.add.at(ref, docs[keep], scores[keep])
        assert (np.bincount(docs[keep], minlength=n_docs) > 2).any()
        np.testing.assert_allclose(acc[i], ref, rtol=1e-6)


def _walk_eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    yield from _walk_eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _walk_eqns(sub)


def test_blockmax_kernel_contracts_in_one_bf16_pass():
    """The scoring kernel's contractions take bfloat16 operands into
    float32 results at default precision: no ``Precision.HIGHEST``
    (several MXU passes a contraction) comes back unnoticed."""
    from repro.kernels.blockmax_score.ops import blockmax_score_tiles
    nt, cap, q, L, nb = 8, 256, 5, 8, 16
    args = (jnp.zeros((nt, cap), jnp.int32), jnp.zeros((nt, cap), jnp.int32),
            jnp.zeros((nt, cap), jnp.float32), jnp.zeros((q, L), jnp.int32),
            jnp.zeros((q, nb), bool))
    jaxpr = jax.make_jaxpr(lambda *a: blockmax_score_tiles(
        *a, tile_d=128, block_size=64, n_blocks=nb, interpret=True))(*args)
    calls = [e for e in _walk_eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in calls] == ["blockmax_score_batched"]
    dots = [e for e in _walk_eqns(calls[0].params["jaxpr"])
            if e.primitive.name == "dot_general"]
    assert dots
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2
        assert [v.aval.dtype for v in e.outvars] == [jnp.float32]
        prec = e.params["precision"]
        assert jax.lax.Precision.HIGHEST not in (
            prec if isinstance(prec, tuple) else (prec,)), prec


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,hkv,s,d,dtype", [
    (1, 4, 4, 128, 32, jnp.float32),     # MHA
    (2, 8, 2, 256, 64, jnp.float32),     # GQA 4:1
    (1, 8, 1, 128, 64, jnp.bfloat16),    # MQA bf16
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(b, h, hkv, s, d, dtype, causal):
    rng = np.random.RandomState(h * s)
    q = jnp.asarray(rng.randn(b, h, s, d), dtype) * 0.4
    k = jnp.asarray(rng.randn(b, hkv, s, d), dtype) * 0.4
    v = jnp.asarray(rng.randn(b, hkv, s, d), dtype)
    out = flash_attention(q, k, v, causal=causal, tq=64, tk=64,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("b,h,hkv,s,d,tk", [
    (2, 8, 2, 256, 64, 64),
    (1, 4, 4, 512, 32, 128),
])
def test_flash_decode_matches_ref(b, h, hkv, s, d, tk):
    rng = np.random.RandomState(s)
    q = jnp.asarray(rng.randn(b, h, d), jnp.float32) * 0.4
    k = jnp.asarray(rng.randn(b, hkv, s, d), jnp.float32) * 0.4
    v = jnp.asarray(rng.randn(b, hkv, s, d), jnp.float32)
    kv_len = jnp.asarray(rng.randint(1, s, b), jnp.int32)
    out = flash_decode(q, k, v, kv_len, tk=tk, interpret=True)
    ref = decode_ref(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# score histogram / histogram top-k
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,n_bins", [(4096, 512), (8192, 2048)])
def test_histogram_matches_ref(n, n_bins):
    rng = np.random.RandomState(n)
    s = rng.randint(-1, n_bins, n).astype(np.int32)
    out = score_histogram(jnp.asarray(s), n_bins=n_bins, tile_n=512,
                          interpret=True)
    ref = score_histogram_ref(jnp.asarray(s), n_bins)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


if HAS_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.sampled_from([10, 100, 500]))
    def test_histogram_topk_exact(seed, k):
        rng = np.random.RandomState(seed)
        s = rng.randint(0, 1500, 4096).astype(np.int32)
        vals, idx = histogram_topk(jnp.asarray(s), k=k, interpret=True)
        ref = np.sort(s)[::-1][:k]
        np.testing.assert_array_equal(np.sort(np.asarray(vals))[::-1], ref)
        # indices must actually point at the returned values
        np.testing.assert_array_equal(s[np.asarray(idx)], np.asarray(vals))
else:
    def test_histogram_topk_exact():
        pytest.skip("hypothesis not installed "
                    "(pip install -r requirements-dev.txt)")
