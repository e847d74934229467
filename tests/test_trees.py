"""GBRT / RF / ridge regression learners."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gbrt, linreg, random_forest as rf
from repro.core import trees as T


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(0)
    x = rng.randn(4000, 24).astype(np.float32)
    y = (2.0 * x[:, 0] - 1.5 * np.abs(x[:, 1]) + 0.5 * x[:, 2] * x[:, 3]
         + 0.3 * rng.randn(4000)).astype(np.float32)
    return x, y


def test_gbrt_l2_beats_mean(data):
    x, y = data
    m = gbrt.fit(x, y, gbrt.GBRTParams(n_trees=40, depth=4, loss="l2"))
    p = np.asarray(gbrt.predict(m, x))
    assert np.sqrt(np.mean((p - y) ** 2)) < 0.5 * y.std()


@pytest.mark.parametrize("tau", [0.25, 0.5, 0.75])
def test_gbrt_quantile_coverage(data, tau):
    """The pinball-loss GBRT must estimate the conditional tau-quantile:
    empirical coverage P(y < f(x)) ≈ tau."""
    x, y = data
    m = gbrt.fit(x, y, gbrt.GBRTParams(n_trees=60, depth=4, loss="quantile",
                                       tau=tau, learning_rate=0.2))
    p = np.asarray(gbrt.predict(m, x))
    cov = np.mean(y < p)
    assert abs(cov - tau) < 0.08, f"coverage {cov} vs tau {tau}"


def test_gbrt_quantiles_ordered(data):
    """Predicted quantiles must be (approximately) monotone in tau."""
    x, y = data
    ps = []
    for tau in (0.25, 0.75):
        m = gbrt.fit(x, y, gbrt.GBRTParams(n_trees=40, depth=4,
                                           loss="quantile", tau=tau))
        ps.append(np.asarray(gbrt.predict(m, x)))
    assert np.mean(ps[1] >= ps[0]) > 0.9


def test_rf_fits(data):
    x, y = data
    m = rf.fit(x, y, rf.RFParams(n_trees=24, depth=6))
    p = np.asarray(rf.predict(m, x))
    assert np.sqrt(np.mean((p - y) ** 2)) < 0.7 * y.std()


def test_linreg_recovers_linear():
    rng = np.random.RandomState(1)
    x = rng.randn(1000, 5).astype(np.float32)
    y = x @ np.asarray([1.0, -2, 0.5, 0, 3], np.float32) + 0.01 * rng.randn(1000)
    m = linreg.fit(x, y, l2=1e-3)
    p = np.asarray(linreg.predict(m, x))
    assert np.sqrt(np.mean((p - y) ** 2)) < 0.05


def test_heavy_tail_median_behaviour():
    """The paper's core statistical claim (Fig. 2): on a heavy-tailed target
    the QR(tau≈0.5) prediction tracks the conditional median while the
    mean-targeting RF overshoots it."""
    rng = np.random.RandomState(2)
    n = 6000
    x = rng.randn(n, 8).astype(np.float32)
    base = np.exp(1.0 + 0.9 * x[:, 0])
    y = (base * np.exp(rng.exponential(1.0, n))).astype(np.float32)  # skewed
    qr = gbrt.fit(x, np.log1p(y), gbrt.GBRTParams(
        n_trees=60, depth=4, loss="quantile", tau=0.5, learning_rate=0.2))
    fr = rf.fit(x, np.log1p(y), rf.RFParams(n_trees=24, depth=6))
    pq = np.expm1(np.asarray(gbrt.predict(qr, x)))
    pf = np.expm1(np.asarray(rf.predict(fr, x)))
    med_true = np.median(y)
    assert abs(np.median(pq) - med_true) < abs(np.median(pf) - med_true) * 1.5
    assert np.median(pq) < np.mean(y)       # median well below the mean


# ---------------------------------------------------------------------------
# gather-free inference against the per-row gather walk
# ---------------------------------------------------------------------------

def _descend(feat, thresh, xb_row, depth):
    node = jnp.zeros((), jnp.int32)
    for d in range(depth):
        f = feat[d, node]
        b = thresh[d, node]
        node = node * 2 + (xb_row[f].astype(jnp.int32) > b).astype(jnp.int32)
    return node


@functools.partial(jax.jit, static_argnames=("depth", "reduce"))
def _gather_walk(forest, xb, depth, reduce="sum"):
    """The oracle: each row walks each tree by indexing the node tables."""
    def per_row(row):
        leaves = jax.vmap(lambda ft, th, lf: lf[_descend(ft, th, row, depth)])(
            forest.feat, forest.thresh, forest.leaf)
        return jnp.sum(leaves) if reduce == "sum" else jnp.mean(leaves)
    return jax.vmap(per_row)(xb)


def _edge_rows(forest, n_feat, n_rand, rng):
    """Random bins, then rows that meet each of the first trees' splits
    exactly on the threshold (bin == thresh, left) or one above (right)
    along their path, then all-0 and all-63 rows."""
    feat, thresh = np.asarray(forest.feat), np.asarray(forest.thresh)
    rows = [rng.randint(0, 64, (n_rand, n_feat))]
    for t in range(min(16, feat.shape[0])):
        for up in (0, 1):
            row = rng.randint(0, 64, n_feat)
            node = 0
            for d in range(feat.shape[1]):
                f, b = feat[t, d, node], thresh[t, d, node]
                row[f] = min(b + up, 63)
                node = node * 2 + int(row[f] > b)
            rows.append(row[None])
    rows += [np.zeros((1, n_feat)), np.full((1, n_feat), 63)]
    return np.concatenate(rows).astype(np.uint8)


@functools.lru_cache(maxsize=None)
def _fit_case(case):
    rng = np.random.RandomState(3)
    if case == "stage2_gbrt":      # Stage-2 LTR: 48 trees of depth 4, 8 features
        x = rng.randn(3000, 8).astype(np.float32)
        y = x[:, 0] - np.abs(x[:, 1]) + 0.3 * rng.randn(3000)
        return [gbrt.fit(x, y, gbrt.GBRTParams(n_trees=48, depth=4))], x
    if case == "stage0_stacked":   # Stage-0 k/ρ/t: 3 x 48 trees of depth 5
        x = rng.randn(3000, 20).astype(np.float32)
        y = np.exp(x[:, 0] + 0.5 * x[:, 1] + 0.5 * rng.randn(3000))
        return [gbrt.fit(x, np.log1p(y), gbrt.GBRTParams(
            n_trees=48, depth=5, loss="quantile", tau=tau), seed=i)
            for i, tau in enumerate((0.5, 0.7, 0.9))], x
    x = rng.randn(3000, 12).astype(np.float32)   # the RF baseline: depth 6
    y = x[:, 0] * x[:, 1] + 0.3 * rng.randn(3000)
    return [rf.fit(x, y, rf.RFParams(n_trees=64, depth=6))], x


@pytest.mark.parametrize("rows", ["batch", "single"])
@pytest.mark.parametrize("case", ["stage2_gbrt", "stage0_stacked", "rf_mean"])
def test_forest_matches_gather_walk(case, rows):
    """The served inference equals the gather walk bit for bit, on binned
    rows at and beside every split threshold and through each model's
    public predict (XLA fixes no order for a float reduction: on the CPU
    the walk itself sums a forest of 32 trees or fewer in an order that
    depends on the batch, so parity is checked on the served sizes)."""
    models, x = _fit_case(case)
    reduce = "mean" if case == "rf_mean" else "sum"
    depth = models[0].params.depth
    n = {"stage2_gbrt": 4 * 128, "stage0_stacked": 32}.get(case, 512)
    rng = np.random.RandomState(len(case))
    xb = np.stack([_edge_rows(m.forest, x.shape[1], n, rng) for m in models])
    xr = x[:n]
    if rows == "single":           # the first row on a threshold
        xb, xr = xb[:, n:n + 1], xr[:1]

    def walk(m, b):
        out = _gather_walk(m.forest, b, depth, reduce)
        return np.asarray(out if reduce == "mean" else m.base + out)
    if case == "stage0_stacked":
        stacked, _ = gbrt.stack_models(models)
        got_b = T.forest_predict_stacked(stacked.forest, jnp.asarray(xb),
                                         depth)
        got_x = gbrt.predict_stacked(stacked, xr, depth)
    else:
        got_b = T.forest_predict_binned(models[0].forest, xb[0], depth,
                                        reduce)[None]
        predict = gbrt.predict if reduce == "sum" else rf.predict
        got_x = predict(models[0], xr)[None]
    np.testing.assert_array_equal(np.asarray(got_b), np.stack(
        [np.asarray(_gather_walk(m.forest, b, depth, reduce))
         for m, b in zip(models, xb)]))
    np.testing.assert_array_equal(np.asarray(got_x), np.stack(
        [walk(m, T.apply_bins(xr, m.bin_edges)) for m in models]))
