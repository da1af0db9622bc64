"""Categorical split finding vs a NumPy oracle of the reference algorithm.

The oracle mirrors ``FindBestThresholdCategoricalInner``
(/root/reference/src/treelearner/feature_histogram.cpp:147-343): one-hot for
small cardinality, otherwise categories sorted by g/(h+cat_smooth) scanned
from both directions up to max_cat_threshold with cat_l2 regularization.
min_data_per_group is tested at 1 where the vectorized crossing-of-multiples
approximation is exact.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.ops.split import CatParams, best_split  # noqa: E402

from .planes import planes  # noqa: E402


def _np_leaf_gain(g, h, l1, l2):
    t = np.sign(g) * np.maximum(np.abs(g) - l1, 0.0)
    return (t * t) / (h + l2 + 1e-15)


def np_cat_best(hist, pg, ph, pc, num_bins, cp: CatParams, l1, l2,
                min_data, min_hess):
    """Oracle: best categorical split for ONE feature.

    Returns (raw_gain, left_bin_set) or (-inf, None)."""
    g, h, c = hist[:, 0], hist[:, 1], hist[:, 2]
    best = (-np.inf, None)

    def gain_of(lg, lh, lc, l2e):
        rg, rh, rc = pg - lg, ph - lh, pc - lc
        if lc < min_data or rc < min_data or lh < min_hess or rh < min_hess:
            return -np.inf
        return _np_leaf_gain(lg, lh, l1, l2e) + _np_leaf_gain(rg, rh, l1, l2e)

    if num_bins <= cp.max_cat_to_onehot:
        for t in range(num_bins):
            gn = gain_of(g[t], h[t], c[t], l2)
            if gn > best[0]:
                best = (gn, {t})
    else:
        l2e = l2 + cp.cat_l2
        valid = [t for t in range(num_bins) if c[t] >= cp.cat_smooth]
        ctr = {t: g[t] / (h[t] + cp.cat_smooth) for t in valid}
        order = sorted(valid, key=lambda t: ctr[t])
        used = len(order)
        max_num_cat = min(cp.max_cat_threshold, (used + 1) // 2)
        for direction in (1, -1):
            seq = order if direction == 1 else order[::-1]
            lg = lh = lc = 0.0
            for i in range(min(used, max_num_cat)):
                t = seq[i]
                lg += g[t]
                lh += h[t]
                lc += c[t]
                if pc - lc < cp.min_data_per_group:
                    break
                gn = gain_of(lg, lh, lc, l2e)
                if gn > best[0]:
                    best = (gn, set(seq[: i + 1]))
    return best


def _problem(num_bins, f, n, seed):
    """Row-level categorical data -> per-feature histograms with a SHARED
    parent total (all features histogram the same rows)."""
    rng = np.random.default_rng(seed)
    b = 64
    bins = rng.integers(0, num_bins, size=(n, f))
    # per-category effects so subsets genuinely matter
    effect = rng.normal(scale=2.0, size=(f, num_bins))
    grad = effect[0][bins[:, 0]] + rng.normal(size=n)
    hess = np.ones(n)
    hist = np.zeros((f, b, 3))
    for j in range(f):
        np.add.at(hist[j, :, 0], bins[:, j], grad)
        np.add.at(hist[j, :, 1], bins[:, j], hess)
        np.add.at(hist[j, :, 2], bins[:, j], 1.0)
    return hist, grad.sum(), hess.sum(), float(n)


@pytest.mark.parametrize(
    "num_bins,max_oh", [(3, 4), (12, 4), (40, 4), (12, 16)]
)
def test_categorical_matches_oracle(num_bins, max_oh):
    f, n = 5, 600
    hist, pg, ph, pc = _problem(num_bins, f, n, seed=num_bins * 7 + max_oh)
    cp = CatParams(
        max_cat_to_onehot=max_oh,
        max_cat_threshold=8,
        cat_l2=2.0,
        cat_smooth=3.0,
        min_data_per_group=1,
    )
    l1, l2, min_data, min_hess = 0.0, 1.0, 3, 1e-3

    per_feature = [
        np_cat_best(hist[j], pg, ph, pc, num_bins, cp, l1, l2, min_data, min_hess)
        for j in range(f)
    ]
    best_j = int(np.argmax([pf[0] for pf in per_feature]))
    oracle_gain, oracle_set = per_feature[best_j]
    oracle_improvement = oracle_gain - _np_leaf_gain(pg, ph, l1, l2)

    cand = best_split(
        jnp.asarray(planes(hist), jnp.float32),
        jnp.float32(pg),
        jnp.float32(ph),
        jnp.float32(pc),
        jnp.full((f,), num_bins, jnp.int32),
        jnp.full((f,), -1, jnp.int32),
        jnp.ones((f,), bool),
        lambda_l1=l1,
        lambda_l2=l2,
        min_data_in_leaf=min_data,
        min_sum_hessian_in_leaf=min_hess,
        min_gain_to_split=0.0,
        is_cat=jnp.ones((f,), bool),
        cat_params=cp,
    )
    assert bool(cand.is_cat)
    assert float(cand.gain) == pytest.approx(oracle_improvement, rel=1e-4)
    assert int(cand.feature) == best_j
    got_set = set(np.nonzero(np.asarray(cand.cat_mask))[0].tolist())
    assert got_set == oracle_set
    # left stats match the subset sums
    np.testing.assert_allclose(
        float(cand.left_cnt),
        sum(hist[best_j, t, 2] for t in oracle_set),
        rtol=1e-5,
    )


def test_e2e_categorical_beats_frequency_rank():
    """End-to-end: a target keyed to an arbitrary category SUBSET (unrelated
    to frequency) is learnable — the frequency-rank-prefix model provably
    cannot isolate it with one split, the sorted-subset scan can."""
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(42)
    n, k = 4000, 12
    # frequencies deliberately uncorrelated with effect: odd cats are +1
    probs = rng.dirichlet(np.ones(k))
    cat = rng.choice(k, size=n, p=probs)
    y = np.where(cat % 2 == 1, 1.0, -1.0) + rng.normal(scale=0.05, size=n)
    X = cat.reshape(-1, 1).astype(np.float64)

    params = {
        "objective": "regression",
        "num_leaves": 2,
        "min_data_in_leaf": 5,
        "min_data_per_group": 1,
        "cat_smooth": 1.0,
        "max_cat_to_onehot": 1,  # force the sorted-subset path
        "learning_rate": 1.0,
        "verbosity": -1,
    }
    d = lgb.Dataset(X, y, categorical_feature=[0])
    bst = lgb.train(params, d, num_boost_round=1)
    tree = bst.models_[0]
    assert tree.num_leaves == 2
    assert tree.decision_type[0] & 1  # categorical split
    # one split must isolate the odd set: per-category predictions correct
    pred = bst.predict(np.arange(k, dtype=np.float64).reshape(-1, 1))
    base = y.mean()
    odd, even = pred[1::2].mean(), pred[0::2].mean()
    assert odd - even > 1.5, (odd, even)  # clean separation, not freq prefix


def test_e2e_categorical_roundtrip_and_consistency():
    """Trained cat model: device (bin-space) training scores == host predict,
    and model text round-trip preserves predictions exactly."""
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(7)
    n, k = 1500, 20
    cat = rng.integers(0, k, size=n)
    num = rng.normal(size=n)
    y = np.sin(cat * 1.7) + 0.5 * num + rng.normal(scale=0.1, size=n)
    X = np.column_stack([cat.astype(np.float64), num])
    params = {
        "objective": "regression",
        "num_leaves": 15,
        "min_data_in_leaf": 5,
        "min_data_per_group": 1,
        "verbosity": -1,
        "metric": "l2",
    }
    d = lgb.Dataset(X, y, categorical_feature=[0])
    ev = {}
    bst = lgb.train(
        params, d, num_boost_round=10,
        valid_sets=[d], valid_names=["train"],
        callbacks=[lgb.record_evaluation(ev)],
    )
    pred = bst.predict(X)
    # the device training score and the host prediction walk must agree
    final_l2 = ev["train"]["l2"][-1]
    assert float(np.mean((pred - y) ** 2)) == pytest.approx(final_l2, rel=1e-3)
    assert final_l2 < 0.25 * np.var(y)
    # text round-trip
    b2 = lgb.Booster(model_str=bst.model_to_string())
    np.testing.assert_allclose(b2.predict(X), pred, rtol=1e-6, atol=1e-7)


def test_e2e_categorical_nan_goes_right():
    """NaN categorical values follow the prediction rule (right child) during
    training too — train/predict consistency with missing categoricals."""
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(3)
    n, k = 1200, 8
    cat = rng.integers(0, k, size=n).astype(np.float64)
    nan_rows = rng.random(n) < 0.15
    cat[nan_rows] = np.nan
    y = np.where(np.isnan(cat), 0.0, np.where(cat % 2 == 1, 1.0, -1.0))
    y = y + rng.normal(scale=0.05, size=n)
    X = cat.reshape(-1, 1)
    params = {
        "objective": "regression",
        "num_leaves": 8,
        "min_data_in_leaf": 5,
        "min_data_per_group": 1,
        "max_cat_to_onehot": 1,
        "verbosity": -1,
        "metric": "l2",
    }
    ev = {}
    bst = lgb.train(
        params, lgb.Dataset(X, y, categorical_feature=[0]), num_boost_round=8,
        valid_sets=[lgb.Dataset(X, y, categorical_feature=[0])],
        valid_names=["train"],
        callbacks=[lgb.record_evaluation(ev)],
    )
    pred = bst.predict(X)
    # bin-space (training) and real-space (predict) walks agree
    assert float(np.mean((pred - y) ** 2)) == pytest.approx(
        ev["train"]["l2"][-1], rel=1e-3
    )


def test_mixed_numeric_and_categorical():
    """A numeric feature with a clean threshold must win over a weak
    categorical, and vice versa — the combined argmax is coherent."""
    rng = np.random.default_rng(0)
    n, b = 400, 64
    # feature 0: numeric, perfectly splits at bin < 8
    nume = rng.integers(0, 16, size=n)
    grad = np.where(nume < 8, -1.0, 1.0) + 0.01 * rng.normal(size=n)
    # feature 1: categorical, weak effect
    catv = rng.integers(0, 10, size=n)
    hist = np.zeros((2, b, 3))
    np.add.at(hist[0, :, 0], nume, grad)
    np.add.at(hist[0, :, 1], nume, 1.0)
    np.add.at(hist[0, :, 2], nume, 1.0)
    np.add.at(hist[1, :, 0], catv, grad * 0.01)
    np.add.at(hist[1, :, 1], catv, 1.0)
    np.add.at(hist[1, :, 2], catv, 1.0)
    # NOTE: feature 1's histogram must use the same grad rows for a shared
    # parent; scale only feature 1's association, not its totals
    np.add.at(hist[1, :, 0], catv, grad * 0.99)  # totals now match feature 0
    cand = best_split(
        jnp.asarray(planes(hist), jnp.float32),
        jnp.float32(grad.sum()),
        jnp.float32(n),
        jnp.float32(n),
        jnp.asarray([16, 10], jnp.int32),
        jnp.asarray([-1, -1], jnp.int32),
        jnp.ones((2,), bool),
        lambda_l1=0.0,
        lambda_l2=1.0,
        min_data_in_leaf=5,
        min_sum_hessian_in_leaf=1e-3,
        min_gain_to_split=0.0,
        is_cat=jnp.asarray([False, True]),
        cat_params=CatParams(min_data_per_group=1),
    )
    assert int(cand.feature) == 0
    assert not bool(cand.is_cat)
    assert float(cand.gain) > 0
    assert int(cand.bin) == 7
