"""Monotone constraint tests: basic vs intermediate vs advanced
(reference: src/treelearner/monotone_constraints.hpp — BasicLeafConstraints
:465, IntermediateLeafConstraints :516, AdvancedLeafConstraints :858).

Property: predictions must be monotone along constrained features for ALL
methods.  Quality: intermediate's output-based bounds are tighter than
basic's midpoint bounds, and advanced's per-threshold slice bounds are less
restrictive than intermediate's whole-leaf scalars, so training loss must
not degrade along the ladder (the reference documents each step as an
accuracy upgrade)."""

import numpy as np
import pytest

import lightgbm_tpu as lgb

from .planes import planes


def _make_data(seed=3, n=4000):
    rng = np.random.default_rng(seed)
    X = rng.uniform(-3, 3, size=(n, 4))
    y = (
        2.0 * X[:, 0]
        + np.sin(2 * X[:, 1])
        - 1.5 * X[:, 2]
        + 0.7 * X[:, 3] ** 2
        + rng.normal(scale=0.2, size=n)
    )
    return X, y


def _check_monotone(booster, X, feat, direction, grid=21):
    """Sweep one feature over its range for a batch of rows; prediction must
    move with `direction` pointwise."""
    rows = X[:64].copy()
    vals = np.linspace(X[:, feat].min(), X[:, feat].max(), grid)
    preds = []
    for v in vals:
        r = rows.copy()
        r[:, feat] = v
        preds.append(booster.predict(r))
    P = np.stack(preds)  # [grid, rows]
    diffs = np.diff(P, axis=0) * direction
    assert (diffs >= -1e-9).all(), (
        f"feature {feat} violates monotonicity: worst {diffs.min()}"
    )


@pytest.mark.parametrize("method", ["basic", "intermediate", "advanced"])
def test_monotone_property(method):
    X, y = _make_data()
    params = {
        "objective": "regression",
        "num_leaves": 31,
        "verbosity": -1,
        "metric": "none",
        "monotone_constraints": [1, 0, -1, 0],
        "monotone_constraints_method": method,
    }
    b = lgb.train(params, lgb.Dataset(X, y, params=params), 25)
    _check_monotone(b, X, 0, +1)
    _check_monotone(b, X, 2, -1)


def test_intermediate_not_worse_than_basic():
    X, y = _make_data()
    out = {}
    for method in ("basic", "intermediate"):
        params = {
            "objective": "regression",
            "num_leaves": 63,
            "verbosity": -1,
            "metric": "none",
            "monotone_constraints": [1, 0, -1, 0],
            "monotone_constraints_method": method,
        }
        b = lgb.train(params, lgb.Dataset(X, y, params=params), 40)
        mse = float(np.mean((b.predict(X) - y) ** 2))
        out[method] = mse
    # tighter bounds must not lose accuracy (allow 2% noise margin)
    assert out["intermediate"] <= out["basic"] * 1.02, out


def test_advanced_not_worse_than_intermediate():
    """Advanced's per-threshold slice bounds usually relax the scan
    constraints vs intermediate's whole-leaf scalars, but not always:
    advanced also binds against DISTANT ordered leaves that intermediate's
    touch-propagation never reached.  The loss comparison is therefore a
    quality regression check on this data/seed, not a mathematical
    invariant."""
    X, y = _make_data()
    out = {}
    for method in ("intermediate", "advanced"):
        params = {
            "objective": "regression",
            "num_leaves": 63,
            "verbosity": -1,
            "metric": "none",
            "monotone_constraints": [1, 0, -1, 0],
            "monotone_constraints_method": method,
        }
        b = lgb.train(params, lgb.Dataset(X, y, params=params), 40)
        mse = float(np.mean((b.predict(X) - y) ** 2))
        out[method] = mse
    assert out["advanced"] <= out["intermediate"] * 1.02, out


def test_advanced_rejects_wide_bins():
    """advanced + max_bin > 256 would materialize tens-of-GB per-threshold
    bound planes; the config rejects the combination with a clear error
    instead of OOMing mid-train (r4 ADVICE)."""
    X, y = _make_data()
    params = {
        "objective": "regression",
        "verbosity": -1,
        "max_bin": 1024,
        "monotone_constraints": [1, 0, -1, 0],
        "monotone_constraints_method": "advanced",
    }
    with pytest.raises(ValueError, match="advanced"):
        lgb.train(params, lgb.Dataset(X, y, params=params), 2)
    # without constraints the method param is inert and wide bins are fine
    params.pop("monotone_constraints")
    lgb.train(params, lgb.Dataset(X, y, params=params), 2)


def test_advanced_monotone_with_path_smooth():
    """Smoothing is applied BEFORE the monotone clip at finalize; the
    advanced bound recompute must see smoothed outputs or cross-leaf
    ordering can break."""
    X, y = _make_data(seed=9, n=2500)
    params = {
        "objective": "regression",
        "num_leaves": 31,
        "verbosity": -1,
        "metric": "none",
        "monotone_constraints": [1, 0, -1, 0],
        "monotone_constraints_method": "advanced",
        "path_smooth": 5.0,
        "min_data_in_leaf": 5,
    }
    b = lgb.train(params, lgb.Dataset(X, y, params=params), 25)
    _check_monotone(b, X, 0, +1)
    _check_monotone(b, X, 2, -1)


def test_advanced_monotone_with_categoricals():
    """Advanced mode with a categorical feature in the mix: categorical
    splits keep the parent box, numeric monotonicity still holds."""
    rng = np.random.default_rng(11)
    n = 2500
    X = np.column_stack(
        [
            rng.uniform(-3, 3, size=n),
            rng.integers(0, 5, size=n).astype(float),
            rng.uniform(-3, 3, size=n),
        ]
    )
    y = 2.0 * X[:, 0] + (X[:, 1] == 2) * 1.5 - X[:, 2] + rng.normal(
        scale=0.2, size=n
    )
    params = {
        "objective": "regression",
        "num_leaves": 31,
        "verbosity": -1,
        "metric": "none",
        "monotone_constraints": [1, 0, -1],
        "monotone_constraints_method": "advanced",
        "categorical_feature": [1],
    }
    b = lgb.train(params, lgb.Dataset(X, y, params=params), 25)
    _check_monotone(b, X, 0, +1)
    _check_monotone(b, X, 2, -1)


# ---- monotone_penalty (reference monotone_constraints.hpp:357-366) -------

def _dup_feature_hist(seed=0, n=2000, b=32):
    """Two IDENTICAL feature columns -> exactly tied best gains, so any
    penalty on one feature must flip the argmax to the other."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    bins = rng.integers(0, b, size=n)
    g = rng.normal(size=n).astype(np.float32) - 0.3 * (bins > b // 2)
    h = np.ones(n, np.float32)
    hist = np.zeros((2, b, 3), np.float32)
    for j in range(2):
        np.add.at(hist[j, :, 0], bins, g)
        np.add.at(hist[j, :, 1], bins, h)
        np.add.at(hist[j, :, 2], bins, 1.0)
    parent = hist[0].sum(axis=0)
    return (
        jnp.asarray(planes(hist)),
        parent,
        jnp.full((2,), b, np.int32),
        jnp.full((2,), -1, np.int32),
        jnp.ones((2,), bool),
    )


_BS_HP = dict(
    lambda_l1=0.0,
    lambda_l2=0.01,
    min_data_in_leaf=5,
    min_sum_hessian_in_leaf=1e-3,
    min_gain_to_split=0.0,
)


def test_penalized_split_loses_to_unpenalized_at_matched_gain():
    """feature 0 is monotone-constrained, feature 1 is its exact copy but
    unconstrained: with monotone_penalty the tie must break to feature 1
    (serial argmax alone would pick feature 0)."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.split import best_split

    hist, parent, num_bins, nan_bins, mask = _dup_feature_hist()
    mono = jnp.asarray([1, 0], jnp.int8)
    base = best_split(
        hist, parent[0], parent[1], parent[2], num_bins, nan_bins, mask,
        monotone=mono, **_BS_HP,
    )
    assert int(base.feature) == 0  # tie -> lowest index without penalty
    pen = best_split(
        hist, parent[0], parent[1], parent[2], num_bins, nan_bins, mask,
        monotone=mono, monotone_penalty=1.0,
        leaf_depth=jnp.asarray(0, jnp.int32), **_BS_HP,
    )
    assert int(pen.feature) == 1
    # the winning (unpenalized) candidate keeps its full gain
    np.testing.assert_allclose(float(pen.gain), float(base.gain), rtol=1e-6)


def test_monotone_penalty_decays_with_depth():
    """The penalty factor is 1 - penalty/2^depth (penalty <= 1): deeper
    leaves are penalized less, converging to the unpenalized gain."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.split import best_split

    hist, parent, num_bins, nan_bins, mask = _dup_feature_hist(seed=1)
    mono = jnp.asarray([1, 1], jnp.int8)  # both constrained -> both penalized
    base = best_split(
        hist, parent[0], parent[1], parent[2], num_bins, nan_bins, mask,
        monotone=mono, **_BS_HP,
    )
    gains = []
    for depth in (0, 1, 4):
        c = best_split(
            hist, parent[0], parent[1], parent[2], num_bins, nan_bins, mask,
            monotone=mono, monotone_penalty=1.0,
            leaf_depth=jnp.asarray(depth, jnp.int32), **_BS_HP,
        )
        gains.append(float(c.gain))
    assert gains[0] < gains[1] < gains[2] <= float(base.gain) + 1e-6
    # depth 0 -> children at depth 1 -> factor 1 - 1/2 = 0.5
    np.testing.assert_allclose(gains[0], 0.5 * float(base.gain), rtol=1e-5)


def test_monotone_penalty_e2e_still_monotone():
    X, y = _make_data()
    params = {
        "objective": "regression",
        "num_leaves": 31,
        "verbosity": -1,
        "metric": "none",
        "monotone_constraints": [1, 0, -1, 0],
        "monotone_penalty": 1.5,
        "min_data_in_leaf": 5,
    }
    b = lgb.train(params, lgb.Dataset(X, y, params=params), 15)
    assert len(b.models_) == 15
    _check_monotone(b, X, 0, +1)
    _check_monotone(b, X, 2, -1)
