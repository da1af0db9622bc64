"""The histogram's form: three [F, B] planes, stat axis first.

1. The plane-form split scan (ops/split.py::best_split on [3, F, B]) returns
   the same SplitCandidate, bit for bit, as the scan it replaced did on the
   record form [F, B, 3] (tests/ref_best_split_records.py: that function,
   verbatim) — numeric, NaN bin with default_left, categorical, EFB
   ``bundle_end``, monotone basic, ``with_margin``.
2. The grower's loop carries ``hist_buf`` as [L + 1, 3, F, B] (a spare row
   takes the writes of a step that does not split), no value of the traced
   program keeps the stat axis last, and the tree grown is the one the
   commit before grew on the same rows.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.grower import GrowerParams, grow_tree
from lightgbm_tpu.ops.split import CatParams, best_split

from .planes import planes
from .ref_best_split_records import best_split_records

_HP = dict(
    lambda_l1=0.0, lambda_l2=0.5, min_data_in_leaf=5,
    min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
)


def _records(seed, f=6, b=32, n=3000, nan_every=0):
    """Seeded [F, B, 3] float32 histogram of n rows, its parent sums and
    bin tables; ``nan_every`` gives every such feature a NaN bin (its last)
    that holds a good share of the rows."""
    rng = np.random.default_rng(seed)
    num_bins = rng.integers(b // 2, b + 1, size=f).astype(np.int32)
    nan_bins = np.full(f, -1, np.int32)
    if nan_every:
        nan_bins[::nan_every] = num_bins[::nan_every] - 1
    g = rng.normal(size=n).astype(np.float32)
    h = (rng.random(n) + 0.1).astype(np.float32)
    hist = np.zeros((f, b, 3), np.float32)
    for j in range(f):
        bins = rng.integers(0, num_bins[j], size=n)
        if nan_bins[j] >= 0:
            bins[rng.random(n) < 0.2] = nan_bins[j]
        shift = 0.4 * (bins > num_bins[j] // 2) * (j % 3 - 1)
        np.add.at(hist[j, :, 0], bins, g + shift.astype(np.float32))
        np.add.at(hist[j, :, 1], bins, h)
        np.add.at(hist[j, :, 2], bins, 1.0)
    parent = hist[0].sum(axis=0)
    return hist, parent, num_bins, nan_bins


def _bundle_end(f, b, num_bins):
    """Features 0 and 2 are EFB planes of two members each: sub-ranges
    [1, mid] and [mid + 1, last] of the plane's bins."""
    end = np.full((f, b), -1, np.int32)
    for j in (0, 2):
        last, mid = int(num_bins[j]) - 1, int(num_bins[j]) // 2
        end[j, 1:mid + 1] = mid
        end[j, mid + 1:last + 1] = last
    return end


def _case_kwargs(case, f, b, num_bins):
    if case == "categorical":
        return dict(
            is_cat=jnp.asarray(np.arange(f) % 2 == 1),
            cat_params=CatParams(
                max_cat_to_onehot=4, max_cat_threshold=8, cat_l2=2.0,
                cat_smooth=3.0, min_data_per_group=10,
            ),
        )
    if case == "bundle_end":
        return dict(bundle_end=jnp.asarray(_bundle_end(f, b, num_bins)))
    if case == "monotone_basic":
        return dict(
            monotone=jnp.asarray(np.resize([1, -1, 0], f).astype(np.int8)),
            leaf_lb=jnp.float32(-0.05), leaf_ub=jnp.float32(0.08),
            parent_output=jnp.float32(0.01),
        )
    if case == "with_margin":
        return dict(with_margin=True)
    return {}


CASES = {
    # name: nan_every
    "numeric": 0,
    "nan_default_left": 2,
    "categorical": 3,
    "bundle_end": 0,
    "monotone_basic": 2,
    "with_margin": 2,
}


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("case", list(CASES))
def test_plane_scan_equals_record_scan_bit_for_bit(case, seed):
    f, b = 6, 32
    hist, parent, num_bins, nan_bins = _records(
        seed * 7 + len(case), f=f, b=b, nan_every=CASES[case]
    )
    kw = _case_kwargs(case, f, b, num_bins)
    # the case's own candidates must win: only they may compete
    usable = {
        "categorical": np.arange(f) % 2 == 1,
        "bundle_end": np.isin(np.arange(f), (0, 2)),
    }.get(case, np.ones(f, bool))
    args = (
        parent[0], parent[1], parent[2], jnp.asarray(num_bins),
        jnp.asarray(nan_bins), jnp.asarray(usable),
    )
    want = best_split_records(jnp.asarray(hist), *args, **_HP, **kw)
    got = best_split(jnp.asarray(planes(hist)), *args, **_HP, **kw)
    cand, got_cand = (want[0], got[0]) if case == "with_margin" else (want, got)
    pairs = dict(zip(cand._fields, zip(cand, got_cand)))
    if case == "with_margin":
        pairs["margin"] = (want[1], got[1])
    for name, (w, g) in pairs.items():
        w, g = np.asarray(w), np.asarray(g)
        assert w.dtype == g.dtype and w.shape == g.shape, name
        # equal as numbers: -0.0 against +0.0 is a masked sum's only liberty
        np.testing.assert_array_equal(g, w, err_msg=name)
    assert np.isfinite(float(cand.gain)), "the case must find a split"
    if case == "nan_default_left":
        assert bool(cand.default_left), "the seeded NaN bins must go left"
    if case == "categorical":
        assert bool(cand.is_cat)
    if case == "bundle_end":
        assert bool(cand.is_cat) and int(cand.feature) in (0, 2)


# ---------------------------------------------------------------- the carry


def _tree_problem(n=900, f=5, b=16, seed=3):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, b, size=(n, f)).astype(np.int32)
    grad = (
        rng.normal(size=n) + 0.8 * (bins[:, 1] > 9) - 0.5 * (bins[:, 3] < 4)
    ).astype(np.float32)
    hess = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    return bins, grad, hess


def _while_carries(jaxpr):
    """Avals that come out of every loop (``while``, or the ``scan`` a
    fori_loop of static bounds traces to) anywhere inside ``jaxpr``."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("while", "scan"):
            out += [v.aval for v in eqn.outvars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _while_carries(sub)
    return out


def _all_avals(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        out += [v.aval for v in eqn.outvars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _all_avals(sub)
    return out


# the tree the commit before PR 32 grew on _tree_problem() (hist_mode
# 'ordered', 8 leaves, min_data_in_leaf 20, lambda_l2 0.1; CPU backend)
_GOLDEN = dict(
    split_feature=[1, 3, 3, 0, 3, 0, 3],
    split_bin=[9, 3, 3, 9, 11, 5, 5],
    leaf_value=[
        0.47769054770469666, -0.1425171047449112, 0.3630298972129822,
        -0.6930056810379028, -0.2161489874124527, 0.17821356654167175,
        0.24611243605613708, -0.05347796902060509,
    ],
)


@pytest.mark.parametrize("hist_mode,leaf_batch", [
    ("ordered", 1), ("gather", 1), ("full", 1), ("seg", 1),
    ("seg", 2), ("ordered", 4),  # body_batched: the same update, K members
])
def test_grower_carries_planes_and_grows_the_golden_tree(hist_mode, leaf_batch):
    bins, grad, hess = _tree_problem()
    n, f = bins.shape
    L, B = 8, 16
    p = GrowerParams(
        num_leaves=L, max_bin=B, min_data_in_leaf=20, lambda_l2=0.1,
        hist_mode=hist_mode, hist_method="segment", hist_acc="bf16",
        leaf_batch=leaf_batch,
    )
    args = (
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.ones((n,), jnp.float32), jnp.full((f,), B, jnp.int32),
        jnp.full((f,), -1, jnp.int32), jnp.ones((f,), bool),
    )
    jaxpr = jax.make_jaxpr(lambda *a: grow_tree(*a, params=p))(*args).jaxpr
    shapes = [tuple(a.shape) for a in _while_carries(jaxpr)]
    assert (L + 1, 3, f, B) in shapes, shapes
    assert (L, f, B, 3) not in shapes
    stat_last = [
        tuple(a.shape) for a in _all_avals(jaxpr)
        if len(a.shape) >= 3 and a.shape[-1] == 3 and a.shape[-2] == B
    ]
    assert not stat_last, stat_last

    tree, leaf_id = grow_tree(*args, params=p)
    assert int(tree.num_leaves) == L
    np.testing.assert_array_equal(
        np.asarray(tree.split_feature), _GOLDEN["split_feature"])
    np.testing.assert_array_equal(
        np.asarray(tree.split_bin), _GOLDEN["split_bin"])
    np.testing.assert_allclose(
        np.asarray(tree.leaf_value), _GOLDEN["leaf_value"], rtol=2e-6, atol=0)
    assert np.bincount(np.asarray(leaf_id), minlength=L).min() >= 20
