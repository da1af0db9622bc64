"""Quantized-gradient training (reference: GradientDiscretizer,
src/treelearner/gradient_discretizer.cpp; config use_quantized_grad)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.ops.quantize import quantize_gradients  # noqa: E402


def test_quantize_grid_and_scales():
    rng = np.random.default_rng(0)
    g = rng.normal(size=512).astype(np.float32)
    h = np.abs(rng.normal(size=512)).astype(np.float32) + 0.1
    qg, qh, gs, hs = quantize_gradients(
        jnp.asarray(g), jnp.asarray(h), 0, 0,
        num_bins=4, stochastic=False,
    )
    qg, qh = np.asarray(qg), np.asarray(qh)
    g_scale, h_scale = float(gs), float(hs)
    assert g_scale == pytest.approx(np.abs(g).max() / 2)  # num_bins/2
    assert h_scale == pytest.approx(h.max() / 4)
    # every quantized value sits on the integer grid of its scale
    assert np.allclose(np.round(qg / g_scale), qg / g_scale, atol=1e-4)
    assert np.allclose(np.round(qh / h_scale), qh / h_scale, atol=1e-4)
    # deterministic rounding: |error| <= scale/2 (+ eps)
    assert np.abs(qg - g).max() <= g_scale * 0.5 + 1e-5
    assert np.abs(qh - h).max() <= h_scale * 0.5 + 1e-5


def test_stochastic_rounding_unbiased():
    g = jnp.full((20000,), 0.3, jnp.float32)
    h = jnp.ones((20000,), jnp.float32)
    qg, _, _, _ = quantize_gradients(
        g, h, 1, 0, num_bins=4, stochastic=True
    )
    # E[q] == g under stochastic rounding (reference stochastic_rounding)
    assert float(np.asarray(qg).mean()) == pytest.approx(0.3, rel=0.05)


@pytest.mark.parametrize("renew", [False, True])
def test_quantized_training_close_to_exact(renew):
    rng = np.random.default_rng(0)
    n = 3000
    X = rng.normal(size=(n, 6))
    y = X[:, 0] * 2 - X[:, 1] + 0.5 * X[:, 2] + rng.normal(scale=0.1, size=n)
    base = {
        "objective": "regression",
        "num_leaves": 31,
        "min_data_in_leaf": 10,
        "verbosity": -1,
    }
    exact = lgb.train(base, lgb.Dataset(X, y), 20)
    quant = lgb.train(
        {**base, "use_quantized_grad": True, "num_grad_quant_bins": 8,
         "quant_train_renew_leaf": renew},
        lgb.Dataset(X, y),
        20,
    )
    mse_exact = float(np.mean((exact.predict(X) - y) ** 2))
    mse_quant = float(np.mean((quant.predict(X) - y) ** 2))
    assert mse_quant < np.var(y) * 0.1  # genuinely learns
    assert mse_quant < mse_exact * 3.0 + 1e-3  # near the exact model
    if renew:
        # mechanism check: with renewal, the first tree's leaf values are
        # the TRUE-gradient optima -sum_g/(sum_h + l2) over each leaf
        # (RenewIntGradTreeOutput), not the quantized-gradient optima
        b1 = lgb.train(
            {**base, "use_quantized_grad": True, "num_grad_quant_bins": 8,
             "quant_train_renew_leaf": True, "learning_rate": 0.7,
             "boost_from_average": False},  # keep leaf values bias-free
            lgb.Dataset(X, y),
            1,
        )
        tree = b1.models_[0]
        leaves = b1.predict(X, pred_leaf=True)[:, 0]
        grad = -y  # L2 gradients at score 0
        for leaf in range(tree.num_leaves):
            sel = leaves == leaf
            if sel.sum() == 0:
                continue
            want = -grad[sel].sum() / (sel.sum() + 0.0) * 0.7  # lambda_l2=0
            assert tree.leaf_value[leaf] == pytest.approx(want, rel=1e-3), leaf


def test_quantized_binary():
    rng = np.random.default_rng(1)
    n = 2000
    X = rng.normal(size=(n, 5))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    b = lgb.train(
        {
            "objective": "binary",
            "verbosity": -1,
            "use_quantized_grad": True,
            "quant_train_renew_leaf": True,
            "num_leaves": 15,
        },
        lgb.Dataset(X, y),
        15,
    )
    acc = ((b.predict(X) > 0.5) == y).mean()
    assert acc > 0.9


# ---------------------------------------------------------------------------
# PR 33: the rounding draws are a stateless mix of (seed, tree, row, stream),
# quantized training scans on device, and the segment path takes the integer
# kernels for it
# ---------------------------------------------------------------------------


def _binary_table(n=4000, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] + rng.normal(scale=0.5, size=n) > 0)
    return X, y.astype(np.float32)


_QUANT = {
    "objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
    "verbosity": -1, "use_quantized_grad": True,
    "quant_train_renew_leaf": True, "seed": 7,
}


def _trees(booster):
    return booster.dump_model()["tree_info"]


def _structure(tree, out=None):
    out = [] if out is None else out
    if "split_index" in tree:
        out.append((tree["split_feature"], tree["threshold"], tree["internal_count"]))
        _structure(tree["left_child"], out)
        _structure(tree["right_child"], out)
    else:
        out.append((tree["leaf_count"],))
    return out


def test_no_random_key_is_left_in_quantize_gradients():
    """The draws come from integer arithmetic alone: no PRNG primitive in the
    traced function, and no key among its operands."""
    jaxpr = jax.make_jaxpr(
        lambda g, h, s, t: quantize_gradients(g, h, s, t, num_bins=4, stochastic=True)
    )(jnp.zeros(64), jnp.ones(64), np.uint32(3), np.int32(5))
    text = str(jaxpr)
    assert "random" not in text and "threefry" not in text, text


def test_draws_depend_on_seed_tree_row_and_stream_only():
    from lightgbm_tpu.ops.quantize import rounding_uniforms

    u = np.asarray(rounding_uniforms(np.uint32(11), np.int32(4), 4096, 0))
    # a prefix of a longer array: the draw of row r does not depend on N
    assert np.array_equal(u[:1000], np.asarray(rounding_uniforms(np.uint32(11), np.int32(4), 1000, 0)))
    for other in ((12, 4, 0), (11, 5, 0), (11, 4, 1)):
        v = np.asarray(rounding_uniforms(np.uint32(other[0]), np.int32(other[1]), 4096, other[2]))
        assert (u != v).mean() > 0.99
    assert 0.0 <= u.min() and u.max() < 1.0 and abs(u.mean() - 0.5) < 0.02


@pytest.mark.parametrize("steps", [8, 4])
def test_scan_and_per_iteration_grow_identical_trees(steps):
    """train_steps_per_launch N against 1: the same levels, so the same trees
    to the last bit (the serial loop and the scan body call the same two
    functions with the same tree index)."""
    X, y = _binary_table()
    serial = lgb.train({**_QUANT, "train_steps_per_launch": 1}, lgb.Dataset(X, y), 8)
    scanned = lgb.train({**_QUANT, "train_steps_per_launch": steps}, lgb.Dataset(X, y), 8)
    assert len(_trees(serial)) == 8
    assert _trees(serial) == _trees(scanned)


def test_quantized_booster_is_launch_eligible():
    from lightgbm_tpu.boosting.launch import launch_ineligible_reason, resolve_launch_steps

    X, y = _binary_table(600)
    b = lgb.Booster({**_QUANT, "train_steps_per_launch": 8}, lgb.Dataset(X, y))
    assert launch_ineligible_reason(b) is None
    assert resolve_launch_steps(b, has_eval_work=False) == 8


@pytest.mark.parametrize("launch", [1, 4], ids=["per-iteration", "scan"])
def test_one_device_and_four_grow_the_same_structure(launch, monkeypatch):
    """tree_learner=data over four devices against one: the draws are the
    global row's, the scales the global maxima, so the levels are the same
    and the trees have the same structure (the f32 sums of grid multiples
    associate differently across shards, so gains may differ in the last
    bits)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    monkeypatch.setenv("LGBM_TPU_FORCE_NDEV", "4")
    X, y = _binary_table()
    one = lgb.train({**_QUANT, "train_steps_per_launch": launch}, lgb.Dataset(X, y), 4)
    four = lgb.train(
        {**_QUANT, "train_steps_per_launch": launch, "tree_learner": "data"},
        lgb.Dataset(X, y), 4)
    assert four._mesh is not None and four._mesh.size == 4
    a = [_structure(t["tree_structure"]) for t in _trees(one)]
    b = [_structure(t["tree_structure"]) for t in _trees(four)]
    assert a == b
    np.testing.assert_allclose(one.predict(X), four.predict(X), rtol=1e-4, atol=1e-6)


def test_levels_do_not_depend_on_the_layout():
    """The same rows sharded over four devices: the same integer levels and
    scales, bit for bit."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    rng = np.random.default_rng(3)
    g = rng.normal(size=4096).astype(np.float32)
    h = (rng.random(4096) * 0.25).astype(np.float32)
    one = quantize_gradients(jnp.asarray(g), jnp.asarray(h), np.uint32(9), np.int32(2))
    rows = NamedSharding(Mesh(np.array(jax.devices()[:4]), ("data",)), P("data"))
    four = quantize_gradients(
        jax.device_put(g, rows), jax.device_put(h, rows), np.uint32(9), np.int32(2))
    for a, b in zip(one, four):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    k = np.asarray(one[0]) / float(one[2])
    assert np.abs(k - np.rint(k)).max() < 1e-5 and np.abs(k).max() <= 2


def test_segment_kernel_sums_quantized_levels_exactly(monkeypatch):
    """``seg_hist`` given scales takes the integer kernel (interpret mode
    here): every (feature, bin) sum is the integer bincount of the levels
    times the scale, one f32 rounding, bit for bit."""
    from lightgbm_tpu.ops.pallas import seg

    monkeypatch.setattr(seg, "_INTERPRET", True)
    rng = np.random.default_rng(5)
    n, f = 3000, 5
    n_pad = seg.padded_rows(n)
    bins = rng.integers(0, 256, size=(n, f)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = (rng.random(n) * 0.25).astype(np.float32)
    qg, qh, gs, hs = quantize_gradients(jnp.asarray(g), jnp.asarray(h), np.uint32(1), np.int32(0))
    packed = seg.pack_rows(jnp.asarray(bins), qg, qh, jnp.ones(n, jnp.float32), n_pad)
    got = np.asarray(seg.seg_hist(
        packed, jnp.asarray([0, n], jnp.int32), f=f, num_bins=256, n_pad=n_pad,
        quant_scales=(gs, hs)))
    kg = np.rint(np.asarray(qg) / float(gs)).astype(np.int64)
    kh = np.rint(np.asarray(qh) / float(hs)).astype(np.int64)
    assert np.abs(kg).max() <= 2 and kh.min() >= 0 and kh.max() <= 4
    for j in range(f):
        for plane, k, s in ((0, kg, gs), (1, kh, hs)):
            exact = np.bincount(bins[:, j], weights=k, minlength=256)
            assert np.array_equal(got[plane, j], exact.astype(np.float32) * np.float32(s))
        assert np.array_equal(got[2, j], np.bincount(bins[:, j], minlength=256))


def test_segment_path_takes_the_integer_kernels_for_quantized_gradients(monkeypatch):
    """hist_mode=seg with use_quantized_grad and no hist_method: the span
    args say int8, and the trees are those of the f32 sums of the same grid
    multiples (exact either way at this size)."""
    from lightgbm_tpu.ops.pallas import seg

    X, y = _binary_table(1500)
    params = {**_QUANT, "hist_mode": "seg", "grow_fused": "off", "num_leaves": 7}
    plain = lgb.train(params, lgb.Dataset(X, y), 2)
    args = plain._seg_span_args()
    assert args["grad_quant_bins"] == 4 and args["hist_int8"] is False  # no kernel off a TPU
    monkeypatch.setattr(seg, "_INTERPRET", True)
    jax.clear_caches()
    try:
        kernel = lgb.train(params, lgb.Dataset(X, y), 2)
        assert kernel._seg_span_args()["hist_int8"] is True
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    a = [_structure(t["tree_structure"]) for t in _trees(plain)]
    b = [_structure(t["tree_structure"]) for t in _trees(kernel)]
    assert a == b
    off = lgb.Booster({**params, "use_quantized_grad": False}, lgb.Dataset(X, y))
    assert off._seg_span_args()["grad_quant_bins"] == 0


def test_renewal_sums_equal_the_float64_sums_of_the_true_gradients():
    """The one-hot contraction of ``renew_leaf_values``: every leaf's sum of
    the true f32 gradients, at f32 accumulation's accuracy (each addend
    enters as its exact three-term bf16 split)."""
    from lightgbm_tpu.ops.quantize import _leaf_sums

    rng = np.random.default_rng(2)
    n, leaves = 60_000, 255
    leaf_id = rng.integers(0, leaves, n).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = (rng.random(n) * 0.25).astype(np.float32)
    G, H = _leaf_sums(jnp.asarray(leaf_id), jnp.asarray(g), jnp.asarray(h), leaves)
    assert G.shape == (leaves,) and G.dtype == jnp.float32
    G64 = np.bincount(leaf_id, weights=g.astype(np.float64), minlength=leaves)
    H64 = np.bincount(leaf_id, weights=h.astype(np.float64), minlength=leaves)
    np.testing.assert_allclose(np.asarray(G), G64, atol=2e-5)
    np.testing.assert_allclose(np.asarray(H), H64, rtol=2e-6)


def test_renewal_addends_are_three_exact_bfloat16_terms():
    """The masks' split: each term a bfloat16 value, the three adding up to
    the f32 addend exactly, over 80 binades (no bf16 round trip for a
    compiler to elide)."""
    from lightgbm_tpu.ops.quantize import _bf16_head

    rng = np.random.default_rng(4)
    v = jnp.asarray((rng.normal(size=50_000) * np.exp(rng.normal(size=50_000) * 8))
                    .astype(np.float32))
    hi = _bf16_head(v)
    lo = _bf16_head(v - hi)
    lo2 = v - hi - lo
    for term in (hi, lo, lo2):
        assert bool((term.astype(jnp.bfloat16).astype(jnp.float32) == term).all())
    assert bool(((hi + lo) + lo2 == v).all())
