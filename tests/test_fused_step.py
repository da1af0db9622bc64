"""Fused grow-step oracle parity (ops/pallas/grow_step.py).

Off-TPU, ``fused_grow_step`` lowers to the SAME XLA composition the
two-launch grower path runs (sequential stable-sort partitions + local
election + masked reference histogram), so CPU training with
``grow_fused`` on must be byte-identical to the oracle — the full model
dump is compared, not just structure.  The interpret-mode tests exercise
the actual Pallas kernel; its bf16 three-term histogram differs from the
f32 reference at ~1e-6, which can flip near-tie splits on hard data, so
those tests use well-separated data / few rounds and compare structure
plus predictions.

Engagement note: ``grow_fused='auto'`` resolves to the seg fast path,
which off-TPU must be requested explicitly (``hist_mode='seg'``) — the
booster's auto hist mode only picks seg on a TPU backend.

Trace-staleness note: ``grow_step._INTERPRET`` is read at TRACE time.
The interpret tests use distinctive shapes/params so no earlier test in
the process has already cached a non-interpret trace for the same
GrowerParams (which would silently run the oracle instead).
"""

import numpy as np
import pytest
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.pallas import grow_step
from lightgbm_tpu.ops.pallas.seg import pack_rows, padded_rows

BASE = dict(
    objective="binary", num_leaves=31, learning_rate=0.2, hist_mode="seg",
    min_data_in_leaf=5, verbosity=-1, deterministic=True, seed=7,
)

_STRUCT = (
    "split_feature=", "threshold=", "decision_type=", "left_child=",
    "right_child=", "num_leaves=",
)


def _trees(booster):
    """Model dump sliced to the trees section (the trailing parameters
    echo differs by construction when only grow_fused differs)."""
    s = booster.model_to_string()
    return s[s.index("Tree=0"):s.index("end of trees")]


def _structure(booster):
    return [l for l in _trees(booster).splitlines() if l.startswith(_STRUCT)]


@pytest.fixture(scope="module")
def xy():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(2000, 12)).astype(np.float32)
    y = (
        X[:, 0] + 0.5 * X[:, 1] ** 2 + 0.1 * rng.normal(size=2000) > 0.4
    ).astype(np.float32)
    return X, y


def _fit(X, y, rounds=8, dataset_kw=None, **over):
    p = {**BASE, **over}
    ds = lgb.Dataset(X, label=y, **(dataset_kw or {}))
    return lgb.train(p, ds, num_boost_round=rounds)


def test_fused_serial_parity(xy):
    X, y = xy
    ref = _fit(X, y, grow_fused="off")
    got = _fit(X, y, grow_fused="on")
    assert got._grower_params.grow_fused  # engagement, not a vacuous pass
    assert not ref._grower_params.grow_fused
    assert _trees(got) == _trees(ref)


@pytest.mark.parametrize("k", [2, 4])
def test_fused_batched_parity(xy, k):
    X, y = xy
    kw = dict(leaf_batch=k, leaf_batch_adaptive=False)
    ref = _fit(X, y, grow_fused="off", **kw)
    got = _fit(X, y, grow_fused="on", **kw)
    assert got._grower_params.leaf_batch == k
    assert _trees(got) == _trees(ref)


def test_fused_auto_resolves_on_seg(xy):
    X, y = xy
    auto = _fit(X, y, grow_fused="auto")
    assert auto._grower_params.grow_fused
    assert _trees(auto) == _trees(_fit(X, y, grow_fused="on"))


def test_fused_batched_matches_serial_structure(xy):
    """K-batched fused growth commits the same structure serial growth
    does (values can differ only if structure did — require both equal)."""
    X, y = xy
    serial = _fit(X, y, grow_fused="on")
    k4 = _fit(X, y, grow_fused="on", leaf_batch=4, leaf_batch_adaptive=False)
    assert _structure(k4) == _structure(serial)


def test_fused_inert_on_ordered_mode(xy):
    """grow_fused='on' without the seg fast path must not engage or
    perturb training — the ordered-mode dump stays byte-identical."""
    X, y = xy
    ref = _fit(X, y, hist_mode="ordered", grow_fused="off")
    got = _fit(X, y, hist_mode="ordered", grow_fused="on")
    assert _trees(got) == _trees(ref)


def test_fused_categorical_parity(xy):
    X, y = xy
    Xc = X.copy()
    rng = np.random.default_rng(3)
    Xc[:, 0] = rng.integers(0, 12, size=len(y)).astype(np.float32)
    kw = dict(dataset_kw=dict(categorical_feature=[0]))
    assert _trees(_fit(Xc, y, grow_fused="on", **kw)) == _trees(
        _fit(Xc, y, grow_fused="off", **kw)
    )


def test_fused_monotone_parity(xy):
    X, y = xy
    mc = [1, 0, -1] + [0] * (X.shape[1] - 3)
    assert _trees(_fit(X, y, grow_fused="on", monotone_constraints=mc)) == (
        _trees(_fit(X, y, grow_fused="off", monotone_constraints=mc))
    )


def test_fused_forced_splits_parity(xy, tmp_path):
    X, y = xy
    fs = tmp_path / "forced.json"
    fs.write_text('{"feature": 0, "threshold": 0.0, "left": '
                  '{"feature": 1, "threshold": 0.5}}')
    kw = dict(forcedsplits_filename=str(fs))
    assert _trees(_fit(X, y, grow_fused="on", **kw)) == _trees(
        _fit(X, y, grow_fused="off", **kw)
    )


def test_fused_quantized_parity(xy):
    X, y = xy
    kw = dict(use_quantized_grad=True)
    assert _trees(_fit(X, y, grow_fused="on", **kw)) == _trees(
        _fit(X, y, grow_fused="off", **kw)
    )


def test_fused_tree_learner_data_parity(xy):
    X, y = xy
    kw = dict(tree_learner="data", leaf_batch=2, leaf_batch_adaptive=False)
    assert _trees(_fit(X, y, grow_fused="on", **kw)) == _trees(
        _fit(X, y, grow_fused="off", **kw)
    )


def test_fused_no_recompile_after_warmup(xy):
    X, y = xy
    params = {**BASE, "grow_fused": "on", "leaf_batch": 2,
              "leaf_batch_adaptive": False}
    booster = lgb.Booster(params, lgb.Dataset(X, label=y))
    for _ in range(2):
        booster.update()
    warm = lgb.compile_count()
    warm_labels = dict(lgb.compile_counts_by_label())
    for _ in range(6):
        booster.update()
    assert lgb.compile_count() == warm, (
        f"retraced after warmup: {lgb.compile_counts_by_label()} "
        f"vs {warm_labels}"
    )


def test_fused_kernel_interpret_matches_oracle():
    """The actual Pallas kernel (interpret mode off-TPU) vs the XLA
    oracle, standalone: adjacent non-tile-aligned K=2 windows.  Partition
    state and split decisions must be bit-equal; the histogram is bf16
    three-term vs f32 reference, so values compare at kernel tolerance."""
    rng = np.random.default_rng(5)
    f, n = 11, 5000
    n_pad = padded_rows(n)
    bins = rng.integers(0, 256, size=(n, f)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.random(n).astype(np.float32) + 0.5
    m = np.ones(n, np.float32)
    seg = pack_rows(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(m),
        n_pad,
    )
    catm = jnp.zeros((2, 1), jnp.float32)
    kw = dict(f=f, num_bins=256, n_pad=n_pad)
    args = (
        jnp.asarray([37, 37 + 1900], jnp.int32),  # adjacent, unaligned
        jnp.asarray([1900, 2300], jnp.int32),
        jnp.asarray([3, 7], jnp.int32),
        jnp.asarray([120, 80], jnp.int32),
        jnp.asarray([0, 1], jnp.int32),
        jnp.asarray([-1, 200], jnp.int32),
        jnp.asarray([0, 0], jnp.int32),
        catm,
    )
    want = grow_step.fused_grow_step(seg, *args, **kw)
    assert not grow_step._INTERPRET
    grow_step._INTERPRET = True
    try:
        got = grow_step.fused_grow_step(seg, *args, **kw)
    finally:
        grow_step._INTERPRET = False
    for i, name in enumerate(("seg", "nl", "nr", "child_start", "child_cnt")):
        assert np.array_equal(np.asarray(got[i]), np.asarray(want[i])), name
    np.testing.assert_allclose(
        np.asarray(got[5]), np.asarray(want[5]), rtol=1e-3, atol=1e-3
    )


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("f,num_bins,k,n", [
    (11, 256, 2, 2600), (28, 256, 1, 2600), (28, 128, 4, 2600),
    (67, 256, 1, 2600), (7, 100, 2, 2600),
    # the elected child takes long steps and a ragged tail (n over 3 x STEP)
    (28, 256, 1, 13000), (11, 256, 2, 26000), (7, 100, 3, 30000),
])
def test_fused_histogram_is_the_two_launch_histogram(f, num_bins, k, n,
                                                     quantized, full_onehot):
    """The fused kernel's phase 3 is ``seg._hist_window`` reading through
    the output alias, two staging slots deep: its histogram of the elected
    child equals, bit for bit, the two-launch kernel's over the partitioned
    matrix (both run the one function, long steps and tail alike), and the
    H = 1 form's (int8 exactly; bf16 to rounding)."""
    from lightgbm_tpu.ops.pallas.seg import (
        QMAX, hist_bpad, hist_ngroups, seg_hist_pallas_batch,
    )

    rng = np.random.default_rng(11 + f)
    n_pad = padded_rows(n)
    bins = rng.integers(0, num_bins, size=(n, f)).astype(np.int32)
    seg = pack_rows(
        jnp.asarray(bins), jnp.asarray(rng.normal(size=n).astype(np.float32)),
        jnp.asarray(rng.random(n).astype(np.float32) * 0.24 + 0.01),
        jnp.asarray((rng.random(n) < 0.9).astype(np.float32)), n_pad,
    )
    # K adjacent windows, none on a 128-column boundary, the last one empty
    # when K > 1
    cuts = np.linspace(37, n - 50, k + 1).astype(int)
    rows = [
        (cuts[i], 0 if (k > 1 and i == k - 1) else cuts[i + 1] - cuts[i],
         i % f, num_bins // 3 + 7 * i, 0, -1, 0, 0)
        for i in range(k)
    ]
    scal = jnp.asarray(rows, jnp.int32)
    catm = jnp.zeros((k, 256), jnp.float32)
    scales = jnp.asarray([5.0 / QMAX, 0.25 / QMAX], jnp.float32)
    live = jnp.ones((hist_ngroups(f, hist_bpad(num_bins)),), jnp.int32)
    kw = dict(f=f, num_bins=num_bins, n_pad=n_pad, quantized=quantized,
              interpret=True)

    def run():
        seg2, dec, hist = grow_step.fused_grow_step_pallas(
            seg, scal, catm, scales, live, use_cat=False, **kw)
        return seg2, dec, np.asarray(hist)

    seg2, dec, hist = run()
    two_launch = np.asarray(seg_hist_pallas_batch(
        seg2, dec[:, 2:4], scales, live, **kw))
    np.testing.assert_array_equal(hist, two_launch)
    assert hist[0].any() and (k == 1 or not hist[k - 1].any())
    if n > 2600:  # the smaller child is long enough for a long step
        assert int(np.asarray(dec)[0, 3]) > 2048
    with full_onehot():
        seg1, dec1, hist1 = run()
    np.testing.assert_array_equal(np.asarray(seg1), np.asarray(seg2))
    np.testing.assert_array_equal(np.asarray(dec1), np.asarray(dec))
    if quantized:
        np.testing.assert_array_equal(hist, hist1)
    else:
        np.testing.assert_allclose(
            hist, hist1, rtol=0, atol=1e-6 * np.abs(hist1).max())


def test_fused_booster_interpret_structure():
    """End-to-end through the booster with the real kernel (interpret):
    distinctive shapes/params guarantee a fresh trace (see module note);
    well-separated data keeps near-tie gains out of bf16 flip range, so
    structure parity and prediction closeness must hold for serial and
    K=2."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(1200, 10)).astype(np.float32)
    y = (
        X[:, 0] + 0.6 * X[:, 1] + 0.1 * rng.normal(size=1200) > 0.2
    ).astype(np.float32)

    def run(**over):
        p = {**BASE, "num_leaves": 15, "min_data_in_leaf": 20}
        p.update(over)
        b = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=3)
        return _structure(b), b.predict(X[:200])

    s_ref, p_ref = run(grow_fused="off")
    s_ref2, _ = run(grow_fused="off", leaf_batch=2, leaf_batch_adaptive=False)
    assert not grow_step._INTERPRET
    grow_step._INTERPRET = True
    try:
        s1, p1 = run(grow_fused="on")
        s2, p2 = run(grow_fused="on", leaf_batch=2, leaf_batch_adaptive=False)
    finally:
        grow_step._INTERPRET = False
    assert s1 == s_ref
    assert s2 == s_ref2
    np.testing.assert_allclose(p1, p_ref, atol=1e-6)
    np.testing.assert_allclose(p2, p_ref, atol=1e-6)
