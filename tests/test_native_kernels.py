"""Native (real-TPU) parity tier — `LGBM_TPU_NATIVE=1 pytest -m native_tpu`.

Every default-path Pallas kernel runs NATIVELY (no interpret mode) on the
attached chip against its oracle: the streaming partition kernel (column
read, bits-fed, K-batched), the seg histograms (bf16, int8, u16-wide), the
fused grow step (K=1 and K=4), the best-split scan as the grower calls it
(vmapped), the forest-walk predictor, and one end-to-end tree-structure
check of the fused default against the two-launch path.

The oracle for a dispatcher is the SAME dispatcher placed on the CPU
device: ``lax.platform_dependent`` lowers its ``default=`` XLA branch there
and its ``tpu=`` Pallas branch on the chip, so one function gives both
sides.  Off-TPU these tests are skipped (conftest); asking for the tier
without a TPU is an error.  Deviceless Mosaic compile coverage lives in
test_aot_mosaic.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.native_tpu


def _on_cpu(fn, *args, **kw):
    """Run ``fn`` with its operands and computation on the host CPU device
    (the dispatchers' XLA oracle side)."""
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        args = jax.tree.map(lambda a: jax.device_put(a, cpu), args)
        return jax.tree.map(np.asarray, fn(*args, **kw))


def _packed(seed=7, f=11, n=200_000, b=256, wide=False, g=None, h=None):
    from lightgbm_tpu.ops.pallas.seg import pack_rows, padded_rows

    rng = np.random.default_rng(seed)
    n_pad = padded_rows(n)
    bins = rng.integers(0, b, size=(n, f)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32) if g is None else g(rng, n)
    h = rng.random(n).astype(np.float32) + 0.5 if h is None else h(rng, n)
    m = (rng.random(n) < 0.8).astype(np.float32)
    seg = pack_rows(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(m),
        n_pad, wide=wide,
    )
    return rng, bins, seg, n_pad


def _window_hist_ref(seg, start, cnt, f, b, wide=False):
    from lightgbm_tpu.ops.histogram import leaf_histogram_segment
    from lightgbm_tpu.ops.pallas.seg import unpack_stats

    bo, go, ho, mo, _ = unpack_stats(seg[:, start:start + cnt], f, wide=wide)
    return np.asarray(leaf_histogram_segment(bo, go, ho, mo, b))


def _rel_err(got, ref):
    return float(np.abs(np.asarray(got) - ref).max() / max(1e-9, np.abs(ref).max()))


# ------------------------------------------------------------- partition


def test_partition_kernel_native():
    """Streaming partition kernel vs the stable-sort path: bit-identical
    (whole window, unaligned window with NaN bin + default-left, small
    categorical window)."""
    from lightgbm_tpu.ops.pallas.partition import seg_partition_pallas
    from lightgbm_tpu.ops.segpart import sort_partition_xla

    f = 11
    rng, _, seg, n_pad = _packed(f=f)
    n = 200_000
    catm_narrow = (rng.random(256) < 0.5).astype(np.float32)
    catm = jnp.asarray(catm_narrow)[None, :]
    for (sb, cnt, feat, tbin, dl, nanb, iscat) in (
        (0, n, 3, 120, 0, -1, 0),
        (137, 60_000, 5, 80, 1, 200, 0),
        (513, 1029, 7, 30, 0, -1, 1),
    ):
        scal = jnp.asarray([sb, cnt, feat, tbin, dl, nanb, iscat, 0], jnp.int32)
        got, nl_k = seg_partition_pallas(
            seg, scal, catm, f=f, n_pad=n_pad, use_cat=bool(iscat)
        )
        want, nl_s, _ = sort_partition_xla(
            seg, jnp.int32(sb), jnp.int32(cnt), jnp.int32(feat),
            jnp.int32(tbin), jnp.int32(dl), jnp.int32(nanb),
            jnp.int32(iscat), jnp.asarray(catm_narrow), f=f, n_pad=n_pad,
        )
        assert int(nl_k) == int(nl_s), (int(nl_k), int(nl_s))
        assert np.array_equal(np.asarray(got), np.asarray(want)), (
            f"partition kernel mismatch at window ({sb},{cnt})"
        )


def test_partition_kernel_bits_fed_native():
    """Bits-fed variant (feature-parallel seg): go-left bits arrive as a
    vector instead of being read from the feature column."""
    from lightgbm_tpu.ops.pallas.partition import seg_partition_pallas
    from lightgbm_tpu.ops.segpart import sort_partition_xla

    f, n = 11, 200_000
    _, bins, seg, n_pad = _packed(f=f, n=n)
    colv = np.zeros(n_pad, np.int64)
    colv[:n] = bins[:, 3]
    glv = jnp.asarray((colv <= 120).astype(np.float32))
    scal = jnp.asarray([0, n, 3, 120, 0, -1, 0, 0], jnp.int32)
    got, nl_k = seg_partition_pallas(
        seg, scal, jnp.zeros((1, 256), jnp.float32), glv, f=f, n_pad=n_pad,
        use_cat=False,
    )
    want, nl_s, _ = sort_partition_xla(
        seg, jnp.int32(0), jnp.int32(n), jnp.int32(3), jnp.int32(120),
        jnp.int32(0), jnp.int32(-1), jnp.int32(0),
        jnp.zeros((256,), jnp.float32), f=f, n_pad=n_pad,
    )
    assert int(nl_k) == int(nl_s)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# Four disjoint frontier windows: adjacent + unaligned, one with a NaN bin
# and default-left, one empty (a no-op member).
_K4 = dict(
    sbegins=[37, 37 + 61_000, 130_000, 180_000],
    cnts=[61_000, 40_003, 0, 19_999],
    feats=[3, 7, 1, 10],
    tbins=[120, 80, 5, 200],
    dls=[0, 1, 0, 0],
    nanbs=[-1, 200, -1, -1],
    iscats=[0, 0, 0, 0],
)


def _k_args(k):
    cols = [jnp.asarray(_K4[name][:k], jnp.int32) for name in
            ("sbegins", "cnts", "feats", "tbins", "dls", "nanbs", "iscats")]
    return (*cols, jnp.zeros((k, 1), jnp.float32))


def test_partition_batch_native():
    """K=4 batched partition launch (one program per window) vs the
    sequential stable-sort chain: bit-identical."""
    from lightgbm_tpu.ops.segpart import sort_partition_batch

    f = 28
    _, _, seg, n_pad = _packed(f=f)
    kw = dict(f=f, n_pad=n_pad)
    got = sort_partition_batch(seg, *_k_args(4), **kw)
    want = _on_cpu(sort_partition_batch, seg, *_k_args(4), **kw)
    for name, g_, w_ in zip(("seg", "nl", "nr"), got, want):
        assert np.array_equal(np.asarray(g_), w_), name


# ------------------------------------------------------------- histograms


def test_seg_hist_native():
    """bf16 three-term seg histogram vs the f32 reference."""
    from lightgbm_tpu.ops.pallas.seg import seg_hist_pallas

    f = 11
    _, _, seg, n_pad = _packed(f=f)
    hs = seg_hist_pallas(
        seg, jnp.asarray([137, 60_000], jnp.int32), f=f, num_bins=256,
        n_pad=n_pad,
    )
    rel = _rel_err(hs, _window_hist_ref(seg, 137, 60_000, f, 256))
    assert rel < 5e-6, rel


def test_seg_hist_int8_native():
    """int8 grid variant on quantized-training inputs (every addend an
    integer multiple of the scale): the kernel's integer accumulation is
    EXACT, so it is held to the exact integer sums — the f32 reference
    histogram is the less accurate side here (its ~200-addend f32 sums sit
    ~5e-6 off; measured on the chip, PR 22)."""
    from lightgbm_tpu.ops.pallas.seg import seg_hist_pallas, unpack_stats

    f, start, cnt = 11, 137, 60_000
    gs, hsc = np.float32(0.037), np.float32(0.0021)
    _, _, seg_q, n_pad = _packed(
        f=f,
        g=lambda rng, n: rng.integers(-63, 64, size=n).astype(np.float32) * gs,
        h=lambda rng, n: rng.integers(0, 64, size=n).astype(np.float32) * hsc,
    )
    out_q = np.asarray(seg_hist_pallas(
        seg_q, jnp.asarray([start, cnt], jnp.int32),
        jnp.asarray([gs, hsc], jnp.float32), f=f, num_bins=256, n_pad=n_pad,
        quantized=True,
    ))
    bins, g, h, m, _ = (
        np.asarray(a) for a in unpack_stats(seg_q[:, start:start + cnt], f)
    )
    keep = m > 0
    kq = np.rint(g / gs).astype(np.int64) * keep
    hq = np.rint(h / hsc).astype(np.int64) * keep
    exact = np.zeros((3, f, 256), np.int64)  # (g, h, count) planes
    for j in range(f):
        np.add.at(exact[0, j], bins[:, j], kq)
        np.add.at(exact[1, j], bins[:, j], hq)
        np.add.at(exact[2, j], bins[:, j], keep.astype(np.int64))
    assert np.array_equal(out_q[2], exact[2])
    # one f32 rounding of (integer sum) * scale
    np.testing.assert_allclose(out_q[0], exact[0] * float(gs), rtol=3e-7)
    np.testing.assert_allclose(out_q[1], exact[1] * float(hsc), rtol=3e-7)
    # and the f32 reference agrees at ITS accuracy
    ref_q = _window_hist_ref(seg_q, start, cnt, f, 256)
    np.testing.assert_allclose(out_q, ref_q, rtol=1e-5, atol=1e-5)


def test_wide_seg_hist_native():
    """u16 wide planes (max_bin > 256) vs the reference."""
    from lightgbm_tpu.ops.pallas.seg import seg_hist_pallas

    f, n, b = 4, 50_000, 1024
    _, _, seg, n_pad = _packed(seed=3, f=f, n=n, b=b, wide=True)
    hs = seg_hist_pallas(
        seg, jnp.asarray([137, 40_000], jnp.int32), f=f, num_bins=b,
        n_pad=n_pad, wide=True,
    )
    rel = _rel_err(hs, _window_hist_ref(seg, 137, 40_000, f, b, wide=True))
    assert rel < 5e-6, rel


# -------------------------------------------------------- fused grow step


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("k", [1, 4])
def test_fused_grow_step_native(k, int8):
    """One fused launch (partition + election + smaller-child histogram for
    K members) vs the XLA composition: partition state and the election
    bit-equal; histograms at the accumulator's tolerance (bf16 three-term
    ~1e-6; the int8 grid's step is ~6e-5 of the largest addend)."""
    from lightgbm_tpu.ops.pallas.grow_step import fused_grow_step
    from lightgbm_tpu.ops.pallas.seg import QMAX

    f = 28
    _, _, seg, n_pad = _packed(f=f)
    kw = dict(f=f, num_bins=256, n_pad=n_pad)
    if int8:
        # the grower's default accumulator: scales put max|g|, max|h| on QMAX
        kw["quant_scales"] = (jnp.float32(6.0 / QMAX), jnp.float32(1.5 / QMAX))
    got = fused_grow_step(seg, *_k_args(k), **kw)
    want = _on_cpu(fused_grow_step, seg, *_k_args(k), **kw)
    for i, name in enumerate(("seg", "nl", "nr", "child_start", "child_cnt")):
        assert np.array_equal(np.asarray(got[i]), want[i]), name
    hist, ref = np.asarray(got[5]), want[5]
    assert np.array_equal(hist[:, 2], ref[:, 2]), "counts"
    if int8:
        # per-bin error <= rows_in_bin * half a grid step
        step = np.asarray([6.0 / QMAX, 1.5 / QMAX], np.float32)
        bound = 0.5 * step[:, None, None] * ref[:, 2:3] + 1e-4
        assert np.all(np.abs(hist[:, :2] - ref[:, :2]) <= bound)
    else:
        assert _rel_err(hist, ref) < 5e-6


# ------------------------------------------------------------- split scan


def test_split_scan_vmapped_native():
    """The best-split scan kernel the way the grower calls it — vmapped
    over both children of a split — vs the XLA best_split."""
    from lightgbm_tpu.ops.pallas.split_scan import fused_best_split
    from lightgbm_tpu.ops.split import best_split

    rng = np.random.default_rng(11)
    f, b, n = 28, 256, 40_000
    hp = dict(lambda_l1=0.0, lambda_l2=0.0, min_data_in_leaf=100,
              min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0)
    num_bins = rng.integers(b // 2, b + 1, size=f).astype(np.int32)
    nan_bins = np.where(rng.random(f) < 0.5, num_bins - 1, -1).astype(np.int32)
    hist2 = np.zeros((2, 3, f, b), np.float32)  # (g, h, count) planes
    for c in range(2):
        g = rng.normal(size=n).astype(np.float32)
        h = rng.random(n).astype(np.float32) + 0.1
        for j in range(f):
            bins = rng.integers(0, num_bins[j], size=n)
            np.add.at(hist2[c, 0, j], bins, g)
            np.add.at(hist2[c, 1, j], bins, h)
            np.add.at(hist2[c, 2, j], bins, 1.0)
    par2 = hist2[:, :, 0].sum(axis=-1)  # [2, 3] parent (g, h, cnt)
    fm2 = np.ones((2, f), bool)
    fm2[1, ::3] = False  # the two children see different feature masks

    def both(fn, **extra):
        return jax.vmap(
            lambda hh, p, m: fn(
                hh, p[0], p[1], p[2], jnp.asarray(num_bins),
                jnp.asarray(nan_bins), m, **hp, **extra,
            )
        )(jnp.asarray(hist2), jnp.asarray(par2), jnp.asarray(fm2))

    got = both(fused_best_split)
    want = both(best_split)
    assert np.array_equal(np.asarray(got.feature), np.asarray(want.feature))
    assert np.array_equal(np.asarray(got.bin), np.asarray(want.bin))
    assert np.array_equal(
        np.asarray(got.default_left), np.asarray(want.default_left)
    )
    assert np.array_equal(np.asarray(got.left_cnt), np.asarray(want.left_cnt))
    np.testing.assert_allclose(
        np.asarray(got.gain), np.asarray(want.gain), rtol=5e-3, atol=1e-4
    )


# ----------------------------------------------------------------- predict


def test_forest_walk_native():
    """Forest-walk predictor vs the XLA bin walker through a trained model."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.predict import predict_bins_raw

    rng = np.random.default_rng(7)
    X = rng.normal(size=(20_000, 7))
    X[::5, 2] = np.nan
    y = np.where(np.isnan(X[:, 2]), 1.0, X[:, 0])
    b = lgb.train(
        {"objective": "regression", "num_leaves": 31, "verbosity": -1},
        lgb.Dataset(X, y), 12,
    )
    raw_fw = b._forest_walk_raw(X[:5000], 0, 12, 1)
    assert raw_fw is not None, "forest walk ineligible on the TPU"
    bins_h = jnp.asarray(b._bin_input_host(X[:5000]))
    exp = np.asarray(
        predict_bins_raw(b._stacked_bins(0, 12), bins_h, b._nan_bins)
    ).reshape(5000, -1).sum(axis=1)
    assert np.allclose(raw_fw[:, 0], exp, atol=1e-5)


# -------------------------------------------------------------- end to end


def test_fused_default_tree_structure_native():
    """The TPU default (fused grow step + Pallas split scan) against the
    two-launch path on real data at Higgs width.

    With the same Pallas scan, the fused step must grow the IDENTICAL
    structure (it is the same partition + histogram arithmetic in one
    launch).  Against the XLA best_split the two scans agree on every
    decision except near ties: both compute gains in f32 and sit ~1e-3 from
    the f64 truth (tests/test_split_scan.py), so a node may flip only where
    the two winners' gains are that close.  Measured on the chip (PR 22):
    trees 0-2 identical, 5 of 254 nodes of tree 3 differ, first at a
    relative gain gap of 1.1e-4; train MSE equal to 1e-7 relative."""
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(3)
    X = rng.normal(size=(200_000, 28))
    X[::9, 5] = np.nan
    y = X[:, 0] + np.sin(X[:, 1]) + 0.3 * np.isnan(X[:, 5])
    base = {"objective": "regression", "verbosity": -1, "num_leaves": 255,
            "min_data_in_leaf": 100}

    def fit(**over):
        p = {**base, **over}
        b = lgb.Booster(p, lgb.Dataset(X, y, params=p))
        for _ in range(4):
            b.update()
        assert not b.degraded
        return b

    def nodes(bst):
        return [
            (np.asarray(t.split_feature), np.asarray(t.threshold),
             np.asarray(t.split_gain))
            for t in bst.models_
        ]

    def mse(bst):
        return float(np.mean((bst.predict(X[:50_000]) - y[:50_000]) ** 2))

    fused = fit()
    assert fused._grower_params.hist_mode == "seg"
    assert fused._grower_params.grow_fused

    same_scan = fit(grow_fused="off", fused_split_scan=True)
    assert not same_scan._grower_params.grow_fused
    for ti, ((f0, t0, _), (f1, t1, _)) in enumerate(
        zip(nodes(fused), nodes(same_scan))
    ):
        assert np.array_equal(f0, f1) and np.array_equal(t0, t1), (
            f"fused step vs two-launch kernels diverge in tree {ti}"
        )

    xla_scan = fit(grow_fused="off")
    for ti, ((f0, t0, g0), (f1, t1, g1)) in enumerate(
        zip(nodes(fused), nodes(xla_scan))
    ):
        n = min(len(f0), len(f1))
        same = (f0[:n] == f1[:n]) & (t0[:n] == t1[:n])
        if same.all() and len(f0) == len(f1):
            continue
        i = int(np.argmin(same))  # first flip; later ones follow from it
        gap = abs(float(g0[i]) - float(g1[i])) / max(abs(float(g0[i])), 1e-12)
        assert gap <= 5e-3, (
            f"tree {ti} node {i}: Pallas scan chose feature {f0[i]} (gain "
            f"{g0[i]}), XLA best_split feature {f1[i]} (gain {g1[i]}) — "
            f"relative gap {gap:.2e} is not a near tie"
        )
    assert abs(mse(fused) - mse(xla_scan)) <= 1e-4 * mse(xla_scan)
