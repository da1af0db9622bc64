"""A packed row of more than one 128-plane group (ops/pallas/seg.py).

Rows wider than 128 i16 planes (more than 242 byte-binned columns) are
stored as G plane groups that share one row order.  On the CPU, with the
kernels in interpret mode and at small sizes:

* the grouped pack -> partition -> histogram equals a plain NumPy stable
  partition and ``bincount`` histogram exactly (statistics that are small
  multiples of a power of two, so that every order of summation is exact);
* trees grown through the grouped path equal the trees of the same table
  on the program's non-segment path;
* a table that needs ONE group, dealt over two through the packing
  function's test-only argument, gives bit-identical partitions, histograms
  and trees to the one-group form — which ties the groups to the model.
"""

import functools

import numpy as np
import pytest
import jax.numpy as jnp

from lightgbm_tpu.ops import segpart
from lightgbm_tpu.ops.pallas import partition, seg

B = 256
WIDTHS = (243, 500, 2000)  # 2 x 80, 3 x 96 and 8 x 128 planes


def _table(f, n, seed, num_bins=B):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, num_bins - 1, size=(n, f))
    # exact in float32 under any summation order
    grad = rng.integers(-16, 17, size=n).astype(np.float32) / 8
    hess = rng.integers(1, 9, size=n).astype(np.float32) / 8
    return bins, grad, hess


def _pack(bins, grad, hess, groups=None, wide=False):
    n = bins.shape[0]
    n_pad = seg.padded_rows(n)
    mat = seg.pack_rows(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.ones(n, jnp.float32), n_pad, wide=wide, groups=groups,
    )
    return mat, n_pad


def _partition(mat, n_pad, f, sb, cnt, feat, tbin, wide=False):
    """(matrix, nl) of the Pallas kernel in interpret mode; a grouped matrix
    goes by the go-left bits."""
    gl = None
    if seg.is_grouped(mat):
        gl = segpart.go_left_bits(
            mat, jnp.int32(feat), jnp.int32(tbin), jnp.int32(0), jnp.int32(-1),
            jnp.int32(0), jnp.zeros((1,), jnp.float32), wide=wide,
        )
    scal = jnp.asarray([sb, cnt, feat, tbin, 0, -1, 0, 0], jnp.int32)
    out, nl = partition.seg_partition_pallas(
        mat, scal, jnp.zeros((1, max(256, B)), jnp.float32), gl,
        f=f, n_pad=n_pad, use_cat=False, wide=wide, interpret=True,
    )
    return out, int(nl)


def _hist(mat, n_pad, f, start, cnt, num_bins=B, wide=False):
    return np.asarray(seg.seg_hist_pallas(
        mat, jnp.asarray([start, cnt], jnp.int32), f=f, num_bins=num_bins,
        n_pad=n_pad, wide=wide, interpret=True,
    ))


def _numpy_hist(bins, grad, hess, rows, num_bins=B):
    out = np.zeros((3, bins.shape[1], num_bins))  # (g, h, count) planes
    for j in range(bins.shape[1]):
        col = bins[rows, j]
        out[0, j] = np.bincount(col, weights=grad[rows], minlength=num_bins)
        out[1, j] = np.bincount(col, weights=hess[rows], minlength=num_bins)
        out[2, j] = np.bincount(col, minlength=num_bins)
    return out


@pytest.mark.parametrize("f", WIDTHS)
def test_layout_from_width_alone(f):
    g, sub = seg.group_shape(f)
    assert g == seg.plane_groups(f) == -(-seg.used_lanes(f, grouped=True) // 128)
    assert sub <= seg.LANES and sub % 16 == 0
    stat0 = seg.stat_lanes(f, grouped=True)[0]
    assert stat0 % seg.STAT_BLOCK == 0 and stat0 >= seg.bin_lanes(f)
    assert stat0 + seg.STAT_BLOCK <= g * sub
    assert seg.seg_vmem_ok(f, B)  # a group is counted, not the cap's stand-in


def test_one_group_up_to_the_old_cap():
    assert seg.plane_groups(242) == 1 and seg.plane_groups(243) == 2
    assert seg.plane_groups(121, wide=True) == 1
    assert seg.plane_groups(122, wide=True) == 2
    assert seg.group_shape(2000) == (8, 128)
    bins, grad, hess = _table(242, 300, 0)
    assert _pack(bins, grad, hess)[0].shape == (128, seg.padded_rows(300))


@pytest.mark.parametrize("f", WIDTHS)
def test_grouped_pack_partition_histogram_equal_numpy(f):
    n, sb, cnt = 1400, 133, 1100
    bins, grad, hess = _table(f, n, f)
    mat, n_pad = _pack(bins, grad, hess)
    assert mat.shape == seg.group_shape(f) + (n_pad,)
    b2, g2, h2, _, r2 = seg.unpack_stats(mat, f, n)
    assert np.array_equal(np.asarray(b2), bins)
    assert np.array_equal(np.asarray(g2), grad)
    assert np.array_equal(np.asarray(r2), np.arange(n))

    ridx = np.arange(n)
    # the split feature in the LAST group's planes, then in the first's
    for feat, tbin in ((f - 1, 120), (0, 90)):
        out, nl = _partition(mat, n_pad, f, sb, cnt, feat, tbin)
        go = bins[sb:sb + cnt, feat] <= tbin
        order = np.concatenate([
            np.arange(sb), sb + np.nonzero(go)[0], sb + np.nonzero(~go)[0],
            np.arange(sb + cnt, n),
        ])
        b3, g3, h3, m3, r3 = seg.unpack_stats(out, f, n)
        assert nl == int(go.sum())
        assert np.array_equal(np.asarray(r3), ridx[order])
        assert np.array_equal(np.asarray(b3), bins[order])
        assert np.array_equal(np.asarray(g3), grad[order])
        assert np.array_equal(np.asarray(h3), hess[order])
        # nothing outside the window moved, in any plane of any group
        flat0, flat1 = (np.asarray(seg.flat_planes(m)) for m in (mat, out))
        assert np.array_equal(flat0[:, :sb], flat1[:, :sb])
        assert np.array_equal(flat0[:, sb + cnt:], flat1[:, sb + cnt:])
        # the XLA formulation the CPU path runs is the same permutation
        want, nl_x, _ = segpart.sort_partition_xla(
            mat, jnp.int32(sb), jnp.int32(cnt), jnp.int32(feat),
            jnp.int32(tbin), jnp.int32(0), jnp.int32(-1), jnp.int32(0),
            jnp.zeros((1,), jnp.float32),
            segpart.go_left_bits(
                mat, jnp.int32(feat), jnp.int32(tbin), jnp.int32(0),
                jnp.int32(-1), jnp.int32(0), jnp.zeros((1,), jnp.float32)),
            f=f, n_pad=n_pad, use_gl_vec=True,
        )
        assert int(nl_x) == nl
        assert np.array_equal(np.asarray(want), np.asarray(out))
        # the left child's histogram, over the rows the partition put there
        got = _hist(out, n_pad, f, sb, nl)
        assert np.array_equal(got, _numpy_hist(bins, grad, hess, order[sb:sb + nl]))
        mat = out
        bins, grad, hess, ridx = bins[order], grad[order], hess[order], ridx[order]


def test_grouped_wide_rows_equal_numpy():
    """u16 bins (max_bin > 256) past 121 columns: one plane a feature."""
    f, n, nb = 130, 900, 512
    bins, grad, hess = _table(f, n, 5, num_bins=nb)
    mat, n_pad = _pack(bins, grad, hess, wide=True)
    assert mat.shape == seg.group_shape(f, wide=True) + (n_pad,)
    out, nl = _partition(mat, n_pad, f, 50, 700, f - 1, 300, wide=True)
    go = bins[50:750, f - 1] <= 300
    order = np.concatenate([
        np.arange(50), 50 + np.nonzero(go)[0], 50 + np.nonzero(~go)[0],
        np.arange(750, n),
    ])
    b3, _, _, _, r3 = seg.unpack_stats(out, f, n, wide=True)
    assert nl == int(go.sum())
    assert np.array_equal(np.asarray(r3), order)
    assert np.array_equal(np.asarray(b3), bins[order])
    got = _hist(out, n_pad, f, 50, nl, num_bins=nb, wide=True)
    assert np.array_equal(
        got, _numpy_hist(bins, grad, hess, order[50:50 + nl], num_bins=nb))


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("f,groups,wide,num_bins,long", [
    (500, None, False, 256, False), (28, 2, False, 256, False),
    (300, None, False, 128, False), (130, None, True, 512, False),
    (125, None, True, 1000, False),
    (28, 2, False, 256, True), (243, None, False, 256, True),
    (130, None, True, 512, True),
])
def test_grouped_two_digit_onehot_equals_numpy_and_full_onehot(
        f, groups, wide, num_bins, long, quantized, full_onehot):
    """The grouped row's histogram programs (an aligned 16-plane bin block
    over the stat block, the planes picked at one of a few static offsets)
    through the two-digit one-hot: exactly NumPy's ``bincount`` and exactly
    the H = 1 form, K = 2 windows (one off a 128-column boundary, one of
    cnt = 0), both dtypes (grad and hess are multiples of 1/8, so every
    order of summation is exact and the int8 grid holds them whole).
    ``long``: the first window takes three long steps (two DMAs each) and a
    ragged tail, a third ends on a long step's edge; the H = 1 form keeps
    the TILE-row loop alone."""
    step = seg.hist_step(f, seg.hist_bpad(num_bins), seg.hist_sub(f, wide, True))
    assert step > seg.TILE
    n, first = (3 * step + 600, (77, 3 * step + 37)) if long else (1200, (133, 900))
    bins, grad, hess = _table(f, n, f + 1, num_bins=num_bins)
    mat, n_pad = _pack(bins, grad, hess, groups=groups, wide=wide)
    assert seg.is_grouped(mat)
    scal = jnp.asarray([first, (40, 0), (393, 2 * step - 9)][:2 + long], jnp.int32)
    kw = dict(f=f, num_bins=num_bins, n_pad=n_pad, wide=wide,
              quantized=quantized, interpret=True)
    scales = jnp.full((2,), 1 / 8, jnp.float32)
    got = np.asarray(seg.seg_hist_pallas_batch(mat, scal, scales, **kw))
    with full_onehot():
        want = np.asarray(seg.seg_hist_pallas_batch(mat, scal, scales, **kw))
    assert np.array_equal(got, want)
    for i in (0, 2)[:1 + long]:
        st, cnt = (int(v) for v in scal[i])
        assert np.array_equal(
            got[i], _numpy_hist(bins, grad, hess, np.arange(st, st + cnt),
                                num_bins=num_bins))
    assert not got[1].any()


@pytest.mark.parametrize("f", (28, 100, 242))
def test_two_forced_groups_are_bit_identical_to_one(f):
    n, sb, cnt = 1400, 77, 1200
    bins, grad, hess = _table(f, n, 100 + f)
    rng = np.random.default_rng(f)
    grad = rng.normal(size=n).astype(np.float32)  # real sums: same bits or not
    hess = rng.random(n).astype(np.float32) + 0.5
    one, n_pad = _pack(bins, grad, hess)
    two, _ = _pack(bins, grad, hess, groups=2)
    assert one.ndim == 2 and two.shape[0] == 2
    for feat, tbin in ((f - 1, 100), (1, 140)):
        one, nl1 = _partition(one, n_pad, f, sb, cnt, feat, tbin)
        two, nl2 = _partition(two, n_pad, f, sb, cnt, feat, tbin)
        assert nl1 == nl2
        for a, b in zip(seg.unpack_stats(one, f, n), seg.unpack_stats(two, f, n)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        h1 = _hist(one, n_pad, f, sb, nl1)
        h2 = _hist(two, n_pad, f, sb, nl1)
        assert np.array_equal(h1.view(np.uint32), h2.view(np.uint32))


def _grow(bins, grad, hess, mode, num_leaves=8, **over):
    from lightgbm_tpu.ops.grower import GrowerParams, grow_tree

    n, f = bins.shape
    params = GrowerParams(
        num_leaves=num_leaves, max_bin=B, min_data_in_leaf=5,
        min_sum_hessian_in_leaf=0.0, lambda_l2=0.1, hist_mode=mode, **over,
    )
    tree, leaf_id = grow_tree(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.ones(n, jnp.float32), jnp.full((f,), B, jnp.int32),
        jnp.full((f,), -1, jnp.int32), jnp.ones(f, bool), params,
    )
    return tree, np.asarray(leaf_id)


@pytest.fixture
def interpret_kernels(monkeypatch):
    """The grower's two-launch seg path on the Pallas kernels (interpret
    mode) instead of their XLA formulations."""
    monkeypatch.setattr(seg, "_INTERPRET", True)
    monkeypatch.setattr(partition, "_INTERPRET", True)


def _learnable(f, n, seed):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, B - 1, size=(n, f))
    signal = (bins[:, f - 1] > 100) * 1.0 + (bins[:, 3] > 60) * 0.5 \
        + (bins[:, f // 2] > 180) * 0.25
    grad = (signal - signal.mean() + 0.05 * rng.normal(size=n)).astype(np.float32)
    hess = np.ones(n, np.float32)
    return bins, grad, hess


def _same_tree(a, b, exact):
    (ta, la), (tb, lb) = a, b
    assert int(ta.num_leaves) == int(tb.num_leaves) > 1
    for name in ("split_feature", "split_bin", "left_child", "right_child"):
        assert np.array_equal(np.asarray(getattr(ta, name)),
                              np.asarray(getattr(tb, name))), name
    assert np.array_equal(la, lb)
    if exact:
        for name in ("leaf_value", "split_gain", "internal_value"):
            assert np.array_equal(
                np.asarray(getattr(ta, name)).view(np.uint32),
                np.asarray(getattr(tb, name)).view(np.uint32)), name
    else:
        np.testing.assert_allclose(np.asarray(ta.leaf_value),
                                   np.asarray(tb.leaf_value), rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("f", WIDTHS)
def test_grouped_trees_equal_the_non_segment_path(f, interpret_kernels):
    bins, grad, hess = _learnable(f, 1200, f)
    got = _grow(bins, grad, hess, "seg")
    want = _grow(bins, grad, hess, "ordered")
    _same_tree(got, want, exact=False)
    assert {int(x) for x in np.asarray(got[0].split_feature)[:3]} & {f - 1, 3, f // 2}


def test_grouped_trees_on_the_xla_formulation():
    """The CPU path proper (no interpret hook): the same grouped arrays
    through the stable-sort partition and the masked histogram."""
    f = 243
    bins, grad, hess = _learnable(f, 1500, 9)
    _same_tree(_grow(bins, grad, hess, "seg"), _grow(bins, grad, hess, "ordered"),
               exact=False)


@pytest.mark.parametrize("fused", (False, True))
def test_two_forced_groups_grow_bit_identical_trees(fused, interpret_kernels,
                                                    monkeypatch):
    """The model does not see the groups: a 40-column table dealt over two
    plane groups grows the tree of the one-group form, bit for bit — also
    when the one-group form takes the fused step (which a grouped row is
    not given)."""
    from lightgbm_tpu.ops.pallas import grow_step

    f = 40
    bins, grad, hess = _learnable(f, 1100, 3)
    rng = np.random.default_rng(1)
    hess = (rng.random(1100) + 0.5).astype(np.float32)
    if fused:
        monkeypatch.setattr(grow_step, "_INTERPRET", True)
    one = _grow(bins, grad, hess, "seg", grow_fused=fused)
    monkeypatch.setattr(
        seg, "pack_rows", functools.partial(seg.pack_rows, groups=2))
    two = _grow(bins, grad, hess, "seg", grow_fused=fused)
    _same_tree(one, two, exact=True)


def test_what_a_grouped_row_cannot_take_raises():
    bins, grad, hess = _table(243, 600, 2)
    mat, n_pad = _pack(bins, grad, hess)
    scal = jnp.asarray([0, 600, 1, 100, 0, -1, 0, 0], jnp.int32)
    with pytest.raises(ValueError, match="go-left bits"):
        partition.seg_partition_pallas(
            mat, scal, jnp.zeros((1, 256), jnp.float32), None,
            f=243, n_pad=n_pad, use_cat=False, interpret=True)
    with pytest.raises(ValueError, match="one-group"):
        partition.seg_partition_pallas_batch(
            mat, scal.reshape(1, 8), jnp.zeros((1, 256), jnp.float32),
            f=243, n_pad=n_pad, use_cat=False, interpret=True)
    with pytest.raises(ValueError, match="leaf_batch"):
        _grow(bins, grad, hess, "seg", leaf_batch=2)


class _Log:
    def __init__(self):
        self.warnings = []

    def info(self, msg):
        pass

    def warning(self, msg):
        self.warnings.append(str(msg))


@pytest.mark.parametrize("f,max_bin,groups,planes,digits,block,step", [
    # a table of 600 rows is packed 2,048 columns long: no step is longer
    (2000, 255, 8, 128, "8x32", 2, "2048+512"),
    (243, 255, 2, 80, "8x32", 2, "2048+512"),
    (242, 255, 1, 128, "8x32", 2, "2048+512"),
    (130, 511, 2, 80, "8x64", 2, "2048+512"),
    (28, 127, 1, 32, "4x32", 4, "2048+512"),
    (3, 4000, 1, 32, "16x64", 1, "2048+512"),
])
def test_the_gate_resolves_seg_at_any_width(f, max_bin, groups, planes, digits,
                                            block, step, monkeypatch):
    """On a TPU the Booster takes the segment path whatever the width, with
    no warning; the spans carry G and the planes a group, the digits of the
    histogram kernel's one-hot with the features a matmul takes, which form
    of the kernel runs and the gradient levels of quantized training."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils import log as log_mod

    rng = np.random.default_rng(0)
    x = rng.normal(size=(600, f)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    logs = _Log()
    lgb.register_logger(logs)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        booster = lgb.Booster(
            {"objective": "binary", "num_leaves": 255, "max_bin": max_bin,
             "verbosity": -1},
            lgb.Dataset(x, y, params={"max_bin": max_bin}),
        )
    finally:
        log_mod.unregister_logger()
    p = booster._grower_params
    assert p.hist_mode == "seg" and p.grow_fused
    assert not [w for w in logs.warnings if "segment-resident" in w], logs.warnings
    assert booster._seg_span_args() == {
        "seg_groups": groups, "seg_group_planes": planes,
        "hist_digits": digits, "hist_feature_block": block,
        # long steps and the 512-row tile for a window's end (PR 38);
        # "512" alone where the form is the full one-hot
        "hist_step": step,
        # the default hist_acc=auto takes the kernel's int8 form on a TPU;
        # no quantized gradients (PR 33)
        "hist_int8": True, "grad_quant_bins": 0}


def test_the_gate_still_says_what_cannot_run(monkeypatch):
    """VMEM at very wide bins keeps its warning, which no longer advises
    deleting columns; leaf_batch > 1 on a grouped row is clamped aloud."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops.pallas import seg as seg_mod
    from lightgbm_tpu.utils import log as log_mod

    rng = np.random.default_rng(0)
    x = rng.normal(size=(600, 300)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    logs = _Log()
    lgb.register_logger(logs)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        b1 = lgb.Booster(
            {"objective": "binary", "leaf_batch": 4, "verbosity": -1},
            lgb.Dataset(x, y),
        )
        monkeypatch.setattr(seg_mod, "SEG_VMEM_BUDGET", 1024)
        b2 = lgb.Booster({"objective": "binary", "verbosity": -1}, lgb.Dataset(x, y))
    finally:
        log_mod.unregister_logger()
    assert b1._grower_params.hist_mode == "seg" and b1._grower_params.leaf_batch == 1
    assert any("leaf_batch > 1 does not support a packed row of more than one "
               "plane group" in w for w in logs.warnings), logs.warnings
    assert b2._grower_params.hist_mode == "ordered"
    lost = [w for w in logs.warnings if "segment-resident" in w]
    assert len(lost) == 1 and "VMEM" in lost[0] and "smaller max_bin" in lost[0]
    assert "feature selection" not in lost[0] and "used features >" not in lost[0]


@pytest.mark.parametrize("how", ("valid_sets", "launch_scan"))
def test_lgb_train_on_a_grouped_row_equals_the_ordered_path(how):
    """Through the normal entry point, with a validation set (one
    Booster.update() an iteration) and without (the launch scan)."""
    import lightgbm_tpu as lgb

    f = 243
    bins, _, _ = _learnable(f, 900, 11)
    x = bins.astype(np.float32)
    y = ((bins[:, f - 1] > 100) ^ (bins[:, 3] > 60)).astype(np.float32)
    base = dict(objective="binary", num_leaves=7, learning_rate=0.3, max_bin=255,
                min_data_in_leaf=5, verbosity=-1, deterministic=True, seed=3)
    models = {}
    for mode in ("seg", "ordered"):
        params = dict(base, hist_mode=mode)
        dtrain = lgb.Dataset(x, y, params={"max_bin": 255})
        if how == "valid_sets":
            kw = dict(valid_sets=[lgb.Dataset(x[:200], y[:200], reference=dtrain)])
        else:
            params["train_steps_per_launch"] = 2
            kw = {}
        b = lgb.train(params, dtrain, num_boost_round=4, **kw)
        assert b._grower_params.hist_mode == mode and not b.degraded
        s = b.model_to_string()
        models[mode] = [l for l in s[s.index("Tree=0"):s.index("end of trees")].splitlines()
                        if l.startswith(("split_feature=", "threshold=", "left_child=",
                                         "right_child=", "num_leaves=", "leaf_count="))]
    assert models["seg"] == models["ordered"]


@pytest.mark.parametrize("step", (4, 16, 64))
def test_long_step_row_share_comes_from_the_trees(step, monkeypatch):
    """``hist/long_step_row_share`` in ``Booster.telemetry()`` (and, through
    ``health()``, on ``GET /metrics``): of the rows the trees histogrammed
    (the root and the smaller child of every split, by the model's counts)
    the share in whole STEP-row steps, computed when asked; ``hist_step`` on
    the spans comes from the same function."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.registry import get_session

    rng = np.random.default_rng(3)
    x = rng.normal(size=(700, 5)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    booster = lgb.train(
        {"objective": "binary", "num_leaves": 7, "hist_mode": "seg",
         "min_data_in_leaf": 5, "verbosity": -1},
        lgb.Dataset(x, y), num_boost_round=3)
    assert booster._hist_step() == 2048 == seg.hist_step(
        5, seg.hist_bpad(255), seg.hist_sub(5, False), seg.padded_rows(700))
    assert booster._seg_span_args()["hist_step"] == "2048+512"
    monkeypatch.setattr(type(booster), "_hist_step", lambda self: step)
    rows = long_rows = 0
    for tree in booster.dump_model()["tree_info"]:
        def walk(node):
            nonlocal rows, long_rows
            if "leaf_index" in node:
                return node["leaf_count"]
            kids = [walk(node["left_child"]), walk(node["right_child"])]
            rows += min(kids)
            long_rows += min(kids) // step * step
            return node["internal_count"]
        root = walk(tree["tree_structure"])
        rows += root
        long_rows += root // step * step
    want = long_rows / rows
    assert 0 < want < 1
    assert booster.telemetry()["gauges"]["hist/long_step_row_share"] == want
    ses = get_session()
    was = ses.enabled
    ses.configure(enabled=True)
    try:
        booster.health()
        assert ses.gauges["hist/long_step_row_share"] == want
    finally:
        ses.reset()
        ses.configure(enabled=was)
    monkeypatch.setattr(type(booster), "_hist_step", lambda self: 0)  # off the path
    assert "hist/long_step_row_share" not in booster.telemetry()["gauges"]
