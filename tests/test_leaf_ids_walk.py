"""A row's leaf on the segment path (PR 36): the tree's contraction walk in
place of ``leaf_ids``' scatter, cumsum, row gather and sort, wherever static
shapes say the walk is the cheaper form (``score_lookup.leaf_ids_form``).

Held here: the walk's leaf is ``leaf_ids``' leaf for every row of trees
``grow_tree`` itself grew (missing values both ways, degenerate and deep
trees, every bin width and row format, every step program, a mesh); training
is the same model in both forms; the rule's table; what the lowered programs
hold; the span argument that says which form ran.
"""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import gbdt
from lightgbm_tpu.ops import grower, score_lookup
from lightgbm_tpu.ops.grower import GrowerParams, bag_window_ok, grow_tree
from lightgbm_tpu.ops.score_lookup import LEAF_WALK_MAX_WORK, leaf_ids_form

TREE_FIELDS = ("split_feature", "split_bin", "default_left", "left_child",
               "right_child", "leaf_value", "leaf_count", "num_leaves")


@pytest.fixture
def both_forms(monkeypatch):
    """``run(build)`` -> {"walk": build(), "segment": build()}: once under
    the shapes' own rule, once with the rule held to "segment" (the parent's
    program), every program traced anew."""
    def run(build):
        out = {}
        for form in ("walk", "segment"):
            with monkeypatch.context() as m:
                if form == "segment":
                    for mod in (grower, gbdt):
                        m.setattr(mod, "leaf_ids_form", lambda *a: "segment")
                jax.clear_caches()
                out[form] = build()
        jax.clear_caches()
        return out
    return run


def _numpy_leaves(bins, nan_bins, tree):
    """The leaf of every row by a plain walk, node by node."""
    sf, sb, dl, lc, rc = (np.asarray(getattr(tree, k)) for k in TREE_FIELDS[:5])
    out = np.zeros(len(bins), np.int64)
    if int(tree.num_leaves) < 2:
        return out
    for r, row in enumerate(np.asarray(bins)):
        node = 0
        while node >= 0:
            x, nb = int(row[sf[node]]), int(nan_bins[sf[node]])
            left = x <= sb[node] or (dl[node] and nb >= 0 and x == nb)
            node = lc[node] if left else rc[node]
        out[r] = ~node
    return out


# ------------------------------------------------- trees grow_tree grows
def _table(n, f, b, seed, nan_share=0.0, dtype=np.uint8):
    rng = np.random.default_rng(seed)
    top = b - 1 if nan_share else b  # the last bin is the NaN bin
    bins = rng.integers(0, top, size=(n, f))
    signal = (bins[:, 1] > top // 2) * 1.0 + (bins[:, f - 2] > 2 * top // 3) * 0.5
    nan_bins = np.full(f, -1, np.int32)
    if nan_share:
        nan_bins[:] = b - 1
        # missing in column 1 looks like a HIGH value (goes right: default
        # left false), missing in column f - 2 like a LOW one (default left)
        miss = rng.random((n, f)) < nan_share
        signal = np.where(miss[:, 1], 1.0, signal) - np.where(miss[:, f - 2], 0.5, 0.0) * (
            bins[:, f - 2] > 2 * top // 3)
        bins = np.where(miss, b - 1, bins)
    grad = (signal.mean() - signal + 0.05 * rng.normal(size=n)).astype(np.float32)
    hess = np.ones(n, np.float32)
    return bins.astype(dtype), grad, hess, nan_bins


def _grow(bins, grad, hess, nan_bins, b, num_leaves=15, forced=None, scales=None,
          lambda_l2=0.1, **over):
    n, f = bins.shape
    params = GrowerParams(
        num_leaves=num_leaves, max_bin=b, min_data_in_leaf=1,
        min_sum_hessian_in_leaf=0.0, lambda_l2=lambda_l2, hist_mode="seg",
        hist_acc="bf16", **over)
    tree, leaf_id = grow_tree(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.ones(n, jnp.float32),
        jnp.full((f,), b, jnp.int32), jnp.asarray(nan_bins), jnp.ones(f, bool), params,
        forced=forced, quant_scales=scales)
    return jax.tree.map(np.asarray, tree), np.asarray(leaf_id)


def _case_missing():
    bins, g, h, nanb = _table(1400, 6, 64, 1, nan_share=0.15)
    return bins, nanb, _grow(bins, g, h, nanb, 64)


def _case_stump():
    bins, g, h, nanb = _table(900, 5, 64, 2)
    return bins, nanb, _grow(bins, g, h, nanb, 64, num_leaves=2)


def _case_never_split():
    bins, g, h, nanb = _table(900, 5, 64, 3)
    return bins, nanb, _grow(bins, 0 * g, h, nanb, 64)


def _case_chain_254():
    """255 leaves in a chain 254 deep: forced splits peel the highest bin off
    leaf 0, one a step."""
    bins = np.repeat(np.arange(255), 3)[:, None].astype(np.uint8)
    bins = np.hstack([bins, bins[::-1]])
    grad = -(bins[:, 0].astype(np.float32) - 127.0)
    steps = np.arange(254)
    forced = (jnp.zeros(254, jnp.int32), jnp.zeros(254, jnp.int32),
              jnp.asarray(253 - steps, jnp.int32), jnp.zeros(254, bool))
    nanb = np.full(2, -1, np.int32)
    return bins, nanb, _grow(bins, grad, np.ones_like(grad), nanb, 256, num_leaves=255,
                             forced=forced, n_forced=254, lambda_l2=0.0)


def _case_bins(b, dtype=np.uint8):
    def case():
        bins, g, h, nanb = _table(1100, 6, b, b, nan_share=0.05, dtype=dtype)
        return bins, nanb, _grow(bins, g, h, nanb, b)
    return case


def _case_grouped_row():
    """300 columns: two to a plane, 150 planes, two plane groups."""
    bins, g, h, nanb = _table(700, 300, 64, 6, nan_share=0.05)
    return bins, nanb, _grow(bins, g, h, nanb, 64, num_leaves=8)


def _case_quantized():
    bins, g, h, nanb = _table(1200, 6, 64, 7, nan_share=0.05)
    gs, hs = np.float32(np.abs(g).max() / 2), np.float32(0.5)
    gq, hq = np.rint(g / gs).astype(np.float32) * gs, np.rint(h / hs).astype(np.float32) * hs
    return bins, nanb, _grow(bins, gq, hq, nanb, 64, scales=(jnp.float32(gs), jnp.float32(hs)))


def _case_fused_step():
    bins, g, h, nanb = _table(1300, 6, 64, 8, nan_share=0.1)
    return bins, nanb, _grow(bins, g, h, nanb, 64, grow_fused=True)


def _booster_case(extra, zeros=False, machines=0):
    """A tree of a live Booster's own grow call (the Dataset's bins and NaN
    bins, the Booster's parameters, a mesh's padded and sharded rows)."""
    def case():
        rng = np.random.default_rng(11)
        n = 1203  # not a multiple of 8: a mesh pads the rows
        X = rng.normal(size=(n, 7)).astype(np.float32)
        if zeros:
            X[rng.random(X.shape) < 0.3] = 0.0
        else:
            X[rng.random(X.shape) < 0.1] = np.nan
        y = np.where(np.isnan(X[:, 0]) | (X[:, 0] == 0), 1.0, X[:, 0]) + np.sin(
            np.nan_to_num(X[:, 2]))
        params = {"objective": "regression", "num_leaves": 15, "min_data_in_leaf": 3,
                  "verbosity": -1, "hist_mode": "seg", **extra}
        if machines:
            params.update(tree_learner="data", num_machines=machines)
        b = lgb.Booster(params, lgb.Dataset(X, label=y))
        pad = np.zeros(b._pad_rows if machines else 0, np.float32)
        g = jnp.asarray(np.concatenate([(y.mean() - y).astype(np.float32), pad]))
        ones = jnp.asarray(np.concatenate([np.ones(n, np.float32), pad]))
        fn, args, kwargs = b._grow_call(g, ones, ones, b._full_feature_mask, None)
        tree, leaf_id = fn(*args, **kwargs)
        return (np.asarray(b._bins), np.asarray(b._nan_bins),
                (jax.tree.map(np.asarray, tree), np.asarray(leaf_id)))
    return case


def _case_fleet():
    """Three members under ``vmap`` (``make_fleet_grow``): one shared table,
    a gradient each; the walk's row blocks shrink by the member count."""
    from lightgbm_tpu.parallel.mesh import make_fleet_grow

    bins, g, h, nanb = _table(1300, 6, 64, 9, nan_share=0.1)
    n, f = bins.shape
    params = GrowerParams(
        num_leaves=15, max_bin=64, min_data_in_leaf=1, min_sum_hessian_in_leaf=0.0,
        lambda_l2=0.1, hist_mode="seg", hist_acc="bf16")
    m = 3
    grads = jnp.stack([jnp.asarray(np.roll(g, 97 * k) * (1 + k)) for k in range(m)])
    rows = jnp.ones((m, n), jnp.float32)
    trees, leaf_ids = make_fleet_grow(None, params)(
        jnp.asarray(bins), grads, rows, rows, jnp.full((f,), 64, jnp.int32),
        jnp.asarray(nanb), jnp.ones((m, f), bool), None, None,
        jax.random.split(jax.random.PRNGKey(0), m), None, None, None, None, None, None, None)
    trees, leaf_ids = jax.tree.map(np.asarray, trees), np.asarray(leaf_ids)
    assert len({trees.split_feature[k].tobytes() + trees.split_bin[k].tobytes()
                for k in range(m)}) == m
    # judged member by member below: the last one here, all of them between forms
    last = jax.tree.map(lambda a: a[m - 1], trees)
    return bins, nanb, (last, leaf_ids[m - 1]), (trees, leaf_ids)


_PARITY = {
    "missing_default_left_both_ways": _case_missing,
    "zero_as_missing": _booster_case({"zero_as_missing": True}, zeros=True),
    "use_missing_off": _booster_case({"use_missing": False}),
    "stump": _case_stump,
    "never_split": _case_never_split,
    "chain_254_deep": _case_chain_254,
    "max_bin_63": _case_bins(64),
    "max_bin_255": _case_bins(256),
    "max_bin_1023_two_digit_bins": _case_bins(1024, np.uint16),
    "grouped_packed_row": _case_grouped_row,
    "quantized_gradients": _case_quantized,
    "fused_step": _case_fused_step,
    "two_launch_step": _booster_case({"grow_fused": "off"}),
    "mesh_of_eight_tree_learner_data": _booster_case({}, machines=8),
    "fleet_of_three_under_vmap": _case_fleet,
}


@pytest.mark.parametrize("case", sorted(_PARITY))
def test_the_walk_gives_every_row_leaf_ids_leaf(case, both_forms):
    got = both_forms(_PARITY[case])
    bins, nan_bins, (tree, leaf_walk) = got["walk"][:3]
    _, _, (tree_seg, leaf_seg) = got["segment"][:3]
    if case == "fleet_of_three_under_vmap":
        (trees, ids), (trees_seg, ids_seg) = got["walk"][3], got["segment"][3]
        assert np.array_equal(ids, ids_seg)
        assert np.array_equal(trees.split_bin, trees_seg.split_bin)
    for name in TREE_FIELDS:
        assert np.array_equal(getattr(tree, name), getattr(tree_seg, name)), name
    assert np.array_equal(leaf_walk, leaf_seg)
    n = len(bins)
    assert np.array_equal(leaf_walk[:n], _numpy_leaves(bins, nan_bins, tree))
    leaves = int(tree.num_leaves)
    if case == "missing_default_left_both_ways":
        used = np.asarray(tree.default_left)[: leaves - 1]
        assert used.any() and not used.all()
    elif case == "chain_254_deep":
        assert leaves == 255 and int(np.asarray(tree.leaf_depth).max()) == 254
        assert len(np.unique(leaf_walk)) == 255
    elif case == "never_split":
        assert leaves == 1 and not leaf_walk.any()
    elif case == "stump":
        assert leaves == 2 and set(np.unique(leaf_walk)) == {0, 1}
    else:
        assert leaves > 4


# --------------------------------------- training is the same in both forms
RNG = np.random.default_rng(5)
N, F = 1203, 10
X = RNG.normal(size=(N, F)).astype(np.float32)
X[RNG.random(X.shape) < 0.05] = np.nan
Y = ((X[:, 0] > 0) + np.sin(2 * np.nan_to_num(X[:, 1])) + 0.3 * RNG.normal(size=N)).astype(
    np.float32)
BASE = {"objective": "regression", "num_leaves": 15, "learning_rate": 0.1,
        "min_data_in_leaf": 5, "verbosity": -1, "seed": 3, "hist_mode": "seg"}
_TRAIN = {
    "per_iteration_fused": {"train_steps_per_launch": 1, "grow_fused": "on"},
    "per_iteration_two_launch": {"train_steps_per_launch": 1, "grow_fused": "off"},
    "launch_scan_of_eight": {"train_steps_per_launch": 8},
    "quantized_with_renewal": {"use_quantized_grad": True, "num_grad_quant_bins": 4,
                               "quant_train_renew_leaf": True, "train_steps_per_launch": 8},
    "l1_leaf_renewal_on_the_host": {"objective": "regression_l1"},
    "mesh_of_eight": {"tree_learner": "data", "num_machines": 8},
    "three_classes": {"objective": "multiclass", "num_class": 3},
}


def _strip(text: str) -> str:
    return re.sub(r"\[train_steps_per_launch: [^\]]*\]\n?", "", text)


@pytest.mark.parametrize("case", sorted(_TRAIN))
def test_training_grows_the_same_model_in_both_forms(case, both_forms):
    def train():
        label = np.digitize(Y, [0.0, 1.0]) if case == "three_classes" else Y
        b = lgb.train({**BASE, **_TRAIN[case]}, lgb.Dataset(X, label=label), num_boost_round=9)
        b._drain_pending()
        form = b._score_span_args()["leaf_ids"]
        return form, _strip(b.model_to_string()), np.asarray(b._score)[:, :N].copy()
    got = both_forms(train)
    assert got["walk"][0] == "walk" and got["segment"][0] == "segment"
    assert got["walk"][1] == got["segment"][1]
    assert np.array_equal(got["walk"][2], got["segment"][2])


# ------------------------------------------------------------------ the rule
@pytest.mark.parametrize("leaves,features,cat_width,shards,form", [
    (255, 67, 1, 0, "walk"),       # criteo67
    (255, 28, 1, 1, "walk"),       # higgs
    (31, 2000, 1, 0, "walk"),
    (255, 2000, 1, 0, "walk"),     # epsilon: 5.8e5, under the constant
    (1023, 67, 1, 0, "segment"),   # 1.1e6: the walk loses at 8M rows
    (4095, 28, 1, 0, "segment"),
    (255, 67, 256, 0, "segment"),  # a categorical or a bundled tree: cat_mask
    (255, 67, 1, 2, "segment"),    # rows replicated over feature shards
])
def test_the_form_follows_static_shapes(leaves, features, cat_width, shards, form):
    assert leaf_ids_form(leaves, features, cat_width, shards) == form


def test_the_rule_is_one_bound_on_the_contractions_size():
    pad = score_lookup._pad128
    for leaves, features in ((255, 67), (511, 28), (767, 67), (1023, 67), (255, 4000)):
        work = pad(leaves - 1) * (features + pad(leaves))
        want = "walk" if work <= LEAF_WALK_MAX_WORK else "segment"
        assert leaf_ids_form(leaves, features, 1, 0) == want
    # a sampled booster has no segment position for its out-of-bag rows: the
    # window keeps its own condition, up to ONEHOT_MAX_LEAVES
    big = GrowerParams(num_leaves=4095, max_bin=64, hist_mode="seg", bag_window=True)
    assert bag_window_ok(big, 1) and leaf_ids_form(4095, 28, 1, 0) == "segment"
    assert not bag_window_ok(dataclasses.replace(big, num_leaves=5000), 1)
    assert not bag_window_ok(big, 64) and not bag_window_ok(
        dataclasses.replace(big, feature_shard=2), 1)


def test_a_categorical_and_a_bundled_booster_keep_the_segment_form():
    rng = np.random.default_rng(2)
    Xc = rng.normal(size=(600, 6)).astype(np.float32)
    Xc[:, 3] = rng.integers(0, 9, 600)
    yc = (Xc[:, 0] + (Xc[:, 3] % 3 == 0)).astype(np.float32)
    cat = lgb.Booster({**BASE, "categorical_feature": [3]}, lgb.Dataset(Xc, label=yc))
    assert cat._score_span_args()["leaf_ids"] == "segment"
    onehot = np.zeros((600, 24), np.float32)
    onehot[np.arange(600), rng.integers(0, 24, 600)] = 1.0
    ds = lgb.Dataset(np.hstack([Xc[:, :2], onehot]), label=yc, params={"enable_bundle": True})
    bundled = lgb.Booster(BASE, ds)
    assert bundled._has_bundle
    assert bundled._score_span_args()["leaf_ids"] == "segment"
    off_path = lgb.Booster({**BASE, "hist_mode": "ordered"}, lgb.Dataset(Xc, label=yc))
    assert off_path._score_span_args()["leaf_ids"] == "none"


# --------------------------------------------------------------- the program
def _grow_text(booster, n, scopes=True):
    g = jnp.zeros((n,), jnp.float32)
    fn, args, kwargs = booster._grow_call(g, g + 1, g + 1, booster._full_feature_mask, None)
    return fn.lower(*args, **kwargs).as_text(debug_info=scopes)


def _row_ops(text: str, rows: int, op: str, scope=None):
    """Operations ``op`` ("sort" | "gather") of a lowered program (with its
    locations) that have an operand or result of ``rows`` rows, under
    ``scope`` if one is given: their type signatures."""
    dim = re.compile(rf"tensor<(\d+x)*{rows}(x\d+)*x[a-z]")
    locs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, flags=re.M))
    # a sort carries its comparator as a region: its types follow the region
    body = r".*?\}\) : " if op == "sort" else r"[^\n]*? : "
    found = re.finditer(
        rf'"stablehlo\.{op}"{body}([^\n]*?) loc\((#loc\d+)\)$', text, flags=re.S | re.M)
    return [
        m.group(1)[:160] for m in found
        if dim.search(m.group(1))
        and (scope is None or re.search(rf'["/]{scope}/', locs.get(m.group(2), "")))
    ]


def _leaf_ids_faults(text: str, rows: int):
    """Sorts of the rows anywhere, gathers of them under scope ``leaf_ids``
    (``pack_rows`` picks whole planes with a gather of its own)."""
    return _row_ops(text, rows, "sort") + _row_ops(text, rows, "gather", scope="leaf_ids")


def _wide_table(n=1111, f=12, seed=9):
    rng = np.random.default_rng(seed)
    Xw = rng.normal(size=(n, f)).astype(np.float32)
    return Xw, (Xw[:, 0] + Xw[:, 5] ** 2).astype(np.float32)


def test_an_unsampled_255_leaf_grow_program_holds_no_sort_or_gather_of_the_rows():
    n = 1111  # no other dimension of the program has this size
    Xw, yw = _wide_table(n)
    walk = lgb.Booster({**BASE, "num_leaves": 255}, lgb.Dataset(Xw, label=yw))
    assert walk._score_span_args()["leaf_ids"] == "walk"
    text = _grow_text(walk, n)
    assert "leaf_ids/" in text and not _leaf_ids_faults(text, n)
    # past the rule's size the segment form stays: its one sort of the rows
    # and its gather of a leaf a position
    seg = lgb.Booster({**BASE, "num_leaves": 1023}, lgb.Dataset(Xw, label=yw))
    assert seg._score_span_args()["leaf_ids"] == "segment"
    text = _grow_text(seg, n)
    assert len(_row_ops(text, n, "sort")) == 1
    assert _row_ops(text, n, "gather", scope="leaf_ids")


def test_the_launch_scan_of_an_unsampled_booster_holds_no_sort_or_gather_of_the_rows():
    from lightgbm_tpu.boosting.launch import LaunchRunner

    n = 1111
    Xw, yw = _wide_table(n)
    texts = {}
    for leaves in (255, 1023):
        b = lgb.Booster({**BASE, "num_leaves": leaves}, lgb.Dataset(Xw, label=yw))
        runner = LaunchRunner(b, 8)
        texts[leaves] = runner._fn.lower(*runner._operands(0)[0]).as_text(debug_info=True)
    assert "leaf_ids/" in texts[255] and not _leaf_ids_faults(texts[255], n)
    assert len(_row_ops(texts[1023], n, "sort")) == 1
    assert _row_ops(texts[1023], n, "gather", scope="leaf_ids")


def test_a_sampled_booster_lowers_to_the_program_it_had(both_forms):
    """GOSS takes the walk under ``bag_window`` and its own scope, whatever
    the rule says: with the rule held to "segment" its program is the same
    text."""
    Xw, yw = _wide_table()
    goss = {**BASE, "objective": "binary", "data_sample_strategy": "goss",
            "top_rate": 0.2, "other_rate": 0.1}
    yb = (yw > np.median(yw)).astype(np.float32)

    def text():
        b = lgb.Booster(goss, lgb.Dataset(Xw, label=yb))
        assert b._grower_params.bag_window
        assert b._score_span_args()["leaf_ids"] == "walk"
        named = _grow_text(b, len(Xw))
        assert "/oob_score/" in named and "/leaf_ids/" not in named
        return hashlib.sha256(_grow_text(b, len(Xw), scopes=False).encode()).hexdigest()

    got = both_forms(text)
    assert got["walk"] == got["segment"]
