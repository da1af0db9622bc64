"""The score update without gathers (ops/score_lookup.py): ``leaf_lookup``
against ``leaf_value[leaf_id]`` and the contraction walk against the
``while_loop`` walker, bit for bit; which trees keep the walker; and
``lgb.train`` end to end against the gather forms forced back in."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu import predict  # noqa: E402
from lightgbm_tpu.obs.registry import get_session  # noqa: E402
from lightgbm_tpu.ops import score_lookup  # noqa: E402
from lightgbm_tpu.ops.score_lookup import leaf_lookup, tree_values  # noqa: E402


def _bits(a):
    return np.asarray(a, dtype=np.float32).view(np.uint32)


# ------------------------------------------------------------- leaf_lookup
def _awkward_values(rng, num_leaves):
    lv = rng.normal(size=num_leaves).astype(np.float32)
    special = np.array(
        [1e-40, -1e-45, 1e30, -1e30, 1e-30, -0.0, 0.0, -3.5], np.float32
    )
    k = min(num_leaves, len(special))
    lv[:k] = special[:k]
    return lv


@pytest.mark.parametrize("num_leaves", [2, 31, 255, 256, 1023])
def test_leaf_lookup_is_the_gather_bit_for_bit(num_leaves):
    rng = np.random.default_rng(num_leaves)
    lv = _awkward_values(rng, num_leaves)
    ids = rng.integers(0, num_leaves, 1001).astype(np.int32)  # not k * 128
    got = leaf_lookup(jnp.asarray(lv), jnp.asarray(ids))
    assert got.dtype == jnp.float32 and got.shape == (1001,)
    assert np.array_equal(_bits(got), _bits(lv[ids]))


def test_leaf_lookup_all_zero_table():
    ids = np.arange(777, dtype=np.int32) % 31
    got = leaf_lookup(jnp.zeros(31, jnp.float32), jnp.asarray(ids))
    assert np.array_equal(_bits(got), np.zeros(777, np.uint32))


@pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf])
def test_a_non_finite_leaf_value_reaches_only_its_own_rows(bad):
    rng = np.random.default_rng(3)
    lv = rng.normal(size=63).astype(np.float32)
    lv[17] = bad
    ids = rng.integers(0, 63, 2000).astype(np.int32)
    got = np.asarray(leaf_lookup(jnp.asarray(lv), jnp.asarray(ids)))
    assert np.array_equal(_bits(got), _bits(lv[ids]))
    assert np.isfinite(got[ids != 17]).all() and not np.isfinite(got[ids == 17]).any()


def test_the_forms_follow_the_static_leaf_count():
    top = score_lookup.ONEHOT_MAX_LEAVES
    assert score_lookup.lookup_form(255) == "onehot"
    assert score_lookup.lookup_form(top) == "onehot"
    assert score_lookup.lookup_form(top + 1) == "gather"
    assert predict.valid_walk_form(255, 1) == "contract"
    assert predict.valid_walk_form(255, 256) == "walk"  # categorical / EFB
    assert predict.valid_walk_form(top + 1, 1) == "walk"


# ------------------------------------------------------ the contraction walk
def _random_tree(rng, size, num_leaves, num_features, max_bin, chain=False):
    """A leaf-wise tree of ``num_leaves`` leaves in tables of ``size`` leaves
    (the grower's layout: node t splits leaf l into l and t + 1; unused
    nodes point at leaf 0 on both sides)."""
    lc = np.full(size - 1, -1, np.int32)
    rc = np.full(size - 1, -1, np.int32)
    sf = rng.integers(0, num_features, size - 1).astype(np.int32)
    sb = rng.integers(max_bin // 4, 3 * max_bin // 4 + 1, size - 1).astype(np.int32)
    dl = rng.integers(0, 2, size - 1).astype(bool)
    parent = {0: None}
    for t in range(num_leaves - 1):
        leaves = list(parent)
        leaf = max(leaves) if chain else leaves[rng.integers(len(leaves))]
        if parent[leaf] is not None:
            node, right = parent[leaf]
            (rc if right else lc)[node] = t
        lc[t], rc[t] = ~leaf, ~(t + 1)
        parent[leaf], parent[t + 1] = (t, False), (t, True)
    return sf, sb, dl, lc, rc


def _walker(bins, nan_bins, tree, leaf_value):
    return predict._walk_tree_values(
        jnp.asarray(bins), jnp.asarray(nan_bins), *map(jnp.asarray, tree),
        jnp.asarray(leaf_value),
    )


_WALK_CASES = {
    # name: (table leaves, leaves grown, features, rows, bins dtype, max bin,
    #        chain, nan bins: "none" | "mixed" | "all", default_left)
    "chain_254_deep": (255, 255, 5, 700, np.uint8, 255, True, "mixed", None),
    "balanced_255": (255, 255, 28, 1000, np.uint8, 255, False, "mixed", None),
    "single_split": (2, 2, 3, 300, np.uint8, 255, False, "mixed", None),
    "padded_nodes": (255, 40, 28, 900, np.uint8, 255, False, "mixed", None),
    "never_split": (31, 1, 4, 200, np.uint8, 255, False, "none", None),
    "nan_default_left": (63, 63, 6, 800, np.uint8, 63, False, "all", True),
    "nan_default_right": (63, 63, 6, 800, np.uint8, 63, False, "all", False),
    "no_nan_bins": (63, 63, 6, 800, np.uint8, 63, False, "none", None),
    "features_1": (31, 31, 1, 513, np.uint8, 255, False, "mixed", None),
    "features_28": (127, 127, 28, 513, np.uint8, 255, False, "mixed", None),
    "features_67": (255, 255, 67, 1300, np.uint8, 255, False, "mixed", None),
    "features_2000": (31, 31, 2000, 257, np.uint8, 255, False, "mixed", None),
    "bins_uint16": (127, 127, 9, 600, np.uint16, 1000, False, "mixed", None),
    "bins_int32": (31, 31, 9, 400, np.int32, 70000, False, "mixed", None),
}


@pytest.mark.parametrize("case", sorted(_WALK_CASES))
def test_the_contraction_reaches_the_walkers_leaf(case):
    size, grown, f, n, dtype, max_bin, chain, nan_kind, dl_all = _WALK_CASES[case]
    rng = np.random.default_rng(sorted(_WALK_CASES).index(case))
    tree = _random_tree(rng, size, grown, f, max_bin, chain)
    if dl_all is not None:
        tree = tree[:2] + (np.full(size - 1, dl_all),) + tree[3:]
    bins = rng.integers(0, max_bin + 1, (n, f)).astype(dtype)
    has_nan = {"none": np.zeros(f, bool), "all": np.ones(f, bool),
               "mixed": rng.random(f) < 0.5}[nan_kind]
    nan_bins = np.where(has_nan, max_bin, -1).astype(np.int32)
    # distinct values, so equal bits mean the same leaf for every row
    leaf_value = (np.arange(size) + 0.25).astype(np.float32)
    want = _walker(bins, nan_bins, tree, leaf_value)
    got = tree_values(
        jnp.asarray(bins), jnp.asarray(nan_bins), *map(jnp.asarray, tree),
        jnp.asarray(leaf_value),
    )
    assert np.array_equal(_bits(got), _bits(want))
    if grown > 2 and not chain:
        assert len(np.unique(np.asarray(want))) > 2  # the rows do spread


def test_row_blocks_that_do_not_divide_the_rows():
    rng = np.random.default_rng(11)
    tree = _random_tree(rng, 63, 63, 12, 255)
    bins = rng.integers(0, 256, (1000, 12)).astype(np.uint8)
    nan_bins = np.full(12, -1, np.int32)
    leaf_value = rng.normal(size=63).astype(np.float32)
    args = (jnp.asarray(bins), jnp.asarray(nan_bins), *map(jnp.asarray, tree),
            jnp.asarray(leaf_value))
    whole = tree_values(*args)
    for block in (128, 384, 999):  # 1000 = 7 * 128 + 104 = 2 * 384 + 232
        assert np.array_equal(_bits(tree_values(*args, block_rows=block)), _bits(whole))
    assert np.array_equal(_bits(whole), _bits(_walker(bins, nan_bins, tree, leaf_value)))


# ------------------------------------------------- which trees keep the walker
@pytest.fixture
def telemetry():
    ses = get_session()
    was = ses.enabled
    ses.configure(enabled=True)
    ses.reset()
    yield ses
    ses.reset()
    ses.configure(enabled=was)


@pytest.fixture
def gather_forms(monkeypatch):
    """The parent's forms forced back in: ``leaf_value[leaf_id]`` as a gather
    and every validation tree through the walker."""
    def force():
        monkeypatch.setattr(score_lookup, "ONEHOT_MAX_LEAVES", 0)
        jax.clear_caches()

    yield force
    monkeypatch.undo()
    jax.clear_caches()


def _binary_table(n, f=8, seed=0, nan_share=0.03):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.normal(size=n) > 0).astype(np.float64)
    X[rng.random(X.shape) < nan_share] = np.nan
    return X, y


def _numeric_sets():
    X, y = _binary_table(1200)
    Xv, yv = _binary_table(333, seed=1)
    return {}, (X, y), (Xv, yv)


def _categorical_sets():
    rng = np.random.default_rng(5)
    X, y = _binary_table(1500, nan_share=0.0)
    X[:, 3] = rng.integers(0, 12, 1500)
    y = ((X[:, 3] % 3 == 0) ^ (X[:, 0] > 0)).astype(np.float64)
    return ({"categorical_feature": [3], "min_data_per_group": 10},
            (X[:1200], y[:1200]), (X[1200:], y[1200:]))


def _bundled_sets():
    rng = np.random.default_rng(9)
    codes = rng.integers(0, 6, 1500)
    X = np.zeros((1500, 8))
    X[np.arange(1500), codes] = 1.0  # six mutually exclusive columns
    X[:, 6:] = rng.normal(size=(1500, 2))
    y = (codes % 2 + X[:, 6] > 0.5).astype(np.float64)
    return {"min_data_in_leaf": 5}, (X[:1200], y[:1200]), (X[1200:], y[1200:])


def test_a_numeric_booster_contracts_and_says_so(telemetry):
    X, y = _binary_table(1200)
    Xv, yv = _binary_table(333, seed=1)
    train = lgb.Dataset(X, y)
    b = lgb.train({"objective": "binary", "verbosity": -1, "num_leaves": 7},
                  train, 3, valid_sets=[lgb.Dataset(Xv, yv, reference=train)])
    assert b._score_span_args() == {"score_lookup": "onehot", "valid_walk": "contract",
                                    "leaf_ids": "none"}  # the CPU's hist_mode is not seg
    assert telemetry.counters.get("score/valid_contract_trees") == 3
    assert "score/valid_walk_trees" not in telemetry.counters
    plain = lgb.train({"objective": "binary", "verbosity": -1, "num_leaves": 7},
                      lgb.Dataset(X, y), 2)
    assert plain._score_span_args()["valid_walk"] == "none"


def test_a_categorical_tree_takes_the_walker_and_says_so(telemetry):
    extra, (X, y), (Xv, yv) = _categorical_sets()
    params = {"objective": "binary", "verbosity": -1, "num_leaves": 7,
              "cat_smooth": 1.0, **extra}
    train = lgb.Dataset(X, y, categorical_feature=[3])
    b = lgb.train(params, train, 3, valid_sets=[lgb.Dataset(Xv, yv, reference=train)])
    assert b._score_span_args() == {"score_lookup": "onehot", "valid_walk": "walk",
                                    "leaf_ids": "none"}
    assert telemetry.counters.get("score/valid_walk_trees") == 3
    assert "score/valid_contract_trees" not in telemetry.counters


def test_an_efb_bundled_dataset_scores_its_validation_rows(gather_forms):
    """Bundle splits ride the categorical mask, so by the static shape these
    trees keep the walker; the predicate is on the bundle's bins either way."""
    extra, (X, y), (Xv, yv) = _bundled_sets()
    params = {"objective": "binary", "verbosity": -1, "num_leaves": 7, **extra}

    def run():
        train = lgb.Dataset(X, y)
        ev = {}
        b = lgb.train(params, train, 4, valid_sets=[lgb.Dataset(Xv, yv, reference=train)],
                      callbacks=[lgb.record_evaluation(ev)])
        return b, ev

    b, ev = run()
    assert b._has_bundle and b._score_span_args()["valid_walk"] == "walk"
    gather_forms()
    b2, ev2 = run()
    assert b.model_to_string() == b2.model_to_string() and ev == ev2


@pytest.mark.parametrize("sets", [_numeric_sets, _categorical_sets, _bundled_sets])
def test_the_span_arg_names_the_form_the_counter_counted(sets, telemetry):
    """``valid_walk`` is said before a tree exists, from the grower's own
    rule for its trees' ``cat_mask`` (``grower.cat_mask_width``); the counter
    reads the grown tree.  They have to name one form."""
    extra, (X, y), (Xv, yv) = sets()
    train = lgb.Dataset(X, y, categorical_feature=extra.get("categorical_feature", "auto"))
    b = lgb.train({"objective": "binary", "verbosity": -1, "num_leaves": 7, **extra},
                  train, 2, valid_sets=[lgb.Dataset(Xv, yv, reference=train)])
    said = b._score_span_args()["valid_walk"]
    counted = {k.split("_")[1] for k in telemetry.counters if k.startswith("score/valid_")}
    assert counted == {said}
    assert telemetry.counters[f"score/valid_{said}_trees"] == 2


_EAGER = {
    "gbdt": {"objective": "binary"},
    "three_classes": {"objective": "multiclass", "num_class": 3},
    "dart": {"objective": "binary", "boosting": "dart", "drop_rate": 0.5},
    "rf": {"objective": "binary", "boosting": "rf", "bagging_fraction": 0.6,
           "bagging_freq": 1},
    "launch": {"objective": "binary", "train_steps_per_launch": 2},
}


@pytest.mark.parametrize("case", sorted(_EAGER))
def test_no_booster_looks_leaves_up_outside_a_program(case, monkeypatch):
    """Dispatched eagerly ``leaf_lookup``'s one-hot is an [Lp, N] array (6 GB
    at 8M rows and 255 leaves); inside a program the compiler builds it in
    the matmul's fusion.  So every call site sits under a jit: the row ids it
    sees are tracers."""
    from lightgbm_tpu.boosting import gbdt, launch, rf

    seen = []

    def traced_only(leaf_value, leaf_id):
        seen.append(isinstance(leaf_id, jax.core.Tracer))
        return score_lookup.leaf_lookup(leaf_value, leaf_id)

    for mod in (gbdt, launch, rf):
        monkeypatch.setattr(mod, "leaf_lookup", traced_only)
    jax.clear_caches()
    X, y = _binary_table(900, seed=12)
    if "num_class" in _EAGER[case]:
        y = _three_class_labels(X)
    train = lgb.Dataset(X, y)
    lgb.train({"verbosity": -1, "num_leaves": 7, **_EAGER[case]}, train, 4,
              valid_sets=[train])
    jax.clear_caches()
    assert seen and all(seen)


# ------------------------------------------------------------- end to end
def _three_class_labels(X):
    return np.nan_to_num(np.abs(X[:, 0]) * 1.5).astype(int).clip(0, 2)


_E2E = {
    "binary": ({"objective": "binary"}, False),
    "three_classes": ({"objective": "multiclass", "num_class": 3}, True),
    "bagging": ({"objective": "binary", "bagging_fraction": 0.6,
                 "bagging_freq": 1}, False),
    "dart": ({"objective": "binary", "boosting": "dart", "drop_rate": 0.5}, False),
    "rf": ({"objective": "binary", "boosting": "rf", "bagging_fraction": 0.6,
            "bagging_freq": 1}, False),
    "early_stopping": ({"objective": "binary", "early_stopping_round": 50}, False),
}


@pytest.mark.parametrize("case", sorted(_E2E))
def test_training_with_a_validation_set_matches_the_gather_forms(case, gather_forms):
    extra, multi = _E2E[case]
    X, y = _binary_table(1500, seed=2)
    Xv, yv = _binary_table(401, seed=3)
    if multi:
        y, yv = _three_class_labels(X), _three_class_labels(Xv)
    params = {"verbosity": -1, "num_leaves": 15, **extra}

    def run():
        train = lgb.Dataset(X, y)
        valid = lgb.Dataset(Xv, yv, reference=train)
        ev = {}
        b = lgb.train(params, train, 8, valid_sets=[train, valid],
                      callbacks=[lgb.record_evaluation(ev)])
        return (b.model_to_string(), ev, _bits(b._score),
                [_bits(e.score) for e in b._valid])

    new = run()
    gather_forms()
    old = run()
    assert new[0] == old[0]  # the same model text
    assert new[1] == old[1]  # the same evaluation history, digit for digit
    assert np.array_equal(new[2], old[2])
    assert all(np.array_equal(a, b) for a, b in zip(new[3], old[3]))


def test_rollback_puts_training_and_validation_scores_back():
    """Rollback adds the negated leaves through the same lookups: what it
    leaves is (s + v) - v in float32, v the tree's own value for the row."""
    X, y = _binary_table(1500, seed=4)
    Xv, yv = _binary_table(401, seed=5)
    train = lgb.Dataset(X, y)
    b = lgb.Booster({"objective": "binary", "verbosity": -1, "num_leaves": 15}, train)
    b.add_valid(lgb.Dataset(Xv, yv, reference=train), "v")
    for _ in range(3):
        b.update()
    before = (np.asarray(b._score).copy(), np.asarray(b._valid[0].score).copy())
    b.update()
    b.rollback_one_iter()
    assert len(b.models_) == 3
    for was, now in zip(before, (b._score, b._valid[0].score)):
        assert np.abs(was - np.asarray(now)).max() <= 2e-7


def test_rollback_matches_the_gather_forms(gather_forms):
    X, y = _binary_table(1200, seed=6)
    Xv, yv = _binary_table(300, seed=7)
    params = {"objective": "binary", "verbosity": -1, "num_leaves": 15}

    def run():
        train = lgb.Dataset(X, y)
        b = lgb.Booster(params, train)
        b.add_valid(lgb.Dataset(Xv, yv, reference=train), "v")
        for _ in range(4):
            b.update()
        b.rollback_one_iter()
        return _bits(b._score), _bits(b._valid[0].score)

    new = run()
    gather_forms()
    old = run()
    assert np.array_equal(new[0], old[0]) and np.array_equal(new[1], old[1])


def test_add_valid_replays_a_trained_booster_through_the_contraction(gather_forms):
    X, y = _binary_table(1200, seed=8)
    Xv, yv = _binary_table(300, seed=9)
    train = lgb.Dataset(X, y)
    b = lgb.train({"objective": "binary", "verbosity": -1, "num_leaves": 15},
                  train, 5)

    def replay():
        b._valid.clear()
        b.add_valid(lgb.Dataset(Xv, yv, reference=train), "late")
        return _bits(b._valid[0].score)

    new = replay()
    gather_forms()
    assert np.array_equal(new, replay())


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 devices")
def test_row_sharded_validation_scores_contract_shard_by_shard(gather_forms):
    X, y = _binary_table(2000, seed=10)
    Xv, yv = _binary_table(403, seed=11)  # 403: padded up to the mesh
    params = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
              "tree_learner": "data"}

    def run():
        train = lgb.Dataset(X, y)
        ev = {}
        b = lgb.train(params, train, 5,
                      valid_sets=[lgb.Dataset(Xv, yv, reference=train)],
                      callbacks=[lgb.record_evaluation(ev)])
        assert b._mesh is not None
        assert predict.row_mesh_of(b._valid[0].bins) is not None
        return b.model_to_string(), ev, _bits(b._valid[0].score)

    new = run()
    gather_forms()
    old = run()
    assert new[0] == old[0] and new[1] == old[1]
    assert np.array_equal(new[2], old[2])
