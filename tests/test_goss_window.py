"""Row sampling on the segment path (PR 35): trees grown on the in-bag window.

GOSS's draws are a function of (bagging_seed, iteration, global row), its
threshold an exact selection; with a sampler the grower brings the in-bag
rows to the front of the packed buffer once a tree (``GrowerParams.bag_window``)
and every kernel call sees in-bag rows only; rows never partitioned find
their leaf by the tree's own walk.  Held here: the launch scan against the
serial loop byte for byte across the iteration-10 boundary, one device
against an 8-device mesh, the windows the kernels are handed, out-of-bag
scores against ``Booster.predict``, and a booster without a sampler tracing
nothing of it.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import sampling
from lightgbm_tpu.obs.flight import get_flight
from lightgbm_tpu.ops import segpart
from lightgbm_tpu.ops.grower import GrowerParams, grow_tree
from lightgbm_tpu.ops.pallas import grow_step, partition, seg

RNG = np.random.default_rng(5)
N, F = 1203, 10  # not a multiple of 8: a mesh pads the rows
X = RNG.normal(size=(N, F)).astype(np.float32)
Y = ((X[:, 0] + np.sin(2 * X[:, 1]) + 0.3 * RNG.normal(size=N)) > 0).astype(np.float32)
GOSS = {
    "objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
    "min_data_in_leaf": 5, "verbosity": -1, "seed": 3,
    "data_sample_strategy": "goss", "top_rate": 0.2, "other_rate": 0.1,
    "bagging_seed": 77, "hist_mode": "seg",
}


def _strip(dump: str) -> str:
    return re.sub(r"\[train_steps_per_launch: [^\]]*\]\n?", "", dump)


def _train(extra, rounds=16):
    return lgb.train({**GOSS, **extra}, lgb.Dataset(X, label=Y), num_boost_round=rounds)


# ------------------------------------------------------------- the draws
@pytest.mark.parametrize("k", [1, 17, 400, 1999, 2000])
def test_kth_largest_is_the_sorted_array_s(k):
    v = np.abs(RNG.normal(size=2000)).astype(np.float32)
    v[::7] = v[3]  # ties
    v[::11] = 0.0
    v[5] = np.float32(1e-42)  # a denormal
    got = jax.jit(sampling.kth_largest, static_argnums=1)(jnp.asarray(v), k)
    assert float(got) == float(np.sort(v)[len(v) - k])


def test_goss_takes_every_tie_and_amplifies_the_rest():
    g = np.clip(RNG.normal(size=(1, 1000)), -1.5, 1.5).astype(np.float32)
    g[0, :300] = 2.0  # 300 rows tie at the top; top_k is 200
    h = np.ones((1, 1000), np.float32)
    mask, g2, h2, top_rows, ties = sampling.goss_sample(
        jnp.asarray(g), jnp.asarray(h), jnp.uint32(12), np.uint32(9),
        n=1000, top_k=200, other_k=100)
    mask, g2, h2 = (np.asarray(a) for a in (mask, g2, h2))
    assert (int(top_rows), int(ties)) == (300, 300) and mask[:300].all()
    rest = mask[300:] > 0
    assert 50 < rest.sum() < 130  # 700 rows at 100 / 800
    assert np.array_equal(g2[0, :300], g[0, :300])
    np.testing.assert_array_equal(g2[0, 300:][rest], g[0, 300:][rest] * np.float32(8))
    np.testing.assert_array_equal(h2[0, 300:][rest], np.float32(8))
    assert not g2[0, 300:][~rest].any() and not h2[0, 300:][~rest].any()
    # another iteration or seed: another rest
    other = np.asarray(sampling.goss_sample(
        jnp.asarray(g), jnp.asarray(h), jnp.uint32(13), np.uint32(9),
        n=1000, top_k=200, other_k=100)[0])
    assert (other != mask).sum() > 50


@pytest.mark.parametrize("n", [1, 8])
def test_scan_equals_the_serial_loop_across_the_warmup_boundary(n):
    """learning_rate 0.1: ten unsampled iterations, so the second launch of
    eight holds the boundary inside its scan."""
    serial = _train({"train_steps_per_launch": 1})
    assert serial._grower_params.bag_window
    roots = [t["tree_structure"]["internal_count"]
             for t in serial.dump_model()["tree_info"]]
    assert roots[:10] == [N] * 10 and all(0.28 * N < r < 0.36 * N for r in roots[10:])
    if n > 1:
        got = _train({"train_steps_per_launch": n})
        assert _strip(got.model_to_string()) == _strip(serial.model_to_string())


def test_one_device_and_a_mesh_of_eight_draw_the_same_bag():
    one = lgb.Booster({**GOSS}, lgb.Dataset(X, label=Y))
    mesh = lgb.Booster({**GOSS, "tree_learner": "data", "num_machines": 8},
                       lgb.Dataset(X, label=Y))
    assert mesh._mesh is not None and mesh._pad_rows > 0
    g = RNG.normal(size=(1, N)).astype(np.float32)
    h = (RNG.random((1, N)) + 0.5).astype(np.float32)
    pad = np.zeros((1, mesh._pad_rows), np.float32)
    masks = []
    for b, gg, hh in ((one, g, h), (mesh, np.hstack([g, pad]), np.hstack([h, pad]))):
        b._iter = 12
        mask, g2, _ = b._sample(jnp.asarray(gg), jnp.asarray(hh))
        masks.append((np.asarray(mask)[:N], np.asarray(g2)[0, :N]))
    assert np.array_equal(masks[0][0], masks[1][0])
    assert np.array_equal(masks[0][1], masks[1][1])
    assert 0.28 * N < masks[0][0].sum() < 0.36 * N
    assert not np.asarray(mask)[N:].any()  # padding is never in the bag


def test_mesh_grows_the_serial_trees_under_goss():
    serial = _train({"train_steps_per_launch": 1}, rounds=13)
    mesh = _train({"train_steps_per_launch": 1, "tree_learner": "data",
                   "num_machines": 8}, rounds=13)
    for a, b in zip(serial.dump_model()["tree_info"], mesh.dump_model()["tree_info"]):
        a, b = a["tree_structure"], b["tree_structure"]
        assert a["internal_count"] == b["internal_count"]
        assert (a["split_feature"], a["threshold"]) == (b["split_feature"], b["threshold"])


# --------------------------------------------- the windows the kernels see
B = 64


def _table(n=1500, f=6, seed=2):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, B - 1, size=(n, f))
    signal = (bins[:, 1] > 30) * 1.0 + (bins[:, 4] > 45) * 0.5
    grad = (signal - signal.mean() + 0.05 * rng.normal(size=n)).astype(np.float32)
    hess = (rng.random(n) + 0.5).astype(np.float32)
    mask = (rng.random(n) < 0.3).astype(np.float32)
    return bins, grad * mask, hess * mask, mask


def _grow(bins, grad, hess, mask, mode="seg", **over):
    n, f = bins.shape
    params = GrowerParams(
        num_leaves=8, max_bin=B, min_data_in_leaf=5, min_sum_hessian_in_leaf=0.0,
        lambda_l2=0.1, hist_mode=mode, hist_acc="bf16", **over)
    tree, leaf_id = grow_tree(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask),
        jnp.full((f,), B, jnp.int32), jnp.full((f,), -1, jnp.int32),
        jnp.ones(f, bool), params)
    return tree, np.asarray(leaf_id)


@pytest.fixture
def windows(monkeypatch):
    """The Pallas kernels in interpret mode, every (begin, count) they are
    handed recorded."""
    monkeypatch.setattr(seg, "_INTERPRET", True)
    monkeypatch.setattr(partition, "_INTERPRET", True)
    seen = {"hist": [], "partition": [], "compact": []}
    real_hist, real_part = seg.seg_hist, segpart.sort_partition

    def hist(seg_arr, scal, **kw):
        jax.debug.callback(lambda s: seen["hist"].append(tuple(map(int, s))), scal)
        return real_hist(seg_arr, scal, **kw)

    def part(seg_arr, sbegin, cnt, *a, bag_compact=False, **kw):
        key = "compact" if bag_compact else "partition"
        jax.debug.callback(lambda b, c: seen[key].append((int(b), int(c))), sbegin, cnt)
        return real_part(seg_arr, sbegin, cnt, *a, bag_compact=bag_compact, **kw)

    monkeypatch.setattr(seg, "seg_hist", hist)
    monkeypatch.setattr(segpart, "sort_partition", part)
    jax.clear_caches()
    yield seen
    jax.clear_caches()


def test_every_kernel_window_lies_inside_the_bag(windows):
    bins, grad, hess, mask = _table()
    n, n_bag = len(mask), int(mask.sum())
    tree, leaf_id = _grow(bins, grad, hess, mask, bag_window=True)
    jax.effects_barrier()
    assert windows["compact"] == [(0, n)]
    assert windows["hist"][0] == (0, n_bag)  # the root
    live = [w for w in windows["hist"][1:] + windows["partition"] if w[1] > 0]
    assert len(live) >= 2 * (int(tree.num_leaves) - 1)
    assert all(b + c <= n_bag for b, c in live)
    assert float(tree.internal_count[0]) == n_bag
    assert float(np.asarray(tree.leaf_count).sum()) == n_bag
    # the masked whole-table path grows the same tree, and every row's leaf
    # (in the bag or out of it) is the walk's
    want, want_leaf = _grow(bins, grad, hess, mask, mode="ordered")
    for name in ("split_feature", "split_bin", "left_child", "right_child"):
        assert np.array_equal(np.asarray(getattr(tree, name)),
                              np.asarray(getattr(want, name))), name
    np.testing.assert_allclose(np.asarray(tree.leaf_value), np.asarray(want.leaf_value),
                               rtol=1e-4, atol=1e-6)
    assert np.array_equal(leaf_id, want_leaf)


def test_a_full_bag_moves_nothing_and_grows_the_unwindowed_tree(windows):
    bins, grad, hess, _ = _table()
    rng = np.random.default_rng(8)
    grad = rng.normal(size=len(grad)).astype(np.float32) + (bins[:, 1] > 30)
    hess = np.ones_like(grad)
    ones = np.ones_like(grad)
    a, la = _grow(bins, grad, hess, ones, bag_window=True)
    b, lb = _grow(bins, grad, hess, ones)
    jax.effects_barrier()
    assert windows["compact"] == [(0, len(ones))]
    assert np.array_equal(la, lb)
    for name in ("split_feature", "split_bin", "leaf_value", "split_gain"):
        assert np.array_equal(np.asarray(getattr(a, name)), np.asarray(getattr(b, name)))


def test_the_fused_step_grows_the_two_launch_tree_on_the_window(monkeypatch):
    monkeypatch.setattr(seg, "_INTERPRET", True)
    monkeypatch.setattr(partition, "_INTERPRET", True)
    monkeypatch.setattr(grow_step, "_INTERPRET", True)
    jax.clear_caches()
    bins, grad, hess, mask = _table(seed=4)
    two, l2 = _grow(bins, grad, hess, mask, bag_window=True)
    fused, lf = _grow(bins, grad, hess, mask, bag_window=True, grow_fused=True)
    jax.clear_caches()
    assert int(two.num_leaves) > 2 and np.array_equal(l2, lf)
    for name in ("split_feature", "split_bin", "left_child", "right_child",
                 "internal_count", "leaf_count"):
        assert np.array_equal(np.asarray(getattr(two, name)),
                              np.asarray(getattr(fused, name))), name


def test_wide_masks_and_feature_shards_keep_the_masked_path():
    from lightgbm_tpu.ops.grower import bag_window_ok

    p = GrowerParams(num_leaves=8, max_bin=B, hist_mode="seg", bag_window=True)
    assert bag_window_ok(p, 1)
    assert not bag_window_ok(p, B)  # categorical / bundled trees: the walker's
    assert not bag_window_ok(GrowerParams(num_leaves=8, max_bin=B, hist_mode="ordered"), 1)
    assert not bag_window_ok(
        GrowerParams(num_leaves=8, max_bin=B, hist_mode="seg", feature_shard=2), 1)
    assert not bag_window_ok(
        GrowerParams(num_leaves=5000, max_bin=B, hist_mode="seg"), 1)


# ------------------------------------------------------ the model's scores
@pytest.mark.parametrize("extra", [
    {}, {"train_steps_per_launch": 8},
    {"data_sample_strategy": "bagging", "bagging_fraction": 0.5, "bagging_freq": 1},
])
def test_out_of_bag_scores_are_predict_s(extra):
    b = _train({"train_steps_per_launch": 1, **extra})
    assert b._grower_params.bag_window
    b._drain_pending()
    score = np.asarray(b._score[0])[:N]
    raw = b.predict(X, raw_score=True)
    np.testing.assert_allclose(score, raw, rtol=0, atol=2e-5)
    # every tree after the warm-up left rows out of its bag
    counts = [t["tree_structure"]["internal_count"] for t in b.dump_model()["tree_info"]]
    assert min(counts) < 0.7 * N


def test_a_fixed_row_mask_turns_the_window_on_and_off():
    b = lgb.Booster({**GOSS, "data_sample_strategy": "bagging"}, lgb.Dataset(X, label=Y))
    assert not b._grower_params.bag_window
    b.set_row_mask(np.arange(N) % 3 > 0)
    assert b._grower_params.bag_window
    for _ in range(3):
        b.update()
    b._drain_pending()
    np.testing.assert_allclose(np.asarray(b._score[0])[:N], b.predict(X, raw_score=True),
                               rtol=0, atol=2e-5)
    assert b.dump_model()["tree_info"][0]["tree_structure"]["internal_count"] == N - N // 3
    b.set_row_mask(None)
    assert not b._grower_params.bag_window


# ------------------------------------------- a booster without a sampler
def _grow_text(booster, scopes=False):
    g = jnp.zeros((N,), jnp.float32)
    fn, args, kwargs = booster._grow_call(g, g + 1, g + 1, booster._full_feature_mask, None)
    return fn.lower(*args, **kwargs).as_text(debug_info=scopes)


def test_a_booster_without_a_sampler_traces_none_of_it():
    plain = {k: v for k, v in GOSS.items()
             if k not in ("data_sample_strategy", "top_rate", "other_rate", "bagging_seed")}
    off = lgb.Booster(plain, lgb.Dataset(X, label=Y))
    on = lgb.Booster(GOSS, lgb.Dataset(X, label=Y))
    assert not off._grower_params.bag_window and on._grower_params.bag_window
    assert type(off._sampler) is sampling.SampleStrategy
    text_off = _grow_text(off)
    named_off, named_on = _grow_text(off, scopes=True), _grow_text(on, scopes=True)
    for name in ("bag_compact", "oob_score"):
        assert name not in named_off and name in named_on, name
    # the parameters differ in the window alone: with it off the sampling
    # booster lowers, byte for byte, the program of the one that samples nothing
    import dataclasses

    on._grower_params = dataclasses.replace(on._grower_params, bag_window=False)
    assert on._grower_params == off._grower_params
    assert _grow_text(on) == text_off


def test_the_launch_event_carries_the_bag_counters():
    before = len([e for e in get_flight().events() if e.get("event") == "launch"])
    _train({"train_steps_per_launch": 8})
    events = [e for e in get_flight().events() if e.get("event") == "launch"][before:]
    assert len(events) == 2
    first, second = events
    assert first["in_bag_rows"] == [N] * 8 and first["top_rows"] == [0] * 8
    assert second["in_bag_rows"][:2] == [N, N] and second["top_rows"][:2] == [0, 0]
    top_k = int(N * 0.2)
    for bag, top, ties in zip(second["in_bag_rows"][2:], second["top_rows"][2:],
                              second["threshold_ties"][2:]):
        assert top >= top_k and ties >= 1 and top - ties < top_k
        assert top < bag < 0.36 * N
    assert [r["in_bag_rows"] for r in second["records"]] == second["in_bag_rows"]
    # a booster that samples nothing reports none of them
    plain = {k: v for k, v in GOSS.items() if k != "data_sample_strategy"}
    lgb.train({**plain, "train_steps_per_launch": 8}, lgb.Dataset(X, label=Y), 8)
    last = [e for e in get_flight().events() if e.get("event") == "launch"][-1]
    assert "in_bag_rows" not in last
