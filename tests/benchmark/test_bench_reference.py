"""The system against the plain reference at a small size on the CPU, for
each configuration; and the control (bfloat16 statistics in the program's
place) that has to come out as not correct."""

import json

import numpy as np
import pytest

from benchmark import contract, data as bdata
from benchmark.reference import gbdt

ROWS = 30_000


def _small_params(cfg):
    # published widths (leaves, bins) stay; the hessian floor scales with rows
    p = dict(cfg["params"])
    p["min_sum_hessian_in_leaf"] = 20
    p["num_leaves"] = 63
    return p


def _limits(cfg):
    return cfg["limits"]


@pytest.fixture(scope="module", params=["higgs", "criteo67"])
def trained(request):
    import lightgbm_tpu as lgb

    m = contract.Manifest()
    cfg = json.load(open(m.path("configs", request.param + ".json")))
    params = _small_params(cfg)
    f = int(cfg["features"])
    blocks, y = bdata.make_blocks(2_147_483_659, ROWS, f, recipe=cfg["data"])
    vblocks, vy = bdata.make_blocks(2_147_483_659, 2_000, f, valid=True,
                                    recipe=cfg["data"])
    dtrain = lgb.Dataset(blocks, y, params=dict(params))
    dvalid = lgb.Dataset(vblocks, vy, reference=dtrain)
    evals = []
    booster = lgb.train(
        dict(params, verbosity=-1), dtrain, 3, valid_sets=[dvalid],
        callbacks=[lambda env: evals.append(env.evaluation_result_list[0][2])],
    )
    dumps = [t["tree_structure"] for t in booster.dump_model()["tree_info"]]
    return dict(cfg=cfg, params=params, blocks=blocks, y=y, vblocks=vblocks,
                vy=vy, evals=evals, dumps=dumps, name=request.param)


def _follow(t, dumps, **kw):
    ref = __import__("importlib").import_module(f"benchmark.reference.{t['name']}")
    return ref.follow_model(dumps, blocks=t["blocks"], y=t["y"], params=t["params"],
                            recipe=t["cfg"]["data"], **kw)


def test_system_agrees_with_reference(trained):
    t = trained
    nums = _follow(t, t["dumps"], valid_blocks=t["vblocks"], valid_y=t["vy"],
                   valid_metric=t["evals"])
    lim = _limits(t["cfg"])
    if "valid_logloss_gap" not in lim:  # no cell of this configuration evaluates yet
        assert nums.pop("valid_logloss_gap") < 1e-5
    assert set(nums) <= set(lim)
    for name, v in nums.items():
        assert v <= lim[name], (name, v, lim[name])
    assert nums["count_mismatch"] == 0


def test_lower_precision_control_does_not(trained):
    t = trained
    nums = _follow(t, t["dumps"], control="bfloat16")
    lim = _limits(t["cfg"])
    failed = [n for n, v in nums.items() if n in lim and v > lim[n]]
    assert failed, nums
    sound = _follow(t, t["dumps"])
    # the limit sits between the two readings with room on both sides
    for n in failed:
        assert nums[n] >= 3 * sound[n], (n, nums[n], sound[n])


def test_reference_grower_matches_the_system_tree(trained):
    """The same arithmetic run forward grows the system's first tree, and a
    tree grown from bfloat16 statistics is judged not correct."""
    t = trained
    cols, values = gbdt.levels_of(t["blocks"], t["cfg"]["data"])
    y = np.asarray(t["y"], np.float64)
    bias = gbdt.init_score(y)
    g, h = gbdt.gradients(np.full(len(y), bias), y)
    mine = gbdt.grow_tree(cols, values, g, h, t["params"], bias=bias)
    theirs = gbdt.tree_from_dump(t["dumps"][0])
    assert mine.n_leaves == theirs.n_leaves
    # the same root; below it a near-tie may flip and renumber the nodes, so
    # the trees are held together by what they learnt, not node by node
    assert mine.feature[0] == theirs.feature[0]
    assert mine.threshold[0] == pytest.approx(theirs.threshold[0])
    loss = [gbdt.logloss(tr.leaf_value[gbdt.walk(tr, t["blocks"])], y)
            for tr in (mine, theirs)]
    assert abs(loss[0] - loss[1]) / loss[1] < 1e-4, loss
    judged = gbdt.follow([mine], t["blocks"], y, cols, values, t["params"])
    assert max(judged.values()) < 1e-9, judged
    # second tree: gradients are no longer two values that bfloat16 holds
    # exactly, so rounding them shows
    score = theirs.leaf_value[gbdt.walk(theirs, t["blocks"])]
    g1, h1 = gbdt.gradients(score, y)
    low = gbdt.grow_tree(cols, values, g1, h1, t["params"], round_stats="bfloat16")
    judged_low = gbdt.follow([theirs, low], t["blocks"], y, cols, values, t["params"])
    lim = _limits(t["cfg"])
    assert any(v > lim[n] for n, v in judged_low.items()), judged_low
    sound = gbdt.grow_tree(cols, values, g1, h1, t["params"])
    judged_sound = gbdt.follow([theirs, sound], t["blocks"], y, cols, values, t["params"])
    assert all(v <= lim[n] for n, v in judged_sound.items()), judged_sound


def test_walk_and_sums_by_hand():
    tree = gbdt.Tree(
        feature=np.array([1, 0]), threshold=np.array([0.5, -0.25]),
        left=np.array([1, ~0]), right=np.array([~2, ~1]),
        gain=np.zeros(2), internal_count=np.array([5, 3]),
        leaf_value=np.array([1.0, 2.0, 3.0]), leaf_count=np.array([1, 2, 2]),
    )
    x = np.array([[-1.0, 0.0], [0.0, 0.5], [1.0, 0.25], [0.0, 1.0], [5.0, 0.75]],
                 np.float32)
    assert gbdt.walk(tree, [x[:2], x[2:]]).tolist() == [0, 1, 1, 2, 2]
    assert gbdt.predict(tree, [x]).tolist() == [1.0, 2.0, 2.0, 3.0, 3.0]
    r = gbdt.round_bfloat16(np.array([1.0, 1.00390625, 0.1]))
    assert r[0] == 1.0 and r[1] in (1.0, 1.0078125) and abs(r[2] - 0.1) < 0.1 * 2**-8
    assert gbdt.logloss(np.array([0.0, 0.0]), np.array([1.0, 0.0])) == pytest.approx(np.log(2))
