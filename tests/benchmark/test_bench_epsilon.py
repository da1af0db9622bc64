"""What PR 29 added to the benchmark: the ``epsilon`` configuration (400,000 x
2,000 dense: a packed row of 8 plane groups), its cell ``epsilon.fit-eval``,
the cell ``criteo67.fit``, and the readers ``grouped_partition_roofline`` and
``go_left_ms_per_iter``.  Everything is found by name
(``test_bench_contract.py::test_manifest_finds_every_file_by_name`` covers
that unchanged); here: the cells' parameters, the system against the plain
reference on a small 2,000-column table through the grouped path, the byte
count of the grouped partition, and the two readers on hand-built traces."""

import os

import numpy as np
import pytest

from benchmark import contract, data as bdata, work_model
from benchmark.trace_reduce import Op, Trace

ROWS = 3_000


def _reader(metric):
    return contract.load_module(
        os.path.join(contract.BENCH_DIR, "layers", metric + ".py"),
        "benchmark_layer_" + metric)


def _names(cell):
    return {m["name"] for m in cell.per_layer}


def test_epsilon_cell_is_the_issue_s():
    cell = contract.Manifest().cell("epsilon.fit-eval")
    cfg = cell.config
    assert (cfg["rows"], cfg["features"], cell.chips) == (400_000, 2_000, 1)
    assert cfg["params"] == {
        "objective": "binary", "num_leaves": 255, "max_bin": 255,
        "learning_rate": 0.1, "min_data_in_leaf": 1,
        "min_sum_hessian_in_leaf": 100, "hist_acc": "bf16",
        # the path the program resolves by itself, named so that a program
        # from before PR 29 fails at once instead of falling back (assumed)
        "hist_mode": "seg",
    }
    assert cfg["reduced"] == [] and cfg["data"]["kind"] == "grid_normal_linear_logit"
    assert cell.job["name"] == "fit-eval" and cell.job["expect"]["launch_steps"] == 0
    assert set(cfg["limits"]) == {
        "count_mismatch", "floor_violation", "leaf_value_rms_gap",
        "split_gain_rms_gap", "split_regret", "valid_logloss_gap"}
    # the grouped row's kernels are the two-launch ones; the one-group
    # partition roofline (128 planes at most) and the fused step are not its
    names = _names(cell)
    assert {"grouped_partition_roofline", "go_left_ms_per_iter",
            "wide_split_scan_ms_per_iter", "partition_ms_per_iter",
            "histogram_ms_per_iter", "seg_hist_roofline", "grow_kernels_roofline",
            "train_step_mfu", "device_idle_share"} <= names
    assert not names & {"seg_partition_roofline", "fused_step_ms_per_iter",
                        "fused_grow_step_roofline"}


def test_criteo67_fit_cell_is_the_queued_one():
    m = contract.Manifest()
    cell = m.cell("criteo67.fit")
    assert cell.config_name == "criteo67" and cell.chips == 1
    assert cell.job["expect"] == {"hist_mode": "seg", "launch_steps": 8}
    assert _names(cell) == _names(m.cell("higgs.fit"))
    # the new metrics are the new cell's alone: no traced run of an accepted
    # cell is asked for them
    for old in ("higgs.fit", "criteo67.fit-eval"):
        assert not _names(m.cell(old)) & {
            "grouped_partition_roofline", "go_left_ms_per_iter",
            "wide_split_scan_ms_per_iter"}


@pytest.fixture(scope="module")
def trained():
    """Three trees of lgb.train on 3,000 x 2,000 through the grouped segment
    path (asked for by name: off a TPU the default is another path)."""
    import lightgbm_tpu as lgb

    cfg = contract.Manifest().cell("epsilon.fit-eval").config
    params = dict(cfg["params"], num_leaves=15, min_sum_hessian_in_leaf=20)
    f = int(cfg["features"])
    blocks, y = bdata.make_blocks(2_147_483_659, ROWS, f, recipe=cfg["data"])
    vblocks, vy = bdata.make_blocks(2_147_483_659, 400, f, valid=True, recipe=cfg["data"])
    dtrain = lgb.Dataset(blocks, y, params=dict(params))
    dvalid = lgb.Dataset(vblocks, vy, reference=dtrain)
    evals = []
    booster = lgb.train(
        dict(params, verbosity=-1), dtrain, 3, valid_sets=[dvalid],
        callbacks=[lambda env: evals.append(env.evaluation_result_list[0][2])],
    )
    assert booster._grower_params.hist_mode == "seg" and not booster.degraded
    dumps = [t["tree_structure"] for t in booster.dump_model()["tree_info"]]
    return dict(cfg=cfg, params=params, blocks=blocks, y=y, vblocks=vblocks, vy=vy,
                evals=evals, dumps=dumps)


def _follow(t, **kw):
    from benchmark.reference import epsilon

    return epsilon.follow_model(t["dumps"], blocks=t["blocks"], y=t["y"],
                                params=t["params"], recipe=t["cfg"]["data"], **kw)


def test_grouped_trees_pass_the_reference_at_the_configuration_s_limits(trained):
    t = trained
    nums = _follow(t, valid_blocks=t["vblocks"], valid_y=t["vy"], valid_metric=t["evals"])
    lim = t["cfg"]["limits"]
    assert set(nums) == set(lim)
    for name, v in nums.items():
        assert v <= lim[name], (name, v, lim[name])
    assert nums["count_mismatch"] == 0 and len(t["dumps"]) == 3


def test_the_bfloat16_control_fails_them(trained):
    nums = _follow(trained, control="bfloat16")
    lim = trained["cfg"]["limits"]
    assert [n for n, v in nums.items() if n in lim and v > lim[n]], nums


@pytest.mark.parametrize("features,planes", [
    (2000, 1024),  # 1,000 bin planes + the stat block: 8 groups of 128, not 128
    (243, 160), (500, 288), (242, 0), (67, 0), (28, 0),
])
def test_grouped_partition_bytes(features, planes):
    r = _reader("grouped_partition_roofline")
    assert r.group_planes(features) == planes
    assert r.partition_bytes(1000.0, features) == 1000.0 * planes * 2 * 2
    if planes:  # every bin plane and the stats are in, and no group is over its cap
        assert planes >= (features + 1) // 2 + 7 > work_model.storage_planes(features)
        from lightgbm_tpu.ops.pallas import seg

        g, sub = seg.group_shape(features)
        assert g * sub == planes  # the copy of the layout's arithmetic holds


def _tree(n, left):
    return {"split_index": 0, "internal_count": n,
            "left_child": {"leaf_count": left}, "right_child": {"leaf_count": n - left}}


def test_grouped_partition_roofline_on_a_hand_built_trace():
    r = _reader("grouped_partition_roofline")
    peaks = work_model.peaks_for("TPU v5 lite")
    rows = 400_000
    least = rows * 1024 * 4 / peaks["hbm_bytes_per_s"]
    trace = Trace(
        window_s=1.0, planes_found=["/device:TPU:0"], host=[],
        devices={"/device:TPU:0": [
            Op("seg_partition_pallas.3", 0.1, 4 * least, "mosaic s16[8,128,401664]"),
            Op("seg_hist_pallas_batch.4", 0.5, 0.2, "mosaic f32[1,250,8,2048]"),
            Op("fusion.9", 0.8, 0.1, "")]},
    )
    facts = {"trace": trace, "trace_mark": [0.0, 0, 1.0, 1], "chips": 1,
             "tree_dumps": [_tree(rows, 1000)], "features": 2000,
             "device_kind": "TPU v5 lite"}
    assert r.read(facts) == pytest.approx(25.0)
    assert r.read(dict(facts, features=67)) is None  # a one-group row: not its metric
    assert r.read(dict(facts, trace=None)) is None


def test_go_left_reader_on_a_hand_built_trace():
    r = _reader("go_left_ms_per_iter")
    trace = Trace(
        window_s=10.0, planes_found=["/device:TPU:0"], host=[],
        devices={"/device:TPU:0": [
            Op("fusion.1", 1.0, 0.25, ""), Op("fusion.2", 1.5, 0.5, ""),
            Op("seg_partition_pallas.3", 2.0, 1.0, "mosaic s16[8]"),
            Op("fusion.1", 6.0, 0.125, "")]},
        modules={"/device:TPU:0": [Op("jit_grow_tree", 0.5, 4.0, ""),
                                   Op("jit_other", 5.5, 1.0, "")]},
    )
    scopes = {"jit_grow_tree": {"fusion.1": "leaf_loop/go_left",
                                "fusion.2": "leaf_loop/partition"},
              "jit_other": {"fusion.1": "go_left"}}
    facts = {"trace": trace, "op_scopes": scopes, "trace_mark": [0, 0, 10, 2]}
    assert r.read(facts) == pytest.approx(125.0)  # the grow program's alone
    # a program without the scope (a one-group row, the parent): nothing, no error
    scopes["jit_grow_tree"]["fusion.1"] = "leaf_loop/bookkeeping"
    assert r.read(dict(facts, op_scopes=scopes)) is None
    assert r.read({"trace": None, "trace_mark": [0, 0, 10, 2]}) is None
