"""What PR 35 added to the benchmark: the ``criteo67-goss`` configuration
(gradient-based one-side sampling on the Criteo-shaped table), the job
``fit-steady``, the cell ``criteo67-goss.fit-steady``, a plain reference that
draws the bag by itself, and five readers of the sampling layer.  Here: the
cell's parameters, the program's rest draws against the reference's NumPy mix
bit for bit, the system against the reference on a rehearsal and directly,
each planted fault failing a limit, and the readers on a hand-built trace and
on a recorded chip trace of the cell.

The faults can be planted under the unchanged harness on the chip too:

    python3 tests/benchmark/test_bench_criteo67_goss.py <fault> --workload \
        criteo67-goss.fit-steady --seed <n> --seconds <s> --trace 0
"""

import contextlib
import json
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import contract, data as bdata, run as brun, scope_join, trace_reduce  # noqa: E402
from benchmark.trace_reduce import Op, Trace  # noqa: E402

CELL = "criteo67-goss.fit-steady"
NEW_METRICS = {"goss_sample_ms_per_iter", "bag_compact_ms_per_iter", "oob_score_ms_per_iter",
               "in_bag_row_share"}
# its reader is here and pinned; its entry waits in pending_per_layer_pr35.json:
# the parent of PR 35 runs this cell and has no such kernel to read a share of
PENDING = "bag_compact_roofline"


def _reference():
    return contract.load_module(
        contract.Manifest().reference_path("criteo67-goss"),
        "benchmark_reference_criteo67_goss")


def _reader(metric):
    return contract.load_module(
        os.path.join(contract.BENCH_DIR, "layers", metric + ".py"),
        "benchmark_layer_" + metric)


def _names(cell):
    return {m["name"] for m in cell.per_layer}


# ------------------------------------------------------------- the faults
@contextlib.contextmanager
def _goss_sample_as(make):
    """``boosting.sampling.goss_sample`` replaced by ``make(real)`` for the
    programs traced meanwhile."""
    import jax

    from lightgbm_tpu.boosting import sampling

    real = sampling.goss_sample
    sampling.goss_sample = make(real)
    jax.clear_caches()
    try:
        yield
    finally:
        sampling.goss_sample = real
        jax.clear_caches()


def other_bagging_seed():
    """The rest drawn from another seed word."""
    return _goss_sample_as(lambda real: lambda g, h, it, seed, **kw: real(
        g, h, it, np.uint32(int(seed) ^ 0x5BD1E995), **kw))


def no_amplification():
    """The sampled rest left at weight 1."""
    def make(real):
        def sample(g, h, it, seed, **kw):
            mask, _, _, top, ties = real(g, h, it, seed, **kw)
            return mask, g * mask[None, :], h * mask[None, :], top, ties
        return sample
    return _goss_sample_as(make)


def every_row_in_the_bag():
    """No sampling: the mask all ones, the statistics as they came."""
    def make(real):
        def sample(g, h, it, seed, **kw):
            import jax.numpy as jnp

            _, _, _, top, ties = real(g, h, it, seed, **kw)
            return jnp.ones(g.shape[1:], jnp.float32), g, h, top, ties
        return sample
    return _goss_sample_as(make)


def top_set_left_out():
    """A uniform 30 % of the rows at weight 1 / 0.3: no row kept for its
    gradient."""
    def make(real):
        def sample(g, h, it, seed, **kw):
            import jax.numpy as jnp

            from lightgbm_tpu.boosting.sampling import GOSS_STREAM
            from lightgbm_tpu.ops.quantize import hashed_uniforms

            _, _, _, top, ties = real(g, h, it, seed, **kw)
            draws = hashed_uniforms(seed, it | jnp.uint32(GOSS_STREAM), g.shape[1])
            mask = (draws < 0.3).astype(jnp.float32)
            w = mask[None, :] / jnp.float32(0.3)
            return mask, g * w, h * w, top, ties
        return sample
    return _goss_sample_as(make)


FAULTS = {
    "other_bagging_seed": other_bagging_seed,
    "no_amplification": no_amplification,
    "every_row_in_the_bag": every_row_in_the_bag,
    "top_set_left_out": top_set_left_out,
}


# ---------------------------------------------------------------- the cell
def test_goss_cell_is_the_issue_s():
    m = contract.Manifest()
    cell = m.cell(CELL)
    cfg, base = cell.config, m.cell("criteo67.fit").config
    assert (cfg["rows_per_chip"], cfg["features"], cell.chips) == (8_000_000, 67, 1)
    params = dict(cfg["params"])
    assert isinstance(params.pop("bagging_seed"), int)
    assert params == {**base["params"], "data_sample_strategy": "goss",
                      "top_rate": 0.2, "other_rate": 0.1}
    # no engine knob: the program resolves the segment path, the scan and the
    # in-bag window by itself
    assert not set(params) & {"hist_mode", "hist_method", "grow_fused",
                              "train_steps_per_launch"}
    assert cfg["data"] == base["data"]
    entry = next(c for c in m.doc["configs"] if c["name"] == "criteo67-goss")
    assert entry["reduced"] == ["rows_per_chip"] and entry["source"] == cfg["source"]
    assert len(cfg["source"]) <= 200
    fit = m.cell("criteo67.fit").job
    assert cell.job == {**fit, "name": "fit-steady", "what": cell.job["what"],
                        "warmup_iterations": 16, "follow_trees": 17}
    assert set(cfg["limits"]) == {
        "count_mismatch", "floor_violation", "leaf_value_rms_gap",
        "split_gain_rms_gap", "split_regret"}
    assert cfg["limits"]["count_mismatch"] == 0 and cfg["limits"]["floor_violation"] == 0
    assert set(cfg["limits_why"]) == set(cfg["limits"])  # each limit with its reason
    assert all("TBD" not in why for why in cfg["limits_why"].values())
    # the launch-scan cells' metrics on this table, and the sampling layer's four
    assert _names(cell) == _names(m.cell("criteo67.fit")) | NEW_METRICS
    assert {m_["layer"] for m_ in cell.per_layer if m_["name"] in NEW_METRICS} == {"row sampling"}
    with open(os.path.join(contract.BENCH_DIR, "pending_per_layer_pr35.json")) as fh:
        waiting = json.load(fh)
    (entry,) = waiting["entries"]
    assert entry == {"name": PENDING, "unit": "%", "better": "higher", "source": "device_trace",
                     "layer": "row sampling", "moves": "train_iters_per_s", "workloads": [CELL]}
    assert PENDING not in _names(cell) and m.layer_reader_path(PENDING).endswith(".py")


def test_the_cell_is_appended_to_the_five_the_benchmark_had():
    """A prefix, not the whole list: the next cell is appended after it."""
    m = contract.Manifest()
    assert m.workload_names()[:6] == [
        "criteo67.fit-eval", "higgs.fit", "epsilon.fit-eval", "criteo67.fit",
        "criteo67-quant.fit", CELL]
    assert all(m.cell(n).chips == 1 for n in m.workload_names()[:6])


@pytest.mark.parametrize("old", ["higgs.fit", "criteo67.fit", "criteo67.fit-eval",
                                 "epsilon.fit-eval", "criteo67-quant.fit"])
def test_new_metrics_are_the_new_cell_s_alone(old):
    assert not _names(contract.Manifest().cell(old)) & NEW_METRICS


# --------------------------------------------------------------- the draws
@pytest.mark.parametrize("seed,iteration", [(0, 10), (20261004, 16), (2_147_483_659, 23),
                                            (4_294_967_295, 100_000)])
def test_program_draws_equal_the_reference_mix_bit_for_bit(seed, iteration):
    """250,000 rows a case: a million (seed, iteration, row) triples in all."""
    import jax.numpy as jnp

    from lightgbm_tpu.boosting.sampling import GOSS_STREAM
    from lightgbm_tpu.ops.quantize import hashed_uniforms

    ref = _reference()
    assert ref.GOSS_STREAM == GOSS_STREAM
    got = np.asarray(hashed_uniforms(np.uint32(seed), jnp.uint32(iteration) | jnp.uint32(GOSS_STREAM),
                                     250_000))
    want = ref.rest_draws(seed, iteration, 250_000)
    assert got.dtype == np.float32 and np.array_equal(got.astype(np.float64), want)
    # and no stream of quantized training's rounding offsets
    from lightgbm_tpu.ops.quantize import rounding_uniforms

    assert not np.array_equal(
        got, np.asarray(rounding_uniforms(np.uint32(seed), np.int32(iteration), 250_000, 0)))


def test_program_bag_equals_the_reference_bag():
    """float32 against float64: the same bag and the same amplified
    statistics, but for rows whose metric lies in the threshold's band."""
    import jax.numpy as jnp

    from lightgbm_tpu.boosting.sampling import goss_sample

    ref = _reference()
    rng = np.random.default_rng(1)
    n = 200_000
    p = 1.0 / (1.0 + np.exp(-rng.normal(size=n)))
    y = (rng.random(n) < 0.5).astype(np.float64)
    g, h = (p - y).astype(np.float32), (p * (1 - p)).astype(np.float32)
    params = {"top_rate": 0.2, "other_rate": 0.1, "bagging_seed": 5}
    mask, g2, h2, top_rows, ties = goss_sample(
        jnp.asarray(g)[None], jnp.asarray(h)[None], jnp.uint32(12), np.uint32(5),
        n=n, top_k=40_000, other_k=20_000)
    in_bag, weight, open_rows = ref.goss_bag(g.astype(np.float64), h.astype(np.float64), params, 12)
    sure = ~open_rows
    assert open_rows.sum() < 40
    assert np.array_equal(np.asarray(mask)[sure] > 0, in_bag[sure])
    both = in_bag & (np.asarray(mask) > 0) & sure
    np.testing.assert_allclose(np.asarray(g2)[0][both], (g * weight)[both], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(h2)[0][both], (h * weight)[both], rtol=1e-6)
    assert 40_000 <= int(top_rows) < 40_040 and int(ties) >= 1
    assert abs(in_bag.sum() - 60_000) < 1_000


def test_count_mismatch_allows_the_band_rows_and_nothing_else():
    from benchmark.reference import gbdt

    ref = _reference()
    tree = gbdt.Tree(
        feature=np.array([0, 1]), threshold=np.zeros(2), left=np.array([1, ~0]),
        right=np.array([~2, ~1]), gain=np.ones(2), internal_count=np.array([10, 7]),
        leaf_value=np.zeros(3), leaf_count=np.array([4, 3, 3]))
    leaves = np.array([0] * 4 + [1] * 3 + [2] * 3)
    none = leaves[:0]
    assert ref.count_mismatch(tree, leaves, none) == 0
    assert ref.count_mismatch(tree, leaves[1:], none) == 1  # leaf 0, node 1, the root
    # an open row that reaches leaf 0 explains it, one that reaches leaf 2 does not
    assert ref.count_mismatch(tree, leaves[1:], np.array([0])) == 0
    assert ref.count_mismatch(tree, leaves[1:], np.array([2])) == 1


# ------------------------------------------------ the run, on a rehearsal
@pytest.fixture
def small(monkeypatch):
    monkeypatch.setattr(brun, "REHEARSE_ROWS", 24_000)
    monkeypatch.setattr(bdata, "BLOCK_ROWS", 6_000)


def _run(capsys, *extra, seed=2_147_483_777):
    rc = brun.main(["--workload", CELL, "--seed", str(seed), "--seconds", "0.5",
                    "--trace", "0", "--rehearse", *extra])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and out, rc
    return json.loads(out[-1])


def failing(line):
    return sorted(n for n, c in line["checks"].items() if c["value"] > c["limit"])


@pytest.mark.parametrize("seed", [2_147_483_777, 7])
def test_sound_rehearsal_reads_every_number_under_its_limit(capsys, small, seed):
    line = _run(capsys, seed=seed)
    assert line["correct"] is True and line["failed"] == 0, failing(line)
    assert set(contract.Manifest().cell(CELL).config["limits"]) <= set(line["checks"])
    assert line["checks"]["count_mismatch"]["value"] == 0
    assert line["checks"]["trees_followed"]["value"] == 0  # all 17


def test_the_bfloat16_control_fails_the_limits(capsys, small):
    line = _run(capsys, "--control", "bfloat16")
    limits = contract.Manifest().cell(CELL).config["limits"]
    control = line["facts"]["control"]
    over = [n for n, v in control.items() if v > limits[n]]
    assert over and control["count_mismatch"] == 0, control
    assert line["correct"] is True  # the run's own numbers are sound
    judged = [(d["tree"], d["in_bag_rows"]) for d in line["facts"]["detail"]]
    assert [t for t, _ in judged] == [0, 10, 16]
    assert judged[0][1] == 24_000 and all(6_900 < n < 7_700 for _, n in judged[1:])


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_planted_fault_fails_a_limit(capsys, small, fault):
    with FAULTS[fault]():
        line = _run(capsys)
    assert line["correct"] is False
    want = {"no_amplification": {"leaf_value_rms_gap", "split_gain_rms_gap"}}.get(
        fault, {"count_mismatch"})
    assert want <= set(failing(line)), failing(line)


@pytest.mark.parametrize("fault", ["half_rows", "answer_altered"])
def test_the_harness_s_own_faults_fail_it(capsys, small, fault):
    from benchmark import faults

    with faults.FAULTS[fault]():
        line = _run(capsys)
    assert line["correct"] is False
    want = {"half_rows": "count_mismatch", "answer_altered": "leaf_value_rms_gap"}[fault]
    assert want in failing(line), failing(line)


# ------------------------------------------------------------- the readers
def _facts():
    trace = Trace(
        window_s=10.0, planes_found=["/device:TPU:0"], host=[],
        devices={"/device:TPU:0": [
            Op("fusion.1", 1.0, 0.25, ""), Op("fusion.2", 1.5, 0.5, ""),
            Op("bag_compact_pallas.3", 2.0, 0.125, "mosaic s16[64,8001536]"),
            Op("seg_partition_pallas.5", 2.5, 1.0, "mosaic s16[64,8001536]"),
            Op("fusion.4", 3.5, 0.125, ""), Op("fusion.7", 3.75, 0.0625, ""),
            Op("fusion.1", 6.0, 0.0625, "")]},
        modules={"/device:TPU:0": [Op("jit__launch_impl", 0.5, 4.0, ""),
                                   Op("jit_goss_sample", 5.5, 1.0, "")]},
    )
    scopes = {"jit__launch_impl": {"fusion.1": "while/body/sample/sample",
                                   "fusion.2": "while/body/oob_score",
                                   "fusion.4": "while/body/score_update",
                                   "fusion.7": "while/body/bag_compact"},
              "jit_goss_sample": {"fusion.1": "sample"}}
    tree = {"split_index": 0, "internal_count": 2_400_000,
            "left_child": {"leaf_index": 0, "leaf_count": 1_400_000},
            "right_child": {"leaf_index": 1, "leaf_count": 1_000_000}}
    return {"trace": trace, "op_scopes": scopes, "trace_mark": [0, 16, 10, 18],
            "tree_dumps": [tree] * 18, "rows": 8_000_000, "features": 67, "chips": 1,
            "device_kind": "TPU v5 lite"}


@pytest.mark.parametrize("metric,value", [
    ("goss_sample_ms_per_iter", 156.25), ("oob_score_ms_per_iter", 250.0),
    ("bag_compact_ms_per_iter", 93.75),  # the kernel's 62.5 and its glue's 31.25
    ("in_bag_row_share", 30.0),
    # two trees x 8M rows x 64 planes x 2 B x 2 over 819 GB/s, over 0.125 s
    ("bag_compact_roofline", 100.0 * (2 * 8e6 * 64 * 4 / 819e9) / 0.125),
])
def test_readers_on_a_hand_built_trace(metric, value):
    r = _reader(metric)
    facts = _facts()
    assert r.read(facts) == pytest.approx(value, rel=1e-6)
    if metric == "in_bag_row_share":
        return  # read off the model: a parent of PR 35 reports its own bag
    # a program that publishes its scopes and has neither these scopes nor the
    # kernel (no sampler; the parent of PR 35, which runs this cell too): the
    # two times read a measured 0, which the harness's validator takes; the
    # scope ``sample`` and a share of a kernel that never ran read nothing
    bare = {mod: {op: "while/body/bookkeeping" for op in ops}
            for mod, ops in facts["op_scopes"].items()}
    facts["trace"].devices["/device:TPU:0"] = [
        o for o in facts["trace"].devices["/device:TPU:0"] if "bag_compact" not in o.name]
    want = 0.0 if metric in ("bag_compact_ms_per_iter", "oob_score_ms_per_iter") else None
    assert r.read(dict(facts, op_scopes=bare)) == want
    # no device trace, or a program that publishes no scopes: an absent source
    assert r.read({"trace": None, "trace_mark": [0, 0, 10, 2], "tree_dumps": []}) is None
    if metric != "bag_compact_roofline":
        assert r.read(dict(facts, op_scopes={})) is None


def test_a_parent_s_traced_line_of_this_cell_passes_the_harness_s_validator():
    """The parent of PR 35 can run this cell; its traced line must conform
    with what the readers give on a program without the window."""
    m = contract.Manifest()
    cell = m.cell(CELL)
    facts = _facts()
    facts["op_scopes"] = {"jit__launch_impl": {
        "fusion.1": "while/body/sample", "fusion.2": "while/body/leaf_ids",
        "fusion.4": "while/body/score_update", "fusion.7": "while/body/pack_rows"}}
    facts["trace"].devices["/device:TPU:0"] = [
        o for o in facts["trace"].devices["/device:TPU:0"] if "bag_compact" not in o.name]
    values = {name: _reader(name).read(facts) for name in NEW_METRICS}
    assert values["bag_compact_ms_per_iter"] == 0.0 and values["oob_score_ms_per_iter"] == 0.0
    assert values["goss_sample_ms_per_iter"] > 0 and values["in_bag_row_share"] == 30.0
    units = {m_["name"]: m_["unit"] for m_ in cell.per_layer}
    line = {"correct": False, "attempted": 8, "failed": 0,
            "metrics": {n: {"value": 1.0, "unit": u} for n, u in units.items()},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 1, "busy_s": 1.0, "window_s": 2.0}}
    line["metrics"].update({n: {"value": v, "unit": units[n]} for n, v in values.items()})
    assert contract.validate_line(line, required=cell.per_layer, traced=True, chips=1) == []


def test_the_splits_partition_metrics_do_not_read_the_compaction():
    """``seg_partition_roofline`` and ``partition_ms_per_iter`` keep reading
    the splits' partitions against the splits' work."""
    from benchmark import readers

    facts = _facts()
    for metric in ("partition_ms_per_iter", "seg_partition_roofline"):
        with open(os.path.join(contract.BENCH_DIR, "layers", metric + ".json")) as fh:
            match = readers._matcher(json.load(fh))
        names = [o.name for o in facts["trace"].devices["/device:TPU:0"] if match(o)]
        assert names == ["seg_partition_pallas.5"], metric


# ---------------------------------------------------- a recorded chip trace
def _recorded():
    stem = os.path.join(contract.BENCH_DIR, "traces", CELL)
    trace = trace_reduce.load_recorded(stem + "_spans.json.gz", n_devices=1)
    tree = {"split_index": 0, "internal_count": 2_400_000,
            "left_child": {"leaf_index": 0, "leaf_count": 1_400_000},
            "right_child": {"leaf_index": 1, "leaf_count": 1_000_000}}
    return {
        "trace": trace, "op_scopes": scope_join.load_scopes(stem + "_op_scopes.json.gz"),
        # the recorded window is the traced window's first iteration
        "trace_mark": [0.0, 0, trace.window_s, 1], "tree_dumps": [tree],
        "rows": 8_000_000, "features": 67, "chips": 1, "device_kind": "TPU v5 lite",
    }


@pytest.mark.parametrize("metric", sorted(NEW_METRICS | {PENDING}))
def test_every_new_reader_gives_a_number_on_the_recorded_chip_trace(metric):
    value = _reader(metric).read(_recorded())
    assert value is not None and math.isfinite(value) and value > 0
    if metric == "bag_compact_roofline":
        assert value <= 100


def test_closure_on_the_recorded_chip_trace():
    facts = _recorded()
    layers_s, program_s = scope_join.closure(facts)
    # the module events also hold the gaps between operations: 12 ms of a
    # 245 ms iteration here, a larger share than of the unsampled cells' 630
    assert program_s > 0.1 and 0.94 * program_s <= layers_s <= program_s * 1.0001


# ----------------------------------------------- planting a fault by hand
def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] not in FAULTS:
        print(f"usage: {os.path.basename(__file__)} <{'|'.join(FAULTS)}> <run.py's arguments>",
              file=sys.stderr)
        return 2
    with FAULTS[argv[0]]():
        return brun.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main())
