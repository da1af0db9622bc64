"""The join of a traced run to the program's own names (benchmark/scope_join.py)
and the per-layer readers waiting in ``benchmark/pending_per_layer.json``.

Pinned on hand-built traces (module containment, the host-span arithmetic)
and on one recorded chip run of each cell (``benchmark/traces/<cell>_spans.json.gz``
with the program's published scopes beside it, both written by
``benchmark/record_spans.py`` on a TPU v5e): the 95 % rule, the closure sum,
every reader giving a number.  On the CPU, with no device plane, the same
readers return ``None`` without raising and the run still prints a
validated line.
"""

import copy
import json
import math
import os
import subprocess
import sys

import pytest

from benchmark import contract, scope_join, trace_reduce
from benchmark.trace_reduce import Op, Span, Trace

ROOT = contract.ROOT
CELLS = ("criteo67.fit-eval", "higgs.fit")
DEVICE_METRICS = ("split_scan_ms_per_iter", "grow_bookkeeping_ms_per_iter",
                  "kernel_glue_ms_per_iter", "row_layout_ms_per_iter",
                  "grow_unscoped_ms_per_iter", "score_update_ms_per_iter")
HOST_METRICS = ("host_unblocked_ms_per_iter", "host_dispatches_per_iter", "booster_init_s")


def pending():
    with open(os.path.join(contract.BENCH_DIR, "pending_per_layer.json")) as fh:
        return json.load(fh)


def recorded(cell):
    stem = os.path.join(contract.BENCH_DIR, "traces", cell)
    trace = trace_reduce.load_recorded(stem + "_spans.json.gz", n_devices=1)
    return {
        "trace": trace, "op_scopes": scope_join.load_scopes(stem + "_op_scopes.json.gz"),
        # the recorded window is the traced window's first iteration
        "trace_mark": [0.0, 0, trace.window_s, 1],
        "spans": [{"name": "setup/booster_init", "dur": 1_500_000}],
    }


def read(metric, facts):
    mod = contract.load_module(os.path.join(contract.BENCH_DIR, "layers", metric + ".py"),
                               "benchmark_layer_" + metric)
    return mod.read(facts)


# ------------------------------------------------------------- hand-built
def _trace(ops, modules, host=()):
    return Trace(window_s=10.0, devices={"/device:TPU:0": list(ops)}, host=list(host),
                 planes_found=["/device:TPU:0"], modules={"/device:TPU:0": list(modules)})


def test_an_operation_belongs_to_the_program_whose_interval_holds_its_start():
    tr = _trace(
        ops=[Op("fusion.1", 1.0, 0.5, ""), Op("copy.2", 1.9, 0.3, ""),  # runs over the end
             Op("fusion.1", 3.0, 0.1, ""), Op("sort.9", 5.0, 0.1, ""),
             Op("seg_hist_pallas_batch.4", 1.5, 0.2, "mosaic f32[2]")],
        modules=[Op("jit_grow_tree", 1.0, 1.0, ""), Op("jit_other", 3.0, 0.5, "")],
    )
    got = {(o.name, o.start): m for o, m in scope_join.module_of_ops(tr)}
    assert got == {("fusion.1", 1.0): "jit_grow_tree", ("copy.2", 1.9): "jit_grow_tree",
                   ("seg_hist_pallas_batch.4", 1.5): "jit_grow_tree",
                   ("fusion.1", 3.0): "jit_other", ("sort.9", 5.0): None}
    # one instruction name in two programs reads each program's own scope,
    # and of two maps of one module name the one that covers the trace is taken
    maps = [
        {"module": "jit_grow_tree", "scopes": {"fusion.77": "partition"}},  # another table's
        {"module": "jit_grow_tree", "scopes": {"fusion.1": "leaf_loop/candidate_refresh/split_scan",
                                               "copy.2": "leaf_ids"}},
        {"module": "jit_other", "scopes": {"fusion.1": "score_update"}},
    ]
    scopes = scope_join.scopes_of_trace(tr, maps)
    assert scopes["jit_grow_tree"]["copy.2"] == "leaf_ids"
    facts = {"trace": tr, "op_scopes": scopes, "trace_mark": [0, 0, 10, 2]}
    sec = scope_join.scoped_seconds(facts)
    assert sec["grow"] == pytest.approx({"split_scan": 0.5, "row_layout": 0.3})
    assert sec["other"] == pytest.approx({"score_update": 0.1})
    assert scope_join.grow_ms_per_iter(facts, "split_scan") == pytest.approx(250.0)
    assert scope_join.grow_ms_per_iter(facts, "bookkeeping") == 0.0  # a measured zero
    assert scope_join.scope_ms_per_iter(facts, "score_update") == pytest.approx(50.0)
    assert scope_join.scope_ms_per_iter(facts, "gradients") is None  # in no program
    # kernels + layers against the module's own time
    assert scope_join.closure(facts) == pytest.approx((1.0, 1.0))


@pytest.mark.parametrize("scope,layer", [
    ("leaf_loop/candidate_refresh/split_scan", "split_scan"), ("split_scan", "split_scan"),
    ("leaf_loop/candidate_refresh", "bookkeeping"), ("leaf_loop/bookkeeping", "bookkeeping"),
    ("init_state", "bookkeeping"), ("leaf_loop/partition", "kernel_glue"),
    ("leaf_loop/fused_grow_step", "kernel_glue"), ("root_histogram", "kernel_glue"),
    ("pack_rows", "row_layout"), ("leaf_ids", "row_layout"), ("score_update", "score_update"),
    ("leaf_loop", "unscoped"), ("", "unscoped"), ("ambiguous", "unscoped"),
])
def test_scope_paths_fall_into_layers(scope, layer):
    assert scope_join.layer_of(scope) == layer


def test_host_unblocked_with_overlapping_waits():
    spans = [
        Span("train/run", 0.0, 10.0),  # not a top span: its gaps are nobody's
        Span("train/iteration", 1.0, 2.0),
        Span("train/grow", 1.1, 0.2),
        Span("wait/fetch_tree", 1.5, 1.0),  # [1.5, 2.5]
        Span("wait/fetch_tree", 2.0, 0.4),  # inside the other wait: counted once
        Span(scope_join.RUNTIME_WAIT, 2.4, 0.3),  # a held dispatch, overlapping the wait
        Span("train/eval", 3.0, 1.0),
        Span("wait/eval_metric", 3.9, 0.5),  # runs past its parent: only 0.1 s is inside
        Span("train/callbacks", 4.0, 1.0),
        Span("bench/boundary", 4.2, 0.8),
        Span("wait/launch_fetch", 7.0, 1.0),  # outside every top span: not subtracted
    ]
    # top 4.0 s; blocked inside: [1.5, 2.7] 1.2 + [3.9, 4.0] 0.1 + ([4.0, 4.4] of the
    # eval wait and [4.2, 5.0] of the boundary = [4.0, 5.0]) 1.0
    assert scope_join.host_unblocked_seconds(spans) == pytest.approx(4.0 - 2.3)
    assert scope_join.host_unblocked_seconds([Span("PjitFunction(f)", 0, 1)]) is None
    disp = spans + [Span("PjitFunction(grow_tree)", 1.1, 0.01),
                    Span("PjitFunction(grow_tree)", 1.1001, 0.009),  # the same dispatch again
                    Span("PjitFunction(add)", 3.5, 0.001),
                    Span("PjitFunction(add)", 12.0, 0.001)]  # outside the training run
    assert scope_join.host_dispatches(disp) == 2
    assert scope_join.host_dispatches(disp[-4:]) is None  # no span of the program's


# ---------------------------------------------------- recorded chip traces
@pytest.mark.parametrize("cell", CELLS)
def test_closure_on_a_recorded_chip_trace(cell):
    facts = recorded(cell)
    layers_s, program_s = scope_join.closure(facts)
    assert program_s > 1.0
    # the module events also hold the gaps between operations: 34-90 ms a window
    assert 0.98 * program_s <= layers_s <= program_s * 1.0001
    sec = scope_join.scoped_seconds(facts)["grow"]
    assert sec["split_scan"] > 0 and sec["bookkeeping"] > 0 and sec["row_layout"] > 0
    # what no scope covers stays under a quarter of the non-kernel time
    assert sec["unscoped"] < 0.25 * sum(sec.values())


@pytest.mark.parametrize("cell", CELLS)
def test_the_95_percent_rule_raises_on_a_map_with_instructions_removed(cell):
    facts = recorded(cell)
    full = facts["op_scopes"]
    grow = scope_join.grow_pattern()
    (module,) = [m for m in full if grow.search(m)]
    spent = {}
    for op, m in scope_join.module_of_ops(facts["trace"]):
        if m == module and not op.mosaic:
            spent[op.name] = spent.get(op.name, 0.0) + op.dur
    # take the instructions that hold the most time out of the map
    longest = sorted(spent, key=spent.get, reverse=True)
    cut, gone = set(), 0.0
    for name in longest:
        if gone > 0.06 * sum(spent.values()):
            break
        cut.add(name)
        gone += spent[name]
    holed = copy.deepcopy(full)
    holed[module] = {k: v for k, v in full[module].items() if k not in cut}
    with pytest.raises(scope_join.JoinError, match="finds its instruction"):
        scope_join.scoped_seconds({**facts, "op_scopes": holed})
    # a program that publishes no map of the grow program is an absent
    # source, not an error
    assert scope_join.scoped_seconds({**facts, "op_scopes": {}}) is None
    other = {m: v for m, v in full.items() if m != module}
    assert scope_join.scoped_seconds({**facts, "op_scopes": other}) is None


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("metric", DEVICE_METRICS + HOST_METRICS)
def test_every_pending_reader_gives_a_number_on_a_recorded_chip_trace(cell, metric):
    value = read(metric, recorded(cell))
    assert value is not None and math.isfinite(value) and value >= 0
    if metric in ("split_scan_ms_per_iter", "grow_bookkeeping_ms_per_iter",
                  "row_layout_ms_per_iter", "score_update_ms_per_iter",
                  "host_unblocked_ms_per_iter", "host_dispatches_per_iter"):
        assert value > 0
    if metric == "host_unblocked_ms_per_iter":
        # the host's own share of an iteration of two to three seconds
        assert value < 200


def test_the_parent_program_gives_none_not_an_error():
    """What the readers see at a commit without spans or scopes."""
    facts = recorded("higgs.fit")
    bare = {"trace": facts["trace"], "trace_mark": facts["trace_mark"], "op_scopes": {},
            "spans": []}
    bare["trace"].host = [s for s in bare["trace"].host
                          if s.name.split("/")[0] not in ("train", "wait", "setup")]
    for metric in DEVICE_METRICS + HOST_METRICS:
        assert read(metric, bare) is None, metric


# -------------------------------------------------------------- the manifest
def test_pending_entries_load_through_the_manifest_once_appended(tmp_path):
    manifest = contract.Manifest()
    doc = copy.deepcopy(manifest.doc)
    entries = pending()
    assert [m["name"] for m in entries] == [
        "split_scan_ms_per_iter", "grow_bookkeeping_ms_per_iter", "kernel_glue_ms_per_iter",
        "row_layout_ms_per_iter", "grow_unscoped_ms_per_iter", "score_update_ms_per_iter",
        "host_unblocked_ms_per_iter", "host_dispatches_per_iter", "booster_init_s"]
    # they wait: BENCHMARK.json holds none of them yet (PERF.md section 7 says why)
    assert not {m["name"] for m in entries} & {m["name"] for m in doc["per_layer"]}
    doc["per_layer"] += entries
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    os.symlink(contract.BENCH_DIR, tmp_path / "benchmark")  # files added, none edited
    appended = contract.Manifest(str(tmp_path))
    known_layers = {m["layer"] for m in manifest.doc["per_layer"]}
    for cell in CELLS:
        per_layer = {m["name"]: m for m in appended.cell(cell).per_layer}
        for m in entries:
            assert per_layer[m["name"]] == m
            assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
            assert appended.layer_reader_path(m["name"]).endswith(m["name"] + ".py")
    assert {m["layer"] for m in entries} & known_layers == {
        "entry: engine.train, Booster.update, launch.py"}
    assert len(json.dumps(doc)) < 64 * 1024


# ---------------------------------------------------------------- rehearsal
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_a_validated_line_and_device_readers_give_none(cell, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(contract.BENCH_DIR, "record_spans.py"),
         "--workload", cell, "--seed", "2147483999", "--seconds", "0.5",
         "--window-s", "0.5", "--out-dir", str(tmp_path), "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    traced_cell = contract.Manifest().cell(cell)
    assert contract.validate_line(line, required=traced_cell.per_layer, traced=True,
                                  chips=1, rehearse=True) == []
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    with open(tmp_path / (cell + "_pending.json")) as fh:
        values = json.load(fh)
    for metric in DEVICE_METRICS:  # no device plane: nothing to read, nothing raised
        assert values[metric] is None, metric
    for metric in HOST_METRICS:  # the spans are in a CPU profile too
        assert values[metric] is not None and values[metric] >= 0, metric
