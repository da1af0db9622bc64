"""The four-chip cell ``criteo67.fit-dp4``: LightGBM's data-parallel learner
(``tree_learner=data``) over a ``{data: 4}`` mesh on the Criteo-shaped table,
8M rows a chip as in ``criteo67.fit``, through the launch scan.  It is queued,
not listed: the program reports node counts as float32, which cannot hold an
odd count past 2**24 rows, so its runs read ``count_mismatch`` 1 - 2 (PERF.md,
Open questions).  Its entries wait in ``benchmark/pending_cell_criteo67.fit-dp4.json``.
Here: those entries appended to a copy of the manifest, a run on four virtual
CPU devices in a process of its own (sound, and with the exchange between
chips left out), and every reader the cell would list on a recorded chip
trace of it."""

import copy
import gzip
import json
import math
import os
import subprocess
import sys

import pytest

from benchmark import contract, readers, scope_join, trace_reduce

CELL = "criteo67.fit-dp4"
COLLECTIVES = ("collective_ms_per_iter", "collective_exposed_ms_per_iter")


def _pending():
    with open(os.path.join(contract.BENCH_DIR, "pending_cell_" + CELL + ".json")) as fh:
        return json.load(fh)


def appended_doc():
    """BENCHMARK.json with the queued cell's entries appended as the pending
    file says."""
    doc = copy.deepcopy(contract.Manifest().doc)
    p = _pending()
    doc["workloads"].append(p["workload"])
    for m in doc["per_layer"]:
        if m["name"] in p["append_cell_to"]:
            m["workloads"].append(CELL)
    at = [m["name"] for m in doc["per_layer"]].index(p["per_layer_before"])
    doc["per_layer"][at:at] = p["per_layer"]
    return doc


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose manifest lists the cell: files added, none edited."""
    d = tmp_path_factory.mktemp("appended")
    (d / "BENCHMARK.json").write_text(json.dumps(appended_doc(), indent=1))
    os.symlink(contract.BENCH_DIR, d / "benchmark")
    return str(d)


def _names(cell):
    return {m["name"] for m in cell.per_layer}


def test_the_queued_cell_is_criteo67_fit_on_four_chips(root):
    m = contract.Manifest(root)
    cell, one = m.cell(CELL), m.cell("criteo67.fit")
    assert (cell.chips, cell.config_name, cell.traffic) == (4, "criteo67", "fit-dp4")
    assert cell.config == one.config  # the same table, 8M rows a chip: no limits of its own
    assert cell.job["extra_params"] == {"tree_learner": "data"}
    assert cell.job["expect"] == {"hist_mode": "seg", "launch_steps": 8, "mesh_devices": 4}
    assert (cell.job["valid_fraction"], cell.job["early_stopping_rounds"]) == (0.0, 0)
    # two launches of warm-up: under the mesh the second launch compiles
    assert (cell.job["warmup_iterations"], cell.job["trace_iterations"],
            cell.job["follow_trees"]) == (16, 8, 2)
    # criteo67.fit's metrics, and the two collective readers
    assert _names(cell) == _names(one) | set(COLLECTIVES)
    listed = {x["name"]: x for x in m.doc["per_layer"]}
    for name in COLLECTIVES:
        assert listed[name] == {"name": name, "unit": "ms", "better": "lower",
                                "source": "device_trace", "layer": "collectives",
                                "moves": "train_iters_per_s", "workloads": [CELL]}
        assert m.layer_reader_path(name).endswith(name + ".json")
    entry = next(w for w in m.doc["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200
    # the setup metrics list every cell (test_bench_setup_spans.py) and stay last
    assert [x["name"] for x in m.doc["per_layer"]][-5:] == [
        "program_import_s", "trace_lower_s", "cache_retrieval_s", "device_transfer_s",
        "booster_init_self_s"]
    assert listed["program_import_s"]["workloads"] == m.workload_names()
    assert len(json.dumps(m.doc, indent=1)) < 64 * 1024


def test_the_manifest_does_not_list_the_queued_cell():
    m = contract.Manifest()
    assert CELL not in m.workload_names()
    assert not {x["name"] for x in m.doc["per_layer"]} & set(COLLECTIVES)
    # once appended it is the one cell on four chips: one in four at most
    doc = appended_doc()
    four = [w["name"] for w in doc["workloads"] if w["chips"] == 4]
    assert four == [CELL] and len(four) <= max(1, len(doc["workloads"]) // 4)


# ------------------------------------------- four virtual devices, own process
_DRIVER = """
import sys
from benchmark import data, faults, run
run.ROOT = sys.argv[1]
run.REHEARSE_ROWS = 6_000
data.BLOCK_ROWS = 3_000
fault = sys.argv[2]
args = sys.argv[3:]
if fault == "none":
    sys.exit(run.main(args))
with faults.FAULTS[fault]():
    sys.exit(run.main(args))
"""


def _four_device_run(root, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", _DRIVER, root, fault, "--workload", CELL, "--seed", "2147483931",
         "--seconds", "0.5", "--trace", "0", "--rehearse"],
        env=env, cwd=contract.ROOT, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["none", "exchange_left_out"])
def test_a_four_device_rehearsal(root, fault):
    """24,000 rows over four CPU devices: the sound run conforms and reads
    correct with the mesh the cell asks for; with every psum of the grower
    returning its own shard's part it reads not correct."""
    line = _four_device_run(root, fault)
    cell = contract.Manifest(root).cell(CELL)
    assert contract.validate_line(line, required=cell.end_to_end, traced=False, chips=4,
                                  rehearse=True) == []
    assert line["device"]["count"] == 4 and line["facts"]["rows"] == 24_000
    assert line["checks"]["mesh_devices_missing"]["value"] == 0
    failing = sorted(n for n, c in line["checks"].items() if c["value"] > c["limit"])
    if fault == "none":
        assert line["correct"] is True and failing == [], failing
    else:
        assert line["correct"] is False
        assert {"count_mismatch", "split_gain_rms_gap"} <= set(failing), failing


# ------------------------------------------------------ a recorded chip trace
def recorded():
    """The first 0.7 s of a traced window of the cell on a TPU v5e 2x2 (four
    devices, seed 2400000011), the program's scopes, and the facts its
    readers take besides the trace (``*_facts.json.gz``: the harness's
    clocks, the set-up's spans and the first traced tree's counts)."""
    stem = os.path.join(contract.BENCH_DIR, "traces", CELL)
    trace = trace_reduce.load_recorded(stem + "_spans.json.gz", n_devices=4)
    with gzip.open(stem + "_facts.json.gz", "rt", encoding="utf-8") as fh:
        rec = json.load(fh)
    facts = {k: rec[k] for k in ("rows", "features", "chips", "device_kind", "marks", "t0",
                                 "dataset_construct_s", "compile_s")}
    return dict(facts, trace=trace, op_scopes=scope_join.load_scopes(stem + "_op_scopes.json.gz"),
                # the recorded window counts as the traced window's first iteration
                trace_mark=[0.0, 0, trace.window_s, 1], tree_dumps=rec["traced_trees"],
                spans=rec["setup_spans"] + [rec["import_span"]])


PINNED = {
    "device_idle_share": 2.4629292215738396,
    "train_step_mfu": 0.532014290759537,
    "iter_wall_p50_ms": 476.85691868750087,
    "dataset_construct_s": 40.359445474,
    "compile_s": 16.598751068115234,
    "grow_program_ms_per_iter": 695.59731,
    "histogram_ms_per_iter": 401.43331800000004,
    "partition_ms_per_iter": 168.33545500000002,
    "xla_glue_ms_per_iter": 113.233777,
    "grow_kernels_roofline": 4.618894407014297,
    "seg_hist_roofline": 5.236772958364802,
    "seg_partition_roofline": 13.93045771014987,
    "collective_ms_per_iter": 4.103766000000119,
    "collective_exposed_ms_per_iter": 4.10376599999962,
    "program_import_s": 2.589093,
    "trace_lower_s": 6.246971845626831,
    "cache_retrieval_s": 15.832490343999993,
    "device_transfer_s": 0.95546,
    "booster_init_self_s": 0.301151,
}


def test_every_reader_the_cell_lists_on_its_recorded_chip_trace(root):
    m = contract.Manifest(root)
    cell = m.cell(CELL)
    facts = recorded()
    got = {x["name"]: readers.read_metric(m, x["name"], facts) for x in cell.per_layer}
    assert set(got) == set(PINNED)
    assert got == pytest.approx(PINNED, rel=1e-9)
    # the psums are synchronous on the v5e: no -start/-done pair, so the
    # union of the events is the transfer; nothing else runs beside them
    names = {trace_reduce.kind_of(o.name) for o in facts["trace"].ops()}
    assert {"psum", "all-reduce"} <= names
    assert not {n for n in names if n.startswith(("all-reduce-", "psum-"))}
    assert 0 < got["collective_exposed_ms_per_iter"] <= got["collective_ms_per_iter"]
    line = {"correct": False, "attempted": 8, "failed": 0,
            "metrics": {x["name"]: {"value": got[x["name"]], "unit": x["unit"]}
                        for x in cell.per_layer},
            "device": {"platform": "tpu", "kind": facts["device_kind"], "count": 4,
                       "memory_peak_bytes": 10_192_071_168,
                       "busy_s": facts["trace"].busy_s, "window_s": facts["trace"].window_s}}
    assert contract.validate_line(line, required=cell.per_layer, traced=True, chips=4) == []
    assert all(math.isfinite(v) and v > 0 for v in got.values())
