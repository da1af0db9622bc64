"""The last-line validator against the ways PR 23's line went wrong, and the
manifest's by-name lookup: a cell, a configuration and a per-layer metric
are added by new files and new entries alone."""

import copy
import json
import os
import shutil

import pytest

from benchmark import contract, readers

E2E = [
    {"name": "train_iters_per_s", "unit": "iters/s"},
    {"name": "setup_s", "unit": "s"},
]
LAYERS = [
    {"name": "device_idle_share", "unit": "%"},
    {"name": "train_step_mfu", "unit": "%"},
]


def good(traced: bool, chips: int = 1):
    line = {
        "correct": True, "attempted": 12, "failed": 0,
        "metrics": {"train_iters_per_s": {"value": 0.25, "unit": "iters/s"},
                    "setup_s": {"value": 80.5, "unit": "s"}},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": chips,
                   "memory_peak_bytes": 6 * 2**30},
        "checks": {"count_mismatch": {"value": 0.0, "limit": 0.0}},
    }
    if traced:
        line["metrics"] = {"device_idle_share": {"value": 7.5, "unit": "%"},
                           "train_step_mfu": {"value": 0.2, "unit": "%"}}
        line["device"].update(busy_s=9.0, window_s=10.0)
        line["breakdown"] = {"device_ops": [["fusion", 3.0]],
                             "idle_gaps": [["host: bench/boundary", 0.5]]}
    return line


def errors(line, traced, chips=1):
    return contract.validate_line(line, required=LAYERS if traced else E2E,
                                  traced=traced, chips=chips)


@pytest.mark.parametrize("traced,chips", [(False, 1), (True, 1), (True, 4)])
def test_conforming_line_passes(traced, chips):
    assert errors(good(traced, chips), traced, chips) == []
    json.loads(contract.dumps_line(good(traced, chips)))


def _busy_summed(line):
    line["device"]["busy_s"] = 4 * 9.0  # four devices' busy time added up


def _busy_zero(line):
    line["device"]["busy_s"] = 0.0


def _busy_missing(line):
    del line["device"]["busy_s"]


def _nan_metric(line):
    line["metrics"]["train_step_mfu"]["value"] = float("nan")


def _null_metric(line):
    line["metrics"]["train_step_mfu"]["value"] = None


def _missing_layer_metric(line):
    del line["metrics"]["train_step_mfu"]


def _foreign_metric(line):
    line["metrics"]["serve_p95_ms"] = {"value": 1.0, "unit": "ms"}


def _wrong_unit(line):
    line["metrics"]["device_idle_share"]["unit"] = "percent of the window"


def _mfu_over_100(line):
    line["metrics"]["train_step_mfu"]["value"] = 140.0


def _bare_number(line):
    line["metrics"]["device_idle_share"] = 7.5


def _too_few_devices(line):
    line["device"]["count"] = 1


def _no_memory(line):
    line["device"]["memory_peak_bytes"] = 0


def _failed_over_attempted(line):
    line["failed"] = line["attempted"] + 1


def _long_breakdown(line):
    line["breakdown"]["device_ops"] = [["op", 1.0]] * 11


@pytest.mark.parametrize("break_it", [
    _busy_summed, _busy_zero, _busy_missing, _nan_metric, _null_metric,
    _missing_layer_metric, _foreign_metric, _wrong_unit, _mfu_over_100,
    _bare_number, _too_few_devices, _no_memory, _failed_over_attempted,
    _long_breakdown,
], ids=lambda f: f.__name__.strip("_"))
def test_traced_four_chip_failure_modes_are_refused(break_it):
    line = good(True, 4)
    break_it(line)
    assert errors(line, True, 4), break_it.__name__


def test_traceback_as_last_line_is_refused():
    last = 'jaxlib.xla_extension.XlaRuntimeError: RESOURCE_EXHAUSTED'
    with pytest.raises(json.JSONDecodeError):
        json.loads(last)
    assert contract.validate_line(last, required=E2E, traced=False, chips=1)
    assert contract.validate_line(["not", "an", "object"], required=E2E,
                                  traced=False, chips=1)
    assert contract.validate_line({"ok": True}, required=E2E, traced=False, chips=1)


def test_nan_cannot_be_printed():
    line = good(False)
    line["metrics"]["setup_s"]["value"] = float("nan")
    assert errors(line, False)
    with pytest.raises(ValueError):
        contract.dumps_line(line)


def test_rehearsal_relaxes_only_device_numbers():
    line = good(True)
    del line["device"]["busy_s"], line["device"]["window_s"]
    del line["metrics"]["train_step_mfu"]
    assert contract.validate_line(line, required=LAYERS, traced=True, chips=1)
    assert contract.validate_line(line, required=LAYERS, traced=True, chips=1,
                                  rehearse=True) == []
    line["correct"] = "yes"
    assert contract.validate_line(line, required=LAYERS, traced=True, chips=1,
                                  rehearse=True)


# ------------------------------------------------------------- the manifest


def test_manifest_finds_every_file_by_name():
    m = contract.Manifest()
    assert m.workload_names()
    for name in m.workload_names():
        cell = m.cell(name)
        assert cell.chips in (1, 4)
        assert {x["name"] for x in cell.end_to_end} >= {"setup_s", "train_iters_per_s"}
        assert cell.per_layer, name
        assert os.path.exists(m.reference_path(cell.config_name))
        for metric in cell.per_layer:
            path = m.layer_reader_path(metric["name"])
            if path.endswith(".json"):
                with open(path) as fh:
                    assert json.load(fh)["reader"] in readers.STOCK
            assert metric["moves"] in {x["name"] for x in cell.end_to_end}
        for key in ("params", "features", "data", "limits", "precision"):
            assert key in cell.config, (name, key)
        for key in ("warmup_iterations", "trace_iterations", "follow_trees", "expect"):
            assert key in cell.job, (name, key)
    for cfg in m.doc["configs"]:
        assert any(w["config"] == cfg["name"] for w in m.doc["workloads"])
        assert cfg["file"].startswith(tuple(p + "/" for p in m.doc["paths"]))


def test_manifest_meets_the_contract_limits():
    m = contract.Manifest()
    doc = m.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert 1 <= doc["run_seconds"] <= 51 and isinstance(doc["run_seconds"], int)
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 4)
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for m_ in doc["end_to_end"]:
        assert set(m_) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m_["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m_["bound"] <= 0.1
    for m_ in doc["per_layer"]:
        assert set(m_) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
        if "roofline" in m_["name"] or "mfu" in m_["name"]:
            assert m_["unit"] == "%"
    assert any("mfu" in m_["name"] for m_ in doc["per_layer"])
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200
    assert len(json.dumps(doc)) < 64 * 1024


def test_a_cell_a_config_and_a_metric_are_added_by_files_alone(tmp_path):
    """Copy the benchmark's data files, then ADD (never edit) a job file, a
    configuration file, a reference, a layer reader and their manifest
    entries; the harness finds each by name."""
    m = contract.Manifest()
    root = tmp_path
    bench = root / "benchmark"
    for sub in ("configs", "jobs", "layers", "reference"):
        shutil.copytree(m.path(sub), bench / sub)
    doc = copy.deepcopy(m.doc)
    base_cfg = json.load(open(m.path("configs", doc["configs"][0]["name"] + ".json")))
    new_cfg = dict(base_cfg, name="dummy", features=5, rows=1234)
    (bench / "configs" / "dummy.json").write_text(json.dumps(new_cfg))
    (bench / "reference" / "dummy.py").write_text(
        "def follow_model(*a, **k):\n    return {}\n")
    job = json.load(open(m.path("jobs", doc["workloads"][0]["traffic"] + ".json")))
    (bench / "jobs" / "dummy-job.json").write_text(json.dumps(dict(job, name="dummy-job")))
    (bench / "limits").mkdir()
    (bench / "limits" / "dummy.dummy-job.json").write_text('{"valid_logloss_gap": 0.5}')
    (bench / "layers" / "dummy_metric.py").write_text(
        "def read(facts):\n    return 2.0 * facts['x']\n")
    (bench / "layers" / "dummy_silent.py").write_text(
        "def read(facts):\n    return None\n")
    doc["configs"].append({"name": "dummy", "source": "a test", "reduced": [],
                           "file": "benchmark/configs/dummy.json", "why": "test"})
    doc["workloads"].append({"name": "dummy.dummy-job", "config": "dummy",
                             "traffic": "dummy-job", "chips": 1, "why": "test"})
    for name in ("dummy_metric", "dummy_silent"):
        doc["per_layer"].append({"name": name, "unit": "ms", "better": "lower",
                                 "source": "host_clock", "layer": "test",
                                 "moves": "setup_s",
                                 "workloads": ["dummy.dummy-job"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    m2 = contract.Manifest(str(root))
    cell = m2.cell("dummy.dummy-job")
    assert cell.config["rows"] == 1234 and cell.job["name"] == "dummy-job"
    assert cell.config["limits"]["valid_logloss_gap"] == 0.5  # the cell's own limit
    assert cell.config["limits"]["count_mismatch"] == 0  # the configuration's
    assert {"dummy_metric", "dummy_silent"} <= {x["name"] for x in cell.per_layer}
    assert m2.reference_path("dummy").endswith("dummy.py")
    assert readers.read_metric(m2, "dummy_metric", {"x": 21.0}) == 42.0
    assert readers.read_metric(m2, "dummy_silent", {"x": 21.0}) is None
    # the old cells do not see the new metric
    old = m2.cell(m.workload_names()[0])
    assert "dummy_metric" not in {x["name"] for x in old.per_layer}
    with pytest.raises(contract.ContractError):
        m2.cell("no.such-cell")
    with pytest.raises(contract.ContractError):
        m2.layer_reader_path("no_such_metric")
