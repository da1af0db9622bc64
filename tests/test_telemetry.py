"""Unified telemetry: per-iteration event stream, compile accounting,
collective byte model, JSONL sink (obs/ subsystem).

Reference analog: the C++ tree's only observability is ``global_timer``
(utils/common.h:979); the obs/ registry is its structured superset.
"""

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.obs.registry import get_session  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_session():
    ses = get_session()
    ses.configure(enabled=False)
    ses.reset()
    yield
    ses.configure(enabled=False)
    ses.reset()


def _data(n=400, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = X[:, 0] * 2 + np.sin(X[:, 1]) + 0.1 * rng.normal(size=n)
    return X, y


# --------------------------------------------------------------- event schema
def test_iteration_event_schema_and_jsonl(tmp_path):
    X, y = _data()
    sink = str(tmp_path / "events.jsonl")
    params = {
        "objective": "regression",
        "num_leaves": 7,
        "verbosity": -1,
        "metric": "l2",
        "telemetry": True,
        "telemetry_out": sink,
    }
    booster = lgb.train(
        params,
        lgb.Dataset(X, y),
        5,
        valid_sets=[lgb.Dataset(X, y)],
        valid_names=["t"],
    )
    tel = booster.telemetry()
    events = [e for e in tel["events"] if e["event"] == "iteration"]
    assert len(events) == 5
    for it, e in enumerate(events):
        assert e["iter"] == it
        assert e["wall_ms"] > 0
        assert isinstance(e["phases"], dict) and e["phases"]
        assert all(v >= 0 for v in e["phases"].values())
        assert e["compiles_delta"] >= 0
        assert e["leaf_batch"] == 1
    # phases cover the booster hot path
    all_phases = set().union(*(e["phases"] for e in events))
    assert {"gradients", "sample", "grow"} <= all_phases
    assert tel["counters"]["iterations"] == 5
    assert tel["compile_count"] > 0
    # one JSONL line per iteration, eval metrics annotated into the line;
    # train() appends the end-of-train host_rollup + train_summary records
    lines = [json.loads(l) for l in open(sink)]
    kinds = [l["event"] for l in lines]
    assert kinds[:5] == ["iteration"] * 5
    assert kinds[5:] == ["host_rollup", "train_summary"]
    assert any("eval" in l and "t/l2" in l["eval"] for l in lines)
    summary = lines[-1]
    assert summary["counters"]["iterations"] == 5
    assert isinstance(summary["gauges"], dict)


def test_telemetry_callback_collects_history():
    X, y = _data()
    cb = lgb.TelemetryCallback()
    lgb.train(
        {
            "objective": "regression",
            "num_leaves": 7,
            "verbosity": -1,
            "metric": "l2",
            "telemetry": True,
        },
        lgb.Dataset(X, y),
        4,
        valid_sets=[lgb.Dataset(X, y)],
        valid_names=["t"],
        callbacks=[cb],
    )
    assert len(cb.history) == 4
    assert cb.history[0]["event"] == "iteration"
    assert "t/l2" in cb.history[0]["eval"]


# ------------------------------------------------------------ disabled = noop
def test_disabled_records_nothing_and_phase_is_noop():
    ses = get_session()
    X, y = _data()
    lgb.train(
        {"objective": "regression", "num_leaves": 7, "verbosity": -1},
        lgb.Dataset(X, y),
        3,
    )
    assert ses.events == []
    assert ses.counters == {}
    assert ses.gauges == {}
    # structural overhead guard: with telemetry off a layer-boundary span
    # feeds no phase accumulator (add_phase is a flag check)
    ses.begin_iteration()
    ses.add_phase("grow", 1.0)
    assert ses.end_iteration() == {}
    ses.record({"event": "x"})
    assert ses.events == []
    ses.inc("n")
    ses.set_gauge("g", 1.0)
    assert ses.counters == {} and ses.gauges == {}


# --------------------------------------------------------- compile accounting
def test_no_recompile_after_warmup_train():
    X, y = _data(n=500)
    params = {
        "objective": "regression",
        "num_leaves": 7,
        "verbosity": -1,
        "telemetry": True,
    }
    booster = lgb.train(params, lgb.Dataset(X, y), 8)
    events = [
        e for e in booster.telemetry()["events"] if e["event"] == "iteration"
    ]
    assert len(events) == 8
    # the first iterations trace; after warmup every jit call must hit cache
    assert sum(e["compiles_delta"] for e in events[:3]) > 0
    assert all(e["compiles_delta"] == 0 for e in events[3:])


def test_no_recompile_streaming_predict_varied_batches():
    X, y = _data(n=600)
    booster = lgb.train(
        {"objective": "regression", "num_leaves": 7, "verbosity": -1},
        lgb.Dataset(X, y),
        3,
    )
    chunk = 128
    booster.params["pred_chunk_rows"] = chunk
    booster.config = type(booster.config).from_params(booster.params)
    # warmup covers the bucket ladder once
    booster.predict(X[:chunk])
    booster.predict(X)
    from lightgbm_tpu.predict import streaming_compile_count

    before_stream = streaming_compile_count()
    before_global = lgb.compile_count()
    for n in (1, 7, 63, 128, 200, 311, 600):
        booster.predict(X[:n])
    assert streaming_compile_count() == before_stream
    assert lgb.compile_count() == before_global


def test_instrumented_jit_counts_retraces_by_label():
    from lightgbm_tpu.obs.jit import instrumented_jit

    import jax.numpy as jnp

    before = dict(lgb.compile_counts_by_label())

    @instrumented_jit(label="test/add1")
    def add1(x):
        return x + 1

    add1(jnp.ones((4,)))
    add1(jnp.ones((4,)))  # cache hit: no retrace
    add1(jnp.ones((8,)))  # new shape: retrace
    by_label = lgb.compile_counts_by_label()
    assert by_label["test/add1"] - before.get("test/add1", 0) == 2


def test_compile_counter_is_exact_under_threads():
    """compile_count()/compile_counts_by_label() take the same lock as
    note_compile's read-modify-write, so concurrent noters never lose an
    increment and readers never observe a torn count/label pair."""
    import threading

    from lightgbm_tpu.obs.jit import (
        compile_count,
        compile_counts_by_label,
        note_compile,
    )

    n_threads, per_thread = 8, 250
    before_total = compile_count()
    before_label = compile_counts_by_label().get("test/threads", 0)
    barrier = threading.Barrier(n_threads)

    def noter():
        barrier.wait()
        for _ in range(per_thread):
            note_compile("test/threads")
            assert compile_count() >= 0  # interleave reads with writes

    threads = [threading.Thread(target=noter) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert compile_count() - before_total == n_threads * per_thread
    assert (
        compile_counts_by_label()["test/threads"] - before_label
        == n_threads * per_thread
    )


def test_predict_events_when_enabled():
    X, y = _data(n=500)
    booster = lgb.train(
        {"objective": "regression", "num_leaves": 7, "verbosity": -1},
        lgb.Dataset(X, y),
        3,
    )
    ses = get_session().configure(enabled=True)
    ses.reset()
    booster.params["pred_chunk_rows"] = 128
    booster.config = type(booster.config).from_params(booster.params)
    booster.predict(X)
    chunk_evs = [e for e in ses.events if e["event"] == "predict_chunk"]
    summaries = [e for e in ses.events if e["event"] == "predict"]
    assert len(summaries) == 1
    assert summaries[0]["chunks"] == len(chunk_evs) >= 2
    assert summaries[0]["rows"] == 500
    assert set(summaries[0]["phases"]) == {
        "bin_ms", "transfer_ms", "walk_ms", "host_ms"
    }


# ------------------------------------------------------ collective byte model
def test_psum_bytes_model():
    from lightgbm_tpu.parallel import psum_bytes_per_iteration

    f, b = 28, 256
    hist = f * b * 3 * 4
    serial = psum_bytes_per_iteration(10, f, b, leaf_batch=1, mesh_size=4)
    assert serial["steps"] == 10
    assert serial["hist_bytes"] == 11 * hist  # 10 splits + root
    assert serial["count_bytes"] == 10 * 2 * 4 + 8
    batched = psum_bytes_per_iteration(10, f, b, leaf_batch=4, mesh_size=4)
    assert batched["steps"] == 3  # ceil(10 / 4)
    assert batched["hist_bytes"] == (3 * 4 + 1) * hist
    ring = 2 * (4 - 1) / 4
    assert batched["ring_bytes_per_device"] == pytest.approx(
        (batched["hist_bytes"] + batched["count_bytes"]) * ring
    )
    none = psum_bytes_per_iteration(0, f, b)
    assert none["steps"] == 0 and none["hist_bytes"] == hist


def test_collective_gauges_under_data_parallel():
    X, y = _data(n=512)
    params = {
        "objective": "regression",
        "num_leaves": 7,
        "verbosity": -1,
        "tree_learner": "data",
        "telemetry": True,
    }
    booster = lgb.train(params, lgb.Dataset(X, y), 3)
    tel = booster.telemetry()
    if booster._mesh is None:
        pytest.skip("single device: data-parallel mesh not formed")
    events = [e for e in tel["events"] if e["event"] == "iteration"]
    assert all("collective" in e for e in events)
    coll = events[-1]["collective"]
    assert coll["hist_bytes"] > 0 and coll["steps"] > 0
    assert tel["gauges"]["collective_hist_bytes"] == coll["hist_bytes"]
    assert tel["gauges"]["collective_ring_bytes_per_device"] >= 0


# --------------------------------------------- executable accounting (cost/*)
def test_cost_memory_gauges_train_and_predict(tmp_path):
    """obs_device_accounting captures executable cost/memory analysis for
    BOTH the training grower and the streaming predictor, and the families
    round-trip through the JSONL sink's train_summary record."""
    X, y = _data(n=500)
    sink = str(tmp_path / "events.jsonl")
    params = {
        "objective": "regression",
        "num_leaves": 7,
        "verbosity": -1,
        "telemetry": True,
        "telemetry_out": sink,
        "obs_device_accounting": True,
    }
    booster = lgb.train(params, lgb.Dataset(X, y), 3)
    booster.predict(X)
    gauges = booster.telemetry()["gauges"]
    # train: the grower's jit label carries FLOPs and the full memory family
    assert gauges["cost/grow_tree/flops"] > 0
    assert gauges["cost/grow_tree/bytes_accessed"] > 0
    assert gauges["memory/grow_tree/temp_bytes"] > 0
    assert gauges["memory/grow_tree/argument_bytes"] > 0
    assert gauges["memory/grow_tree/output_bytes"] > 0
    # streaming predict: per-variant label (packed/stacked/real)
    pred_cost = [
        k for k in gauges if k.startswith("cost/predict/stream/")
    ]
    assert pred_cost, f"no predict cost gauges in {sorted(gauges)}"
    assert all(gauges[k] >= 0 for k in pred_cost)
    # JSONL round-trip: the train_summary line carries the gauge families
    lines = [json.loads(l) for l in open(sink)]
    summary = [l for l in lines if l["event"] == "train_summary"][-1]
    assert summary["gauges"]["cost/grow_tree/flops"] == pytest.approx(
        gauges["cost/grow_tree/flops"]
    )
    assert "memory/grow_tree/temp_bytes" in summary["gauges"]


def test_device_accounting_off_means_no_cost_gauges():
    X, y = _data()
    booster = lgb.train(
        {
            "objective": "regression",
            "num_leaves": 7,
            "verbosity": -1,
            "telemetry": True,
        },
        lgb.Dataset(X, y),
        2,
    )
    gauges = booster.telemetry()["gauges"]
    assert not [k for k in gauges if k.startswith(("cost/", "memory/"))]


def test_device_memory_graceful_noop_on_unsupported_backend():
    """CPU devices report no memory_stats: sampling must silently no-op
    (latching the unsupported probe) instead of erroring or emitting
    garbage gauges."""
    from lightgbm_tpu.obs import device as obs_device

    ses = get_session().configure(enabled=True, device_accounting=True)
    obs_device.sample_device_memory("test")
    supported = obs_device.device_memory_supported()
    has_stats = any(
        d.memory_stats() for d in jax.local_devices()
    )
    assert supported is has_stats or (supported is None)
    if not has_stats:
        assert not [
            k for k in ses.gauges if k.startswith("memory/hbm_")
        ]


# -------------------------------------------------------------- profiler glue
def test_profile_trace_dir_writes_trace(tmp_path):
    import os

    trace_dir = str(tmp_path / "trace")
    X, y = _data()
    lgb.train(
        {
            "objective": "regression",
            "num_leaves": 7,
            "verbosity": -1,
            "profile_trace_dir": trace_dir,
            "profile_iter_start": 1,
            "profile_iter_end": 2,
        },
        lgb.Dataset(X, y),
        4,
    )
    # start/stop ran and produced profiler output (plugin layout varies)
    assert os.path.isdir(trace_dir)
    found = [
        os.path.join(r, f) for r, _, fs in os.walk(trace_dir) for f in fs
    ]
    assert found, "profiler trace produced no files"


def test_sync_timing_phases_cover_wall():
    X, y = _data(n=500)
    params = {
        "objective": "regression",
        "num_leaves": 15,
        "verbosity": -1,
        "telemetry": True,
        "obs_sync_timing": True,
    }
    booster = lgb.train(params, lgb.Dataset(X, y), 4)
    events = [
        e for e in booster.telemetry()["events"] if e["event"] == "iteration"
    ]
    # with per-phase blocking the measured phases account for most of the
    # iteration wall (bookkeeping outside phases stays small)
    steady = events[-1]
    assert sum(steady["phases"].values()) <= steady["wall_ms"] + 1.0
    assert steady["phases"]["grow"] > 0
