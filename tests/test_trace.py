"""End-to-end structured tracing: span recorder, Chrome export, wiring.

Covers the always-on span recorder (tree integrity, bounded-ring
eviction accounting, deterministic sampling), the Chrome trace-event
JSON export (Perfetto-loadable schema), the training instrumentation
(the ``train/*`` / ``wait/*`` tree of the layer boundaries, in the ring and
as profiler annotations in ``/host:CPU``; launch spans carrying the exact
per-iteration device counters; per-iteration ``from_launch`` JSONL events;
the phases and ``global_timer`` the spans feed), the serving decomposition (request/queue_wait/batch stages,
W3C traceparent round-trip over HTTP), dump-on-fault pairing with the
flight recorder, the iteration-denominated watchdog cadence at
``train_steps_per_launch`` N=1 vs N=8, and the zero-retrace contract.
"""

import json
import os
import urllib.request

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.obs.flight import get_flight  # noqa: E402
from lightgbm_tpu.obs.health import HealthWatchdog  # noqa: E402
from lightgbm_tpu.obs.jit import compile_counts_by_label  # noqa: E402
from lightgbm_tpu.obs.registry import get_session  # noqa: E402
from lightgbm_tpu.obs.trace import (  # noqa: E402
    MIN_CAPACITY,
    TRACE_SCHEMA,
    TraceRecorder,
    format_traceparent,
    get_tracer,
    parse_traceparent,
)


@pytest.fixture(autouse=True)
def _clean_obs():
    ses = get_session()
    ses.configure(enabled=False)
    ses.reset()
    flight = get_flight()
    flight.reset()
    flight.configure(fault_dir="", run_info={}, active=True)
    tracer = get_tracer()
    tracer.reset()
    tracer.configure(active=True, capacity=4096, default_rate=1.0, rates={})
    yield
    ses.configure(enabled=False)
    ses.reset()
    flight.reset()
    flight.configure(fault_dir="", run_info={}, active=True)
    tracer.reset()
    tracer.configure(active=True, capacity=4096, default_rate=1.0, rates={})


def _data(n=300, f=5, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = X[:, 0] * 2 + np.sin(X[:, 1]) + 0.1 * rng.normal(size=n)
    return X, y


_PARAMS = {
    "objective": "regression",
    "num_leaves": 7,
    "verbosity": -1,
    "deterministic": True,
    "seed": 7,
}


# ------------------------------------------------------------- recorder core
def test_span_tree_integrity():
    tr = TraceRecorder()
    with tr.span("root", "train") as root:
        assert root is not None
        with tr.span("child", "train") as child:
            tr.instant("leaf", "lifecycle")
    spans = tr.spans()
    by_name = {s["name"]: s for s in spans}
    assert by_name["child"]["parent_id"] == root.span_id
    assert by_name["child"]["trace_id"] == root.trace_id
    assert by_name["leaf"]["parent_id"] == child.span_id
    assert by_name["root"]["parent_id"] is None
    # ids are stable hex of the documented widths
    assert len(root.trace_id) == 32 and len(root.span_id) == 16
    int(root.trace_id, 16), int(root.span_id, 16)
    # ends arrive child-first, and every duration is non-negative
    assert [s["name"] for s in spans] == ["leaf", "child", "root"]
    assert all((s["dur"] or 0) >= 0 for s in spans)


def test_ring_eviction_accounting():
    tr = TraceRecorder()
    tr.configure(capacity=MIN_CAPACITY)
    for i in range(MIN_CAPACITY + 36):
        tr.end(tr.begin(f"s{i}", "train"))
    st = tr.stats()
    assert st["ring"] == MIN_CAPACITY
    assert st["spans_total"] == MIN_CAPACITY + 36
    assert st["dropped_total"] == 36
    # the ring keeps the newest spans
    assert tr.spans()[-1]["name"] == f"s{MIN_CAPACITY + 35}"


def test_sampling_deterministic_and_per_category():
    tr = TraceRecorder()
    tr.configure(default_rate=0.25, rates={"serve": 1.0, "phase": 0.0})
    kept = sum(tr.begin(f"t{i}", "train") is not None for i in range(100))
    assert kept == 25  # counter-based: exactly rate * n
    assert all(tr.begin(f"r{i}", "serve") is not None for i in range(10))
    assert all(tr.begin(f"p{i}", "phase") is None for i in range(10))
    tr.configure(active=False)
    assert tr.begin("off", "serve") is None


def test_traceparent_parse_and_format():
    tp = format_traceparent("ab" * 16, "cd" * 8)
    assert tp == "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    assert parse_traceparent(tp) == ("ab" * 16, "cd" * 8)
    assert parse_traceparent("garbage") is None
    assert parse_traceparent("") is None
    assert parse_traceparent(None) is None
    # all-zero ids are invalid per W3C trace-context
    assert parse_traceparent("00-" + "0" * 32 + "-" + "cd" * 8 + "-01") is None
    assert parse_traceparent("00-" + "ab" * 16 + "-" + "0" * 16 + "-01") is None


def test_chrome_trace_schema(tmp_path):
    tr = TraceRecorder()
    with tr.span("outer", "train", args={"k": 1}):
        tr.instant("mark", "lifecycle")
    path = tr.dump(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    assert isinstance(doc["traceEvents"], list)
    assert doc["displayTimeUnit"] == "ms"
    assert doc["metadata"]["schema"] == TRACE_SCHEMA
    events = doc["traceEvents"]
    metas = [e for e in events if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in metas)
    assert any(e["name"] == "thread_name" for e in metas)
    xs = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    assert len(xs) == 1 and len(instants) == 1
    for e in xs:
        assert isinstance(e["ts"], int) and isinstance(e["dur"], int)
        assert e["pid"] == os.getpid()
        assert {"trace_id", "span_id"} <= set(e["args"])
    assert instants[0]["s"] == "t"
    # non-meta events are sorted by timestamp
    ts = [e["ts"] for e in events if e["ph"] != "M"]
    assert ts == sorted(ts)
    assert tr.stats()["last_dump"] == path


# -------------------------------------------------------------- train spans
_ITER_CHILDREN = {
    "train/gradients", "train/sample", "train/grow", "train/score_update",
    "wait/fetch_tree", "train/host_tree",
}


def test_train_iteration_spans_and_phase_children():
    X, y = _data()
    lgb.train(dict(_PARAMS, telemetry=True), lgb.Dataset(X, y), 3)
    spans = get_tracer().spans()
    runs = [s for s in spans if s["name"] == "train/run"]
    iters = [s for s in spans if s["name"] == "train/iteration"]
    assert len(runs) == 1
    assert len(iters) == 3
    assert all(s["parent_id"] == runs[0]["span_id"] for s in iters)
    assert all(s["trace_id"] == runs[0]["trace_id"] for s in iters)
    iter_ids = {s["span_id"] for s in iters}
    kids = [s for s in spans if s["parent_id"] in iter_ids and s["cat"] != "compile"]
    # the layer boundaries are the iteration's children, under fixed names
    # (beside them only what compiled on the way, in category compile);
    # the pipelined path fetches a tree one iteration late, so the first
    # iteration has no wait/fetch_tree and the last tree is fetched by the
    # model's first reader, outside any iteration
    assert {s["name"] for s in kids} == _ITER_CHILDREN
    per_iter = {i: {s["name"] for s in kids if s["parent_id"] == i} for i in iter_ids}
    assert all({"train/gradients", "train/sample", "train/grow",
                "train/score_update"} <= names for names in per_iter.values())
    assert sum("wait/fetch_tree" in names for names in per_iter.values()) == 2
    assert not any(s["name"].startswith("phase/") or s["cat"] == "phase" for s in spans)
    assert not any("synthetic" in s for s in spans)


def _host_events(trace_dir):
    """(name, start_ns, end_ns) of every event of the profile's /host:CPU
    plane, whichever thread line it is on."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            {k: v for k, v in e.stats} if e.name == "train/iteration" else {})
                           for e in line.events]
    return events


def test_span_tree_is_in_the_profilers_host_plane(tmp_path):
    """With telemetry OFF (the default, what every benchmark cell runs) and
    any profiler session live, the layer boundaries are events of
    /host:CPU under exactly the ring's names, nested in time."""
    X, y = _data()
    Xv, yv = _data(n=120, seed=3)
    train, valid = lgb.Dataset(X, y), lgb.Dataset(Xv, yv)
    train.construct()
    lgb.global_timer.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        lgb.train(dict(_PARAMS, metric="l2"), train, 2, valid_sets=[valid],
                  callbacks=[lambda env: None])
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    ours = [e for e in events if e[0].split("/")[0] in ("train", "wait", "setup", "dataset")]
    names = {e[0] for e in ours}
    assert names == {
        "setup/booster_init", "setup/objective_init", "setup/transfer",
        "setup/kernel_import", "setup/add_valid", "dataset/construct", "dataset/bin_fit",
        "dataset/bundle", "dataset/pack",
        "train/run", "train/iteration", "train/gradients", "train/sample",
        "train/grow", "train/score_update", "wait/fetch_tree", "train/host_tree",
        "train/eval", "train/eval_score", "wait/eval_metric", "train/callbacks",
    }, names

    def inside(child, parent):
        return [c for c in ours if c[0] == child and any(
            p[0] == parent and p[1] <= c[1] and c[2] <= p[2] for p in ours)]

    (run,) = [e for e in ours if e[0] == "train/run"]
    iters = [e for e in ours if e[0] == "train/iteration"]
    assert len(iters) == 2 and len(inside("train/iteration", "train/run")) == 2
    assert [e[3].get("iter") for e in sorted(iters, key=lambda e: e[1])] == [0, 1]
    assert len(inside("wait/fetch_tree", "train/iteration")) == 1
    for child in ("train/gradients", "train/sample", "train/grow", "train/score_update"):
        assert len(inside(child, "train/iteration")) == 2, child
    assert len(inside("train/eval", "train/run")) == 2
    assert len(inside("train/eval_score", "train/eval")) == 2
    assert len(inside("wait/eval_metric", "train/eval_score")) == 2
    assert len(inside("train/callbacks", "train/run")) == 2
    assert len(inside("dataset/construct", "setup/add_valid")) == 1  # the valid set's
    assert len(inside("setup/add_valid", "setup/booster_init")) == 1
    assert len(inside("setup/objective_init", "setup/booster_init")) == 1
    transfers = [e for e in ours if e[0] == "setup/transfer"]
    assert len(inside("setup/transfer", "setup/booster_init")) == len(transfers) >= 4
    assert not inside("train/iteration", "setup/booster_init")

    # the same names are in the ring, with parent links
    ring = get_tracer().spans()
    by_id = {s["span_id"]: s for s in ring}
    assert names <= {s["name"] for s in ring}
    copies = {s["args"]["what"]: s["args"]["bytes"] for s in ring
              if s["name"] == "setup/transfer"}
    assert copies.keys() >= {"bins", "score", "objective.label", "valid.score"}
    assert copies["objective.label"] == 4 * len(y) and copies["score"] == 4 * len(y)
    parents = {(s["name"], by_id[s["parent_id"]]["name"]) for s in ring
               if s["parent_id"] in by_id}
    assert {("train/iteration", "train/run"), ("wait/fetch_tree", "train/iteration"),
            ("train/grow", "train/iteration"), ("train/eval", "train/run"),
            ("train/eval_score", "train/eval"), ("wait/eval_metric", "train/eval_score"),
            ("train/callbacks", "train/run")} <= parents
    # and the spans fed GlobalTimer with telemetry off
    t = lgb.global_timer
    assert t.counts["boosting/update"] == 2 and t.counts["tree/grow"] == 2
    assert t.counts["boosting/eval"] == 2


def test_spans_feed_the_phases_and_the_timer_they_fed_before():
    """With telemetry on, ``Booster.telemetry()`` phases and ``global_timer``
    hold what the registry's phase timers and the doubled ``timed`` sites
    put there before they folded into the spans."""
    X, y = _data()
    lgb.global_timer.reset()
    b = lgb.train(dict(_PARAMS, telemetry=True), lgb.Dataset(X, y), 4)
    events = [e for e in b.telemetry()["events"] if e.get("event") == "iteration"]
    assert len(events) == 4
    for e in events:
        assert {"gradients", "sample", "grow"} <= set(e["phases"])
        assert set(e["phases"]) <= {"gradients", "sample", "grow", "score_update",
                                    "host_materialize"}
        assert sum(e["phases"].values()) <= e["wall_ms"] + 1.0
    # the first iteration commits its tree on the spot; from the second on
    # the pipelined path updates the score at dispatch and fetches a tree late
    assert ["score_update" in e["phases"] for e in events] == [False, True, True, True]
    assert sum("host_materialize" in e["phases"] for e in events) >= 3
    t = lgb.global_timer
    assert t.counts["boosting/update"] == 4 and t.counts["tree/grow"] == 4
    assert t.counts["dataset/construct"] == 1
    assert t.totals["tree/grow"] <= t.totals["boosting/update"]
    # the launch path: its dispatch is the "launch" phase, one update per launch
    lgb.global_timer.reset()
    get_session().reset()
    b = lgb.train(dict(_PARAMS, telemetry=True, train_steps_per_launch=2),
                  lgb.Dataset(X, y), 4)
    launches = [e for e in b.telemetry()["events"] if e.get("event") == "launch"]
    assert len(launches) == 2 and all(set(e["phases"]) == {"launch"} for e in launches)
    assert lgb.global_timer.counts["boosting/update"] == 2


def test_launch_per_iteration_counters_match_serial(tmp_path):
    X, y = _data()
    serial = lgb.train(
        dict(_PARAMS, telemetry=True), lgb.Dataset(X, y), 6
    )
    serial_events = [
        e for e in serial.telemetry()["events"]
        if e.get("event") == "iteration"
    ]
    assert len(serial_events) == 6
    # ground truth per-iteration splits from the serial model's own trees
    # (the serial JSONL's per-event split counts lag one iteration on the
    # pipelined path, so the trees are the alignment oracle)
    serial_splits = {
        i: tree["num_leaves"] - 1
        for i, tree in enumerate(serial.dump_model()["tree_info"])
    }

    tracer = get_tracer()
    tracer.reset()
    ses = get_session()
    ses.configure(enabled=False)
    ses.reset()
    launched = lgb.train(
        dict(_PARAMS, telemetry=True, train_steps_per_launch=3),
        lgb.Dataset(X, y), 6,
    )
    # byte-identical model (the params block legitimately differs by the
    # train_steps_per_launch line itself)
    drop = lambda txt: [  # noqa: E731
        ln for ln in txt.splitlines()
        if not ln.startswith("[train_steps_per_launch")
    ]
    assert drop(serial.model_to_string()) == drop(launched.model_to_string())
    spans = tracer.spans()
    launches = sorted((s for s in spans if s["name"] == "train/launch"),
                      key=lambda s: s["ts"])
    assert len(launches) == 2
    # no time that nobody measured is in the ring: a launch has no
    # train/iteration children, only what the host did
    assert not any("synthetic" in s for s in spans)
    assert not any(s["name"] == "train/iteration" for s in spans)
    launch_ids = {s["span_id"] for s in launches}
    kids = {}
    for s in spans:
        if s["parent_id"] in launch_ids and s["cat"] != "compile":  # PR 37: what compiled is there too
            kids.setdefault(s["parent_id"], []).append(s["name"])
    assert all(sorted(v) == ["train/launch_dispatch", "train/launch_replay",
                             "wait/launch_fetch"] for v in kids.values()), kids
    # the exact device counters of every iteration ride on the launch span
    records = [r for s in launches for r in s["args"]["per_iteration"]]
    assert [r["iter"] for r in records] == list(range(6))
    assert [s["args"]["launch_begin"] for s in launches] == [0, 3]
    for r in records:
        assert set(r) == {"iter", "trees_materialized", "splits", "grow_steps",
                          "refine_count"}
        assert r["splits"] == serial_splits[r["iter"]]
        assert r["trees_materialized"] == 1 and r["grow_steps"] >= r["splits"] > 0

    # satellite: per-iteration JSONL events replayed with from_launch=true
    launched_events = [
        e for e in launched.telemetry()["events"]
        if e.get("event") == "iteration"
    ]
    assert len(launched_events) == 6
    assert all(e.get("from_launch") for e in launched_events)
    assert {e["iter"]: e["splits"] for e in launched_events} == serial_splits


def test_dump_trace_api(tmp_path):
    X, y = _data()
    b = lgb.train(dict(_PARAMS), lgb.Dataset(X, y), 2)
    out = str(tmp_path / "run_trace.json")
    assert b.dump_trace(out) == out
    doc = json.loads(open(out).read())
    names = {e["name"] for e in doc["traceEvents"]}
    assert "train/run" in names and "train/iteration" in names


def test_dump_on_fault_pairs_flight_and_trace(tmp_path):
    flight = get_flight()
    flight.configure(fault_dir=str(tmp_path), run_info={}, active=True)
    flight.note_event({"event": "iteration", "iter": 0, "wall_ms": 1.0})
    tr = get_tracer()
    tr.end(tr.begin("train/iteration", "train"))
    flight_path = flight.dump("unit_fault")
    trace_path = flight.last_trace_path
    assert os.path.exists(flight_path) and os.path.exists(trace_path)
    # the pair shares one <ts>_<pid>_<n> suffix for postmortem correlation
    fsuf = os.path.basename(flight_path)[len("flight_"):]
    tsuf = os.path.basename(trace_path)[len("trace_"):]
    assert fsuf == tsuf
    doc = json.loads(open(trace_path).read())
    assert any(
        e["name"] == "train/iteration" for e in doc["traceEvents"]
    )


def test_trace_disabled_by_config():
    X, y = _data()
    lgb.train(dict(_PARAMS, trace_spans=False), lgb.Dataset(X, y), 2)
    assert get_tracer().stats()["spans_total"] == 0


# ------------------------------------------------------------- serving spans
@pytest.mark.slow
def test_serving_traceparent_http_round_trip():
    X, y = _data()
    b = lgb.train(dict(_PARAMS), lgb.Dataset(X, y), 3)
    tracer = get_tracer()
    tracer.reset()
    srv = lgb.serve(b, params={"serve_port": -1, "serve_deadline_ms": 2.0})
    try:
        caller_trace, caller_span = "ab" * 16, "cd" * 8
        req = urllib.request.Request(
            srv.url + "/predict",
            data=json.dumps({"rows": X[:4].tolist()}).encode(),
            headers={"traceparent": format_traceparent(caller_trace, caller_span)},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            doc = json.loads(resp.read())
            echoed = resp.headers.get("traceparent")
        assert np.allclose(doc["predictions"], b.predict(X[:4]))
        # echoed header: caller's trace id, the request span's own id
        assert echoed == doc["traceparent"]
        parsed = parse_traceparent(echoed)
        assert parsed is not None and parsed[0] == caller_trace
        spans = {s["span_id"]: s for s in tracer.spans()}
        req_span = spans[parsed[1]]
        assert req_span["name"] == "serve/request"
        assert req_span["trace_id"] == caller_trace
        assert req_span["parent_id"] == caller_span
        by_name = {}
        for s in spans.values():
            by_name.setdefault(s["name"], []).append(s)
        # queue_wait decomposes the request span; the stage spans decompose
        # the flush's batch span
        qw = by_name["serve/queue_wait"]
        assert any(s["parent_id"] == req_span["span_id"] for s in qw)
        batch = by_name["serve/batch"][0]
        for stage in (
            "serve/batch_assembly",
            "serve/device_dispatch",
            "serve/unpad_respond",
        ):
            assert any(
                s["parent_id"] == batch["span_id"] for s in by_name[stage]
            )
        # GET /trace serves the same Chrome JSON document
        with urllib.request.urlopen(srv.url + "/trace", timeout=10) as resp:
            tdoc = json.loads(resp.read())
        assert {e["name"] for e in tdoc["traceEvents"]} >= {
            "serve/request", "serve/batch", "serve/queue_wait"
        }
        # /metrics: trace counters + queue/device attribution summaries
        with urllib.request.urlopen(srv.url + "/metrics", timeout=10) as resp:
            text = resp.read().decode()
        assert "lgbtpu_trace_spans_total" in text
        assert "lgbtpu_trace_dropped_total" in text
        assert 'lgbtpu_serve_queue_ms{quantile="0.99"}' in text
        assert 'lgbtpu_serve_device_ms{quantile="0.99"}' in text
    finally:
        srv.stop()


def test_predict_async_traceparent_echo():
    X, y = _data()
    b = lgb.train(dict(_PARAMS), lgb.Dataset(X, y), 2)
    srv = lgb.serve(b, params={"serve_port": 0, "serve_deadline_ms": 1.0})
    try:
        tp = format_traceparent("12" * 16, "34" * 8)
        resp = srv.predict_async(X[:2], traceparent=tp).result(timeout=30)
        parsed = parse_traceparent(resp.info["traceparent"])
        assert parsed is not None and parsed[0] == "12" * 16
        # without a header the info carries no trace context only when
        # the request span was sampled out; by default it is sampled in
        resp2 = srv.predict_async(X[:2]).result(timeout=30)
        assert parse_traceparent(resp2.info.get("traceparent")) is not None
    finally:
        srv.stop()


# --------------------------------------------------------- watchdog cadence
def _cadence_alerts(launch_steps: int, total: int = 80):
    """Feed the watchdog commit-rate-collapse telemetry as `total`
    iterations grouped into `launch_steps`-sized launch events; returns
    the iterations at which the rule fired."""
    ses = get_session()
    ses.configure(enabled=True)
    ses.set_gauge("grower.commit_rate", 0.05)
    ses.set_gauge("grower.leaf_batch_effective", 4.0)
    wd = HealthWatchdog(warmup_iters=7, cooldown_iters=16)
    fired = []
    for start in range(0, total, launch_steps):
        last = start + launch_steps - 1
        if launch_steps == 1:
            event = {"event": "iteration", "iter": last, "wall_ms": 10.0}
        else:
            event = {
                "event": "launch",
                "iter": last,
                "launch_begin": start,
                "steps": launch_steps,
                "wall_ms": 10.0,
            }
        for alert in wd.observe(event, ses):
            fired.append(alert["iter"])
    ses.configure(enabled=False)
    ses.reset()
    return fired


def test_watchdog_cadence_identical_serial_vs_launch():
    """Satellite: warmup/cooldown counted in iterations, not observe()
    calls — N=1 and N=8 launches see the identical alert cadence."""
    serial = _cadence_alerts(1)
    launched = _cadence_alerts(8)
    assert serial == [7, 23, 39, 55, 71]
    assert launched == serial


# ------------------------------------------------------------- perf contract
def test_tracing_adds_zero_retraces():
    X, y = _data()
    params = dict(_PARAMS, telemetry=True)
    lgb.train(params, lgb.Dataset(X, y), 3)
    before = compile_counts_by_label()
    # identical run with tracing exercised end-to-end (spans + dump) must
    # not introduce a single new compile at any jit site
    get_tracer().reset()
    b = lgb.train(params, lgb.Dataset(X, y), 3)
    assert get_tracer().stats()["spans_total"] > 0
    assert b.dump_trace  # API exists on every Booster
    after = compile_counts_by_label()
    assert after == before


@pytest.mark.parametrize("steps,valid,span,extra,want", [
    (1, True, "train/iteration", {},
     {"score_lookup": "onehot", "valid_walk": "contract"}),
    (1, False, "train/iteration", {}, {"score_lookup": "onehot", "valid_walk": "none"}),
    (3, False, "train/launch", {}, {"score_lookup": "onehot", "valid_walk": "none"}),
    # PR 36: how a tree grown on the segment path gives every row its leaf
    (1, False, "train/iteration", {"hist_mode": "seg"}, {"leaf_ids": "walk"}),
    (3, False, "train/launch", {"hist_mode": "seg"}, {"leaf_ids": "walk"}),
    (1, False, "train/iteration", {"hist_mode": "seg", "num_leaves": 1023},
     {"leaf_ids": "segment"}),
    (3, False, "train/launch", {"hist_mode": "seg", "num_leaves": 1023},
     {"leaf_ids": "segment"}),
    (1, False, "train/iteration", {"hist_mode": "ordered"}, {"leaf_ids": "none"}),
    (3, False, "train/launch", {"hist_mode": "ordered"}, {"leaf_ids": "none"}),
], ids=["iteration_with_a_validation_set", "iteration_without", "launch",
        "iteration_leaf_ids_walk", "launch_leaf_ids_walk",
        "iteration_leaf_ids_segment", "launch_leaf_ids_segment",
        "iteration_off_the_segment_path", "launch_off_the_segment_path"])
def test_the_score_updates_forms_ride_on_the_top_spans(steps, valid, span, extra, want):
    """PR 34: which form looks a tree's output up (``score_lookup``) and which
    scores a validation set (``valid_walk``), beside the kernels' own args;
    PR 36: which form gives every row its leaf (``leaf_ids``)."""
    X, y = _data()
    train = lgb.Dataset(X, y)
    valid_sets = [lgb.Dataset(*_data(seed=1), reference=train)] if valid else None
    lgb.train(dict(_PARAMS, telemetry=True, train_steps_per_launch=steps, **extra),
              train, 3, valid_sets=valid_sets)
    tops = [s for s in get_tracer().spans() if s["name"] == span]
    assert tops
    for s in tops:
        assert {k: s["args"].get(k) for k in want} == want


# ------------------------------------------- the set-up's timeline (PR 37)
_IMPORT_PROBE = """
import json, sys
{first}
import lightgbm_tpu
from lightgbm_tpu.obs import get_tracer
spans = [s for s in get_tracer().spans() if s["name"] == "setup/import"]
print(json.dumps(spans))
"""


@pytest.mark.parametrize("jax_first", [False, True])
def test_a_fresh_interpreters_import_is_a_span(jax_first):
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    out = subprocess.run(
        [sys.executable, "-c", _IMPORT_PROBE.format(first="import jax" if jax_first else "")],
        env=env, cwd=root, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    (span,) = json.loads(out.stdout.strip().splitlines()[-1])
    assert span["cat"] == "setup" and span["dur"] > 0
    assert span["args"] == {"jax_preloaded": jax_first}


def _inside(child, parent):
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


def test_booster_inits_children_lie_inside_it_and_name_its_parts():
    X, y = _data()
    Xv, yv = _data(n=120, seed=3)
    jax.clear_caches()  # whatever an earlier test compiled compiles again
    lgb.train(dict(_PARAMS, metric="l2"), lgb.Dataset(X, y), 2,
              valid_sets=[lgb.Dataset(Xv, yv)])
    spans = get_tracer().spans()
    (init,) = [s for s in spans if s["name"] == "setup/booster_init"]
    by_id = {s["span_id"]: s for s in spans}

    def under_init(s):
        while s is not None and s is not init:
            s = by_id.get(s["parent_id"])
        return s is init

    kids = [s for s in spans if s is not init and under_init(s)]
    assert {s["name"] for s in kids if s["cat"] == "setup"} == {
        "setup/objective_init", "setup/transfer", "setup/kernel_import",
        "setup/add_valid", "dataset/construct", "dataset/bin_fit", "dataset/bundle", "dataset/pack"}
    assert all(_inside(s, init) for s in kids)
    # what compiled on the way (eager conversions, the first programs) is
    # there too, by label, and nothing of the run's iterations is
    assert [s for s in kids if s["cat"] == "compile"]
    assert {s["cat"] for s in kids} <= {"setup", "compile"}
    (valid,) = [s for s in kids if s["name"] == "setup/add_valid"]
    assert valid["args"] == {"name": "valid_0"}
    assert {s["args"]["what"] for s in kids if s["name"] == "setup/transfer"
            and _inside(s, valid)} == {"valid.score", "bins"}  # the set's own matrix
    iters = [s for s in spans if s["name"] == "train/iteration"]
    assert [s["args"].get("first", False) for s in iters] == [True, False]
    assert all(s["ts"] >= init["ts"] + init["dur"] for s in iters)


def test_a_raise_in_the_set_up_leaves_no_parent_on_the_stack():
    X, y = _data()
    with pytest.raises(ValueError):
        lgb.train(dict(_PARAMS, boosting="no-such-boosting"), lgb.Dataset(X, y), 1)
    assert get_tracer().current() is None


def test_trace_spans_off_records_none_of_the_set_up():
    X, y = _data(seed=11)
    lgb.train(dict(_PARAMS, trace_spans=False, train_steps_per_launch=2),
              lgb.Dataset(X, y), 2)
    assert get_tracer().spans() == []
