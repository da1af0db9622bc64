"""Serving plane (lightgbm_tpu.serving): batcher, registry, refresh, server.

Contracts under test:
  * micro-batched serving is BIT-IDENTICAL per request to calling
    ``Booster.predict`` directly — across the bucket ladder, remainder
    buckets, coalesced mixed-size batches, and the real-space walker
    (model_str round-trip, f64 suspect re-walk included);
  * after the load-time ladder warmup, NO request of any size compiles
    anything (``compile_counts_by_label`` stays flat);
  * two co-resident models keep distinct per-model executable scopes
    (``predict/stream/{id}@v{n}/...`` labels) — the satellite-1 regression;
  * hot-swap is atomic under concurrent load: every response matches one
    model version exactly, never a mix;
  * LRU eviction under a device-memory budget drops the least-recently
    used idle model;
  * the refresh loop's metric gate promotes/rejects and writes an atomic
    artifact that round-trips bit-identically;
  * the chaos drills (swap_under_load, kill_during_warmup) pass;
  * the HTTP front end serves /predict, /models, /healthz (with the
    serving block) and /metrics (with lgbtpu_serve_*).
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs.health import HealthWatchdog
from lightgbm_tpu.predict import LADDER_MIN, bucket_rows
from lightgbm_tpu.resilience import chaos
from lightgbm_tpu.serving import MicroBatcher, ModelRegistry, RefreshLoop


def _train(seed=0, n=600, f=8, rounds=5, objective="binary"):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    w = rng.normal(size=f)
    if objective == "binary":
        y = ((X @ w) > 0).astype(np.float64)
    else:
        y = X @ w + 0.1 * rng.normal(size=n)
    bst = lgb.train(
        {"objective": objective, "num_leaves": 15, "verbose": -1},
        lgb.Dataset(X, label=y),
        num_boost_round=rounds,
    )
    return bst, X, y


@pytest.fixture(scope="module")
def served():
    """One warmed server + references computed BEFORE serving starts
    (the registry re-scopes the booster's engine at load, so pre-serve
    predictions are the independent oracle)."""
    bst, X, _ = _train()
    rng = np.random.default_rng(42)
    queries = {
        n: rng.normal(size=(n, X.shape[1]))
        for n in (1, 3, 17, LADDER_MIN, LADDER_MIN + 1, 512, 700)
    }
    refs = {n: bst.predict(q) for n, q in queries.items()}
    server = lgb.serve(bst, deadline_ms=3.0, max_batch=512, port=-1)
    yield server, bst, queries, refs
    server.stop()


# ---------------------------------------------------------------- parity


def test_microbatch_parity_bit_identical(served):
    server, _, queries, refs = served
    for n, q in queries.items():
        got = server.predict(q, timeout=30.0)
        assert got.shape == refs[n].shape
        assert np.array_equal(got, refs[n]), f"rows={n} not bit-identical"


def test_concurrent_mixed_sizes_parity(served):
    server, _, queries, refs = served
    futs = [
        (n, server.predict_async(q))
        for n, q in list(queries.items()) * 4
    ]
    for n, f in futs:
        resp = f.result(timeout=30.0)
        assert np.array_equal(resp.values, refs[n]), f"rows={n} mixed up"
        assert resp.info["model_id"] == "default"


def test_real_space_parity_model_str_roundtrip():
    """The real-space walker (no train-set bins, f64 suspect re-walk)
    must serve bit-identically too."""
    bst, X, _ = _train(seed=7, objective="regression")
    loaded = lgb.Booster(model_str=bst.model_to_string())
    rng = np.random.default_rng(11)
    Xq = rng.normal(size=(301, X.shape[1]))
    ref = loaded.predict(Xq)
    with lgb.serve(loaded, deadline_ms=2.0, max_batch=512, port=0) as srv:
        assert np.array_equal(srv.predict(Xq, timeout=30.0), ref)


# ------------------------------------------------------------- batcher


def _stub_dispatch(log):
    def dispatch(plans):
        log.append([(m.shape, live) for m, live in plans])
        outs = [m[:live].sum(axis=1) for m, live in plans]
        return np.concatenate(outs), {"model_id": "stub"}

    return dispatch


def test_batcher_plans_are_ladder_buckets():
    log = []
    b = MicroBatcher(_stub_dispatch(log), deadline_ms=20.0, max_batch=512)
    try:
        X = np.arange(700.0 * 4).reshape(700, 4)
        got = b.submit(X).result(timeout=30.0)
        assert np.array_equal(got.values, X.sum(axis=1))
    finally:
        b.stop()
    (plans,) = log
    # 700 rows, chunk 512: one full 512 plan + a 188-live remainder
    # padded to its 256 bucket
    assert plans == [((512, 4), 512), ((256, 4), 188)]
    for (rows, _), live in plans:
        assert rows == bucket_rows(live, 512)


def test_batcher_deadline_vs_full_flush_and_carry():
    log = []
    b = MicroBatcher(_stub_dispatch(log), deadline_ms=200.0, max_batch=256)
    try:
        # lone small request: nothing else arrives -> deadline flush
        r = b.submit(np.ones((8, 3))).result(timeout=30.0)
        assert r.values.shape == (8,)
        # the worker publishes its counters after it has answered: the
        # answer can arrive a moment before they do
        for _ in range(200):
            if b.counters["deadline_flush"]:
                break
            time.sleep(0.01)
        assert b.counters["deadline_flush"] == 1
        # 200 + 100 rows: the second overflows 256, so the first batch
        # flushes FULL and the overflow is carried (FIFO) to the next
        f1 = b.submit(np.full((200, 3), 2.0))
        f2 = b.submit(np.full((100, 3), 3.0))
        assert np.array_equal(f1.result(timeout=30.0).values, np.full(200, 6.0))
        assert np.array_equal(f2.result(timeout=30.0).values, np.full(100, 9.0))
        for _ in range(200):  # as above: counters follow the answers
            stats = b.stats()
            if stats["requests"] == 3:
                break
            time.sleep(0.01)
        assert stats["full_flush"] >= 1
        assert stats["requests"] == 3
    finally:
        b.stop()


def test_batcher_rejects_bad_input_and_stop():
    b = MicroBatcher(_stub_dispatch([]), deadline_ms=5.0, max_batch=64)
    with pytest.raises(ValueError):
        b.submit(np.zeros((0, 3)))
    b.stop()
    with pytest.raises(RuntimeError):
        b.submit(np.zeros((1, 3)))


# -------------------------------------------------- compile discipline


def test_zero_recompiles_after_warmup(served):
    server, _, queries, _ = served
    # one pass so every size has been seen at least once post-warmup
    for q in queries.values():
        server.predict(q, timeout=30.0)
    before = dict(lgb.compile_counts_by_label())
    for _ in range(3):
        for q in queries.values():
            server.predict(q, timeout=30.0)
    after = dict(lgb.compile_counts_by_label())
    assert after == before, {
        k: (before.get(k, 0), v)
        for k, v in after.items()
        if before.get(k, 0) != v
    }


def test_two_models_get_distinct_exec_scopes():
    """Satellite-1 regression: co-resident models must compile under
    their own ``predict/stream/{scope}/...`` labels, not shared keys."""
    b1, X, _ = _train(seed=1)
    b2, _, _ = _train(seed=2)
    with lgb.serve(
        {"alpha": b1, "beta": b2}, deadline_ms=2.0, max_batch=256, port=0
    ) as srv:
        rng = np.random.default_rng(5)
        Xq = rng.normal(size=(33, X.shape[1]))
        pa = srv.predict(Xq, model_id="alpha", timeout=30.0)
        pb = srv.predict(Xq, model_id="beta", timeout=30.0)
        assert not np.array_equal(pa, pb)
        labels = lgb.compile_counts_by_label()
        for scope in ("alpha@v1", "beta@v1"):
            assert any(
                lbl.startswith(f"predict/stream/{scope}/") for lbl in labels
            ), f"no scoped exec labels for {scope}: {sorted(labels)}"


# ------------------------------------------------------------ hot-swap


def test_hot_swap_atomicity_under_concurrent_load():
    b1, X, _ = _train(seed=3, objective="regression")
    b2, _, _ = _train(seed=4, objective="regression")
    rng = np.random.default_rng(9)
    Xq = rng.normal(size=(40, X.shape[1]))
    p1, p2 = b1.predict(Xq), b2.predict(Xq)
    assert not np.array_equal(p1, p2)
    with lgb.serve(b1, deadline_ms=1.0, max_batch=256, port=0) as srv:
        futures, stop = [], threading.Event()

        def client():
            # paced + bounded: the swap's warmup takes seconds, and an
            # unthrottled submit loop would bury the worker under an
            # unbounded backlog of futures
            for _ in range(300):
                if stop.is_set():
                    break
                futures.append(srv.predict_async(Xq))
                time.sleep(0.002)

        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        info = srv.swap("default", b2)
        stop.set()
        for t in threads:
            t.join()
        assert info["version"] == 2
        seen = {1: 0, 2: 0}
        for f in futures:
            resp = f.result(timeout=30.0)
            if np.array_equal(resp.values, p1):
                assert resp.info["version"] == 1
            elif np.array_equal(resp.values, p2):
                assert resp.info["version"] == 2
            else:
                raise AssertionError("response mixes model versions")
            seen[resp.info["version"]] += 1
        # post-swap requests must serve v2 exactly
        assert np.array_equal(srv.predict(Xq, timeout=30.0), p2)
        assert srv.serving_snapshot()["models"][0]["version"] == 2


# ------------------------------------------------------------ registry


def test_registry_lru_eviction_under_budget():
    ba, _, _ = _train(seed=5, rounds=3)
    bb, _, _ = _train(seed=6, rounds=3)
    probe = ModelRegistry(chunk=256)
    entry = probe.load("probe", lgb.Booster(model_str=ba.model_to_string()))
    per_model = entry.device_bytes
    probe.close()
    assert per_model > 0
    # budget fits ~one model: loading the second must evict the first
    reg = ModelRegistry(
        chunk=256, memory_budget_bytes=int(per_model * 1.5)
    )
    try:
        reg.load("a", lgb.Booster(model_str=ba.model_to_string()))
        reg.load("b", lgb.Booster(model_str=bb.model_to_string()))
        ids = {m["model_id"] for m in reg.models()}
        assert ids == {"b"}, ids
        with pytest.raises(KeyError):
            reg.booster("a")
        assert reg.resident_bytes() <= int(per_model * 1.5)
    finally:
        reg.close()


def test_registry_load_twice_rejected():
    bst, _, _ = _train(seed=8, rounds=2)
    reg = ModelRegistry(chunk=256)
    try:
        reg.load("m", bst, warm=False)
        with pytest.raises(ValueError):
            reg.load("m", bst, warm=False)
    finally:
        reg.close()


# ------------------------------------------------------------- refresh


def test_refresh_gate_promotes_and_writes_atomic_artifact(tmp_path):
    bst, X, y = _train(seed=10, objective="regression")
    path = str(tmp_path / "refreshed.txt")
    with lgb.serve(bst, deadline_ms=2.0, max_batch=256, port=0) as srv:
        loop = srv.refresh_loop(
            min_rows=64, metric="l2", tolerance=1e9, save_path=path
        )
        loop.observe(X[:300], y[:300])
        report = loop.run_once()
        assert report["promoted"], report
        assert report["version"] == 2
        assert report["artifact"] == path
        promoted = srv.registry.booster("default")
        served = srv.predict(X[:90], timeout=30.0)
    # the artifact round-trips bit-identically to the promoted model
    reloaded = lgb.Booster(model_file=path)
    assert np.array_equal(reloaded.predict(X[:90]), promoted.predict(X[:90]))
    assert np.array_equal(served, promoted.predict(X[:90]))


def test_refresh_gate_rejects_worse_candidate(tmp_path):
    bst, X, y = _train(seed=12, objective="regression")
    with lgb.serve(bst, deadline_ms=2.0, max_batch=256, port=0) as srv:
        loop = srv.refresh_loop(min_rows=64, metric="l2", tolerance=-1e9)
        loop.observe(X[:200], y[:200])
        report = loop.run_once()
        assert not report["promoted"]
        assert loop.rejections == 1
        assert srv.serving_snapshot()["models"][0]["version"] == 1
    # insufficient traffic short-circuits without touching the model
    loop2 = RefreshLoop(srv.registry, "default", min_rows=10**6)
    assert loop2.run_once()["reason"] == "insufficient_rows"


# ------------------------------------------------------------ watchdog


def test_watchdog_serving_rule():
    wd = HealthWatchdog(deadline_miss_ceiling=0.25, deadline_miss_min_requests=16)
    quiet = wd.observe_serving(
        {"iter": 1, "deadline_miss_rate": 0.9, "requests": 4}
    )
    assert quiet == []  # below the min-requests floor: no alert
    alerts = wd.observe_serving(
        {"iter": 2, "deadline_miss_rate": 0.9, "requests": 64}
    )
    assert [a["rule"] for a in alerts] == ["serve_deadline"]
    ok = wd.observe_serving(
        {"iter": 3, "deadline_miss_rate": 0.0, "requests": 64}
    )
    assert ok == []


# --------------------------------------------------------------- chaos


def test_chaos_swap_under_load_drill(tmp_path):
    dump = chaos.swap_under_load_drill(str(tmp_path))
    assert dump


def test_chaos_kill_during_warmup_drill(tmp_path):
    dump = chaos.kill_during_warmup_drill(str(tmp_path))
    assert dump


# ----------------------------------------------------------------- http


def test_http_front_end(served):
    server, _, queries, refs = served
    assert server.url.startswith("http://127.0.0.1:")
    Xq = queries[17]
    req = urllib.request.Request(
        server.url + "/predict",
        data=json.dumps({"rows": Xq.tolist()}).encode(),
        headers={"Content-Type": "application/json"},
    )
    doc = json.loads(urllib.request.urlopen(req, timeout=10).read())
    assert np.array_equal(np.asarray(doc["predictions"]), refs[17])
    assert doc["model_id"] == "default" and doc["version"] >= 1

    models = json.loads(
        urllib.request.urlopen(server.url + "/models", timeout=10).read()
    )
    assert models["models"][0]["model_id"] == "default"

    hz = json.loads(
        urllib.request.urlopen(server.url + "/healthz", timeout=10).read()
    )
    assert "serving" in hz
    assert hz["serving"]["models"][0]["model_id"] == "default"
    assert "default" in hz["serving"]["batchers"]

    text = (
        urllib.request.urlopen(server.url + "/metrics", timeout=10)
        .read()
        .decode()
    )
    for name in (
        "lgbtpu_serve_p50_ms",
        "lgbtpu_serve_p99_ms",
        "lgbtpu_serve_batch_fill",
        "lgbtpu_serve_deadline_miss_rate",
        "lgbtpu_serve_requests_total",
    ):
        assert any(
            line.startswith(name) for line in text.splitlines()
        ), f"{name} missing from /metrics"


def test_http_bad_request_and_unknown_model(served):
    server, _, _, _ = served

    def post(payload):
        req = urllib.request.Request(
            server.url + "/predict", data=payload,
            headers={"Content-Type": "application/json"},
        )
        try:
            return urllib.request.urlopen(req, timeout=10).status
        except urllib.error.HTTPError as e:
            return e.code

    assert post(b"not json") == 400
    assert post(json.dumps({"rows": [[0.0] * 8], "model": "nope"}).encode()) == 404
