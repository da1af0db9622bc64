#!/bin/bash
# Full test suite in CHUNKED pytest processes.
#
# One process compiling the whole suite's ~1000+ XLA programs can segfault
# XLA:CPU's LLVM JIT near the end of the run (jax 0.9.0, single-core VM;
# crash stack inside backend_compile_and_load).  Running the suite as a few
# separate processes keeps each under the threshold; the persistent
# compilation cache (tests/conftest.py) removes most recompiles between
# chunks.  Usage:  bash tools/run_tests.sh [extra pytest args]
set -u
cd "$(dirname "$0")/.." || exit 1
export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=8}"

rc=0

# graftlint gate: pure-ast static analysis (tracer safety, Pallas
# contracts, SPMD collective congruence GL007-GL010) diffed against the
# reviewed baseline.  Runs FIRST over the FULL tree and is a hard gate —
# a new finding or a stale baseline entry fails the suite before any
# pytest chunk spends time compiling.  (--changed-only is for the dev
# loop only; CI always takes the full-tree run.)
echo "=== graftlint (python -m lightgbm_tpu.lint --baseline lint_baseline.json) ==="
python -m lightgbm_tpu.lint --baseline lint_baseline.json || rc=$?

# graftlint IR gate: trace the real jit/shard_map entry matrix to jaxprs
# (abstract CPU tracing, no execution) and audit collectives, dtype
# promotion, donation, Pallas VMEM budgets and row gathers in the score
# update (GL011-GL016).  Also a
# hard gate, full matrix in CI (--changed-only scopes it in the dev
# loop); budgeted <30 s on top of the AST pass.
echo "=== graftlint IR (python -m lightgbm_tpu.lint --ir --baseline lint_baseline.json) ==="
python -m lightgbm_tpu.lint --ir --baseline lint_baseline.json || rc=$?

chunks=(
  "tests/test_a* tests/test_b* tests/test_c*"
  "tests/test_d* tests/test_e* tests/test_f* tests/test_g* tests/test_h* tests/test_i* tests/test_l*"
  "tests/test_m* tests/test_n* tests/test_o* tests/test_p*"
  "tests/test_q* tests/test_r* tests/test_s* tests/test_v*"
)
for chunk in "${chunks[@]}"; do
  echo "=== pytest $chunk $* ==="
  # shellcheck disable=SC2086
  python -m pytest $chunk -q "$@" || rc=$?
done

# telemetry smoke: a 3-iteration instrumented train must produce a JSONL
# stream the rollup tool can parse (one event per iteration, no recompiles
# hiding in steady state)
echo "=== telemetry smoke (3-iteration train -> tools/telemetry_summary.py) ==="
tel_out=$(mktemp /tmp/telemetry_smoke.XXXXXX.jsonl)
python - "$tel_out" <<'PYEOF' && python tools/telemetry_summary.py "$tel_out" || rc=$?
import sys
import numpy as np
import lightgbm_tpu as lgb

rng = np.random.default_rng(0)
X = rng.normal(size=(400, 6))
y = X[:, 0] + 0.1 * rng.normal(size=400)
lgb.train(
    {"objective": "regression", "num_leaves": 7, "verbosity": -1,
     "metric": "l2", "telemetry": True, "telemetry_out": sys.argv[1]},
    lgb.Dataset(X, y), 3,
    valid_sets=[lgb.Dataset(X, y)], valid_names=["t"],
)
PYEOF
rm -f "$tel_out"

# live-obs smoke: a 3-iteration train must serve parseable Prometheus
# text from the opt-in exporter WHILE training (scraped from an iteration
# callback), the chaos drills must each leave a valid flight dump behind,
# and the offline tools must digest both artifacts.
echo "=== live-obs smoke (exporter scrape + chaos flight dumps + obs_top) ==="
python - <<'PYEOF' || rc=$?
import json
import socket
import subprocess
import sys
import tempfile
import urllib.request

import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.resilience import chaos

with socket.socket() as s:
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]

scraped = {}

def scrape(env):
    if env.iteration == 1 and not scraped:
        url = f"http://127.0.0.1:{port}"
        scraped["metrics"] = urllib.request.urlopen(
            url + "/metrics", timeout=5).read().decode()
        scraped["health"] = json.loads(
            urllib.request.urlopen(url + "/healthz", timeout=5).read())

rng = np.random.default_rng(0)
X = rng.normal(size=(400, 6))
y = X[:, 0] + 0.1 * rng.normal(size=400)
tel = tempfile.mktemp(suffix=".jsonl")
booster = lgb.train(
    {"objective": "regression", "num_leaves": 7, "verbosity": -1,
     "telemetry": True, "telemetry_out": tel, "obs_export_port": port},
    lgb.Dataset(X, y), 3, callbacks=[scrape],
)
assert scraped, "exporter scrape callback never ran"
for line in scraped["metrics"].splitlines():  # parseable exposition text
    assert line.startswith("#") or len(line.split(" ")) == 2, line
assert "lgbtpu_iterations_total" in scraped["metrics"]
assert scraped["health"]["status"] == "ok"
assert booster.health()["iter"] == 3
print("live-obs smoke: exporter served parseable metrics during training")

dumps = []
for drill in (chaos.flight_dump_drill_numerics,
              chaos.flight_dump_drill_degradation):
    wd = tempfile.mkdtemp(prefix="lgbm_tpu_flight_smoke_")
    dumps.append(drill(wd))
    print(f"live-obs smoke: {drill.__name__} -> {dumps[-1]}")

for tool_args in ([ "tools/telemetry_summary.py", "--flight"] + dumps,
                  ["tools/obs_top.py", "--tail", tel, "--once",
                   "--no-color"]):
    r = subprocess.run([sys.executable] + tool_args, capture_output=True)
    assert r.returncode == 0, (tool_args, r.stderr.decode())
print("live-obs smoke: flight dumps + offline tools OK")
PYEOF

# serving smoke: lgb.serve() over a 3-tree model must coalesce concurrent
# mixed-size requests bit-identically to Booster.predict, publish
# lgbtpu_serve_* on /metrics and the serving block on /healthz, survive
# one hot-swap with full parity on the new version, and tear down clean.
echo "=== serving smoke (lgb.serve: mixed-size parity + /metrics + hot-swap) ==="
python - <<'PYEOF' || rc=$?
import json
import urllib.request

import numpy as np
import lightgbm_tpu as lgb

rng = np.random.default_rng(0)
X = rng.normal(size=(500, 6))
params = {"objective": "regression", "num_leaves": 7, "verbosity": -1}
b1 = lgb.train(params, lgb.Dataset(X, X[:, 0] + 0.1 * X[:, 1]), 3)
b2 = lgb.train(params, lgb.Dataset(X, X[:, 1] - 0.3 * X[:, 2]), 3)
queries = {n: rng.normal(size=(n, 6)) for n in (1, 7, 64, 300, 700)}
r1 = {n: b1.predict(q) for n, q in queries.items()}
r2 = {n: b2.predict(q) for n, q in queries.items()}

server = lgb.serve(b1, deadline_ms=3.0, max_batch=512, port=-1)
try:
    futs = [(n, server.predict_async(q)) for n, q in list(queries.items()) * 3]
    for n, f in futs:
        assert np.array_equal(f.result(timeout=30.0).values, r1[n]), n
    text = urllib.request.urlopen(server.url + "/metrics", timeout=5).read().decode()
    serve_lines = [l for l in text.splitlines() if l.startswith("lgbtpu_serve_")]
    assert serve_lines, "no lgbtpu_serve_* series on /metrics"
    hz = json.loads(urllib.request.urlopen(server.url + "/healthz", timeout=5).read())
    assert hz["serving"]["models"][0]["model_id"] == "default"
    info = server.swap("default", b2)
    assert info["version"] == 2
    for n, q in queries.items():
        assert np.array_equal(server.predict(q, timeout=30.0), r2[n]), n
    print("serving smoke: parity + metrics + hot-swap OK "
          f"({len(serve_lines)} serve series)")
finally:
    server.stop()
PYEOF

# tensor-forest smoke: the matmul prediction engine must be byte-identical
# to the walker on a 3-iteration eligible model (values + leaf indices),
# resolve via pred_engine=auto (the compile-time parity probe), and warm
# its own retrace label next to the walker's.
echo "=== tensor-forest smoke (pred_engine=matmul byte parity vs walker) ==="
python - <<'PYEOF' || rc=$?
import numpy as np
import lightgbm_tpu as lgb

rng = np.random.default_rng(0)
X = rng.normal(size=(800, 8))
X[rng.random(X.shape) < 0.05] = np.nan
y = np.nan_to_num(X[:, 0]) + 0.3 * np.nan_to_num(X[:, 1])
params = {"objective": "regression", "num_leaves": 15, "verbosity": -1}
b = lgb.train(params, lgb.Dataset(X, y, params=params), 3)
Xq = rng.normal(size=(700, 8))
Xq[rng.random(Xq.shape) < 0.05] = np.nan
walk = b.predict(Xq, pred_engine="walk")
mm = b.predict(Xq, pred_engine="matmul")
assert walk.tobytes() == mm.tobytes(), "matmul values diverged from walker"
assert b.last_predict_stats.get("engine") == "matmul"
auto = b.predict(Xq, pred_engine="auto")
assert auto.tobytes() == walk.tobytes(), "auto engine diverged from walker"
lw = b.predict(Xq, pred_leaf=True, pred_engine="walk")
lm = b.predict(Xq, pred_leaf=True, pred_engine="matmul")
assert np.array_equal(lw, lm), "matmul leaf indices diverged from walker"
labels = lgb.compile_counts_by_label()
assert any("tensor" in k for k in labels), sorted(labels)
print("tensor-forest smoke: walker/matmul byte parity OK")
PYEOF

# streaming-ingest smoke: a 3-iteration train whose Dataset was built by
# the chunked two-pass ingest (pass 1 samples + fits mappers, pass 2
# streams chunks through binning; the full raw f64 matrix never
# materializes) must dump byte-identically to the one-shot build of the
# same data/seed, including through a memmap-backed bin-plane spill.
echo "=== streaming-ingest smoke (chunked two-pass train parity vs one-shot) ==="
python - <<'PYEOF' || rc=$?
import tempfile

import numpy as np
import lightgbm_tpu as lgb

rng = np.random.default_rng(0)
X = rng.normal(size=(3000, 12))
X[:, 4] = (rng.random(3000) < 0.06) * rng.normal(size=3000)  # sparse col
y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "bin_construct_sample_cnt": 700, "data_random_seed": 3,
          "min_data_in_leaf": 10}

def dump(extra):
    p = dict(params, **extra)
    b = lgb.train(p, lgb.Dataset(X.copy(), y, params=p), 3)
    return "\n".join(ln for ln in b.model_to_string().splitlines()
                     if not ln.startswith("[ingest_"))

ref = dump({})
assert dump({"ingest_chunk_rows": 611}) == ref, (
    "chunked-ingest dump diverged from one-shot")
with tempfile.TemporaryDirectory() as td:
    assert dump({"ingest_chunk_rows": 611, "ingest_mmap_dir": td}) == ref, (
        "memmap-spill chunked dump diverged from one-shot")
print("streaming-ingest smoke: chunked/memmap train parity OK")
PYEOF

# perf-contract gate: collect the deterministic telemetry slice (retraces
# by label, analytic+measured collective bytes, executable FLOPs/temp HBM)
# and diff it against the committed contract.  HARD gate — any drift in a
# hard metric fails the suite; wall times only warn.  Accepted changes are
# committed via  python tools/perf_gate.py --update --justify "<why>".
echo "=== perf-contract gate (tools/perf_gate.py vs tools/perf_contract.json) ==="
python tools/perf_gate.py || rc=$?

# fused grow-step smoke: run the Pallas kernel itself (interpret mode,
# JAX_PLATFORMS=cpu) through a 3-iteration train and require structural
# parity with the XLA oracle.  A fresh process matters: grow_step._INTERPRET
# is read at trace time, so flipping it next to an already-traced config
# would silently reuse the oracle trace.
echo "=== fused grow-step smoke (3-iteration interpret-mode train vs oracle) ==="
python - <<'PYEOF' || rc=$?
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.ops.pallas import grow_step

rng = np.random.default_rng(0)
X = rng.normal(size=(1200, 10)).astype(np.float32)
y = (X[:, 0] + 0.6 * X[:, 1] + 0.1 * rng.normal(size=1200) > 0.2).astype(
    np.float32)
KEEP = ("split_feature=", "threshold=", "decision_type=", "left_child=",
        "right_child=", "num_leaves=")

def structure(**over):
    p = dict(objective="binary", num_leaves=15, learning_rate=0.2,
             hist_mode="seg", min_data_in_leaf=20, verbosity=-1,
             deterministic=True, seed=7)
    p.update(over)
    b = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=3)
    s = b.model_to_string()
    return [l for l in s[s.index("Tree=0"):s.index("end of trees")].splitlines()
            if l.startswith(KEEP)]

ref = structure(grow_fused="off")
grow_step._INTERPRET = True
got = structure(grow_fused="on")
assert got == ref, "fused interpret-mode structure diverged from oracle"
print("fused grow-step interpret smoke: structure parity OK")
PYEOF

# int8 histogram smoke: run the histogram engine's int8-by-default path
# (seg kernels in interpret mode, which also engages the int8 accumulator
# off-TPU) through a 3-iteration train, serial AND leaf_batch=2 fused, and
# require structural parity with the f32 XLA oracle.  Fresh process for
# the same trace-time-flag reason as the fused smoke; the oracle refs are
# computed BEFORE the flags flip.  Exact parity holds on this workload
# because no decisive split sits inside a sub-1e-4 relative-gain tie —
# the engine's contract (zero flips at >=1e-4 gap, near-tie f32 refine
# below) is property-tested in tests/test_split_scan.py; data with a
# decisive deeper tie would exercise the benign-flip regime instead.
echo "=== int8 fused-histogram smoke (3-iteration interpret-mode train vs oracle) ==="
python - <<'PYEOF' || rc=$?
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.ops.pallas import grow_step, seg

rng = np.random.default_rng(0)
X = rng.normal(size=(1200, 10)).astype(np.float32)
y = (X[:, 0] + 0.6 * X[:, 1] + 0.1 * rng.normal(size=1200) > 0.2).astype(
    np.float32)
KEEP = ("split_feature=", "threshold=", "decision_type=", "left_child=",
        "right_child=", "num_leaves=")

def structure(**over):
    p = dict(objective="binary", num_leaves=15, learning_rate=0.2,
             hist_mode="seg", min_data_in_leaf=20, verbosity=-1,
             deterministic=True, seed=7)
    p.update(over)
    b = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=3)
    s = b.model_to_string()
    return [l for l in s[s.index("Tree=0"):s.index("end of trees")].splitlines()
            if l.startswith(KEEP)]

ref = structure(grow_fused="off")
ref_b2 = structure(grow_fused="off", leaf_batch=2)
seg._INTERPRET = True       # seg kernels interpret + int8-default engages
grow_step._INTERPRET = True
got = structure(grow_fused="on")
assert got == ref, "int8 histogram structure diverged from f32 oracle"
got_b2 = structure(grow_fused="on", leaf_batch=2)
assert got_b2 == ref_b2, (
    "int8 batched (K=2) structure diverged from f32 oracle")
print("int8 fused-histogram interpret smoke: structure parity OK")
PYEOF

# kill-and-resume smoke: SIGKILL a checkpointing train mid-run (via the
# chaos harness, the closest stand-in for a TPU-pod preemption), resume
# from the latest checkpoint, and require a byte-identical model dump vs
# the uninterrupted run.  Needs real process death, so it lives here and
# not in pytest.
echo "=== kill-and-resume smoke (SIGKILL at iteration 15, resume to 30) ==="
python - <<'PYEOF' || rc=$?
import subprocess
import sys
import tempfile

ckdir = tempfile.mkdtemp(prefix="lgbm_tpu_ckpt_smoke_")

COMMON = f"""
import numpy as np
import lightgbm_tpu as lgb
rng = np.random.default_rng(0)
X = rng.normal(size=(400, 6))
y = X[:, 0] * 2 + np.sin(X[:, 1]) + 0.1 * rng.normal(size=400)
params = dict(objective="regression", num_leaves=15, learning_rate=0.1,
              min_data_in_leaf=20, verbosity=-1, deterministic=True, seed=7,
              bagging_fraction=0.7, bagging_freq=2, bagging_seed=11,
              checkpoint_dir={ckdir!r}, checkpoint_interval=5)
"""

child = COMMON + """
from lightgbm_tpu.resilience import chaos
chaos.kill_at_iteration(15)
lgb.train(params, lgb.Dataset(X, y, params=params), num_boost_round=30)
raise SystemExit("unreachable: SIGKILL did not fire")
"""
proc = subprocess.run([sys.executable, "-c", child])
assert proc.returncode == -9, f"expected SIGKILL (-9), got {proc.returncode}"

exec(COMMON)
resumed = lgb.train(
    params, lgb.Dataset(X, y, params=params), num_boost_round=30,
    resume_from=ckdir,
)
baseline = lgb.train(
    params, lgb.Dataset(X, y, params=params), num_boost_round=30
)
assert resumed.current_iteration() == 30
assert resumed.model_to_string() == baseline.model_to_string(), (
    "resumed dump diverged from uninterrupted run")
print("kill-and-resume smoke: byte-identical dump after SIGKILL+resume OK")
PYEOF

# launch-scan smoke: device-resident boosting must be invisible in the
# model bytes.  3 launches of N=2 scanned iterations (one compiled
# lax.scan dispatch each) vs 6 serial iterations: byte-identical dump
# (modulo the requested-N config echo) and exactly ONE compile of the
# scan executable across all 3 launches.
echo "=== launch-scan smoke (3 launches x N=2 vs 6 serial iterations) ==="
python - <<'PYEOF' || rc=$?
import re

import numpy as np
import lightgbm_tpu as lgb

rng = np.random.default_rng(0)
X = rng.normal(size=(400, 8))
y = X[:, 0] * 2 + np.sin(X[:, 1]) + 0.1 * rng.normal(size=400)
params = dict(objective="regression", num_leaves=15, learning_rate=0.1,
              min_data_in_leaf=20, verbosity=-1, seed=7,
              bagging_fraction=0.7, bagging_freq=1)

def dump(n):
    p = dict(params, train_steps_per_launch=n)
    b = lgb.train(p, lgb.Dataset(X, y), num_boost_round=6)
    return re.sub(r"\[train_steps_per_launch: [^\]]*\]\n?", "",
                  b.model_to_string())

ref = dump(1)
before = dict(lgb.compile_counts_by_label())
assert dump(2) == ref, "launch-scan dump diverged from serial loop"
after = lgb.compile_counts_by_label()
scan_compiles = after.get("grow/scan2", 0) - before.get("grow/scan2", 0)
assert scan_compiles == 1, (
    f"expected 1 scan compile across 3 launches, saw {scan_compiles}")
print("launch-scan smoke: byte parity + single scan compile OK")
PYEOF

# trace smoke: a 3-iteration train plus one served request (with a caller
# traceparent) must yield a Perfetto-loadable Chrome trace via
# Booster.dump_trace containing the train span tree AND the serve request
# decomposition, with the request joined to the caller's trace id.
echo "=== trace smoke (dump_trace: train + serve spans, traceparent join) ==="
python - <<'PYEOF' || rc=$?
import json
import tempfile

import numpy as np
import lightgbm_tpu as lgb

rng = np.random.default_rng(0)
X = rng.normal(size=(400, 6))
y = X[:, 0] + 0.1 * rng.normal(size=400)
b = lgb.train({"objective": "regression", "num_leaves": 7, "verbosity": -1},
              lgb.Dataset(X, y), 3)
caller = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
server = lgb.serve(b, deadline_ms=2.0, port=-1)
try:
    resp = server.predict_async(X[:5], traceparent=caller).result(timeout=30.0)
    echoed = resp.info.get("traceparent", "")
    assert echoed.split("-")[1] == "ab" * 16, echoed
finally:
    server.stop()
path = tempfile.mktemp(suffix=".json")
b.dump_trace(path)
with open(path) as fp:
    doc = json.load(fp)
names = {e.get("name") for e in doc["traceEvents"]}
for want in ("train/run", "train/iteration", "serve/request",
             "serve/queue_wait", "serve/batch"):
    assert want in names, (want, sorted(names))
req = [e for e in doc["traceEvents"]
       if e.get("name") == "serve/request" and e.get("ph") == "X"]
assert req and req[0]["args"]["trace_id"] == "ab" * 16, req
print(f"trace smoke: {len(doc['traceEvents'])} events, "
      "train+serve spans + traceparent join OK")
PYEOF
exit $rc
