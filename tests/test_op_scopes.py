"""``obs.op_scopes()``: instruction -> ``jax.named_scope`` path, published by
the program for every executable compiled through ``instrumented_jit``.

A profiler trace names a device event by its HLO instruction and carries no
name scope; the compiled artifact's text carries both.  Pinned here: the
parse (scope paths through loops, transforms and inner jits; a fusion
without metadata; names the compiler merged), the coverage of every scope
the grower and the launch scan use, and the requirements of ISSUE 27:
R1 a warm run builds no map, R2 a later process reads the files alone,
R3 retrace counts by label do not move, R4 two executables of one module
name are kept apart and read ``ambiguous`` where they disagree.
"""

import json
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.obs import jit as obs_jit  # noqa: E402
from lightgbm_tpu.obs import op_scope_maps, op_scopes  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PARAMS = {"objective": "binary", "num_leaves": 7, "verbosity": -1}


def _data(n=1500, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    return X, (X[:, 0] + np.sin(X[:, 1]) > 0).astype(float)


@pytest.fixture
def scopes_cache(tmp_path):
    """A compilation cache directory of the test's own whose threshold keeps
    every program, so every traced label writes its map there.  The
    persistent cache itself is off meanwhile: JAX leaves metadata out of its
    key, so an entry compiled from older sources would hand back an
    executable with the older scopes.  The process's settings come back
    afterwards."""
    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    before = {n: getattr(jax.config, n) for n in names}
    jax.config.update(names[0], str(tmp_path / "cache"))
    jax.config.update(names[1], 0.0)
    jax.config.update(names[2], -1)
    jax.config.update(names[3], False)
    jax.clear_caches()  # every label of the test's jobs traces, and compiles, in it
    try:
        yield str(tmp_path / "cache" / "op_scopes")
    finally:
        for n, v in before.items():
            jax.config.update(n, v)


# ------------------------------------------------------------------ the parse
_HLO = textwrap.dedent('''\
    HloModule jit_step, is_scheduled=true

    %fused_computation (p0: f32[8]) -> f32[8] {
      %p0 = f32[8]{0} parameter(0)
      %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/while/body/closed_call/leaf_loop/bookkeeping/mul"}
      ROOT %add.1 = f32[8]{0} add(%mul.1, %p0), metadata={op_name="jit(step)/while/body/closed_call/leaf_loop/bookkeeping/jit(_where)/add"}
    }

    %fused_computation.1 (p0.1: f32[8]) -> f32[8] {
      %p0.1 = f32[8]{0} parameter(0)
      %neg.1 = f32[8]{0} negate(%p0.1), metadata={op_name="jit(step)/while/body/closed_call/leaf_loop/bookkeeping/neg"}
      ROOT %exp.1 = f32[8]{0} exponential(%neg.1), metadata={op_name="jit(step)/while/body/closed_call/leaf_loop/candidate_refresh/vmap(split_scan)/exp"}
    }

    %body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
      %arg = (s32[], f32[8]{0}) parameter(0)
      %gte = f32[8]{0} get-tuple-element(%arg), index=1
      %fusion.7 = f32[8]{0} fusion(%gte), kind=kLoop, calls=%fused_computation
      %fusion.8 = f32[8]{0} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.1
      %copy.3 = f32[8]{0} copy(%fusion.8)
      %sort.2 = f32[8]{0} sort(%copy.3), dimensions={0}, metadata={op_name="jit(step)/while/body/closed_call/LaunchRunner._launch_impl.<locals>.step/leaf_ids/jit(argsort)/sort"}
      %merged.4 = f32[8]{0} add(%sort.2, %copy.3), metadata={op_name="jit(step)/root_histogram/broadcast_in_dim;jit(step)/root_histogram/jit(_pad)/pad"}
      ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte, %merged.4)
    }

    ENTRY %main.5 (x: f32[8]) -> f32[8] {
      %x = f32[8]{0} parameter(0)
      %constant.9 = f32[] constant(0), metadata={op_name="jit(step)/split_scan"}
      %alone.1 = f32[8]{0} broadcast(%constant.9), dimensions={}, metadata={op_name="jit(step)/split_scan"}
      ROOT %while.1 = (s32[], f32[8]{0}) while(%x), condition=%cond, body=%body, metadata={op_name="jit(step)/leaf_loop/while"}
    }
    ''')


def test_scope_paths_and_a_fusion_without_metadata():
    module, scopes = obs_jit.parse_op_scopes(_HLO)
    assert module == "jit_step"
    # loops, call wrappers, inner jits, the body function's name: all dropped
    assert scopes["sort.2"] == "leaf_ids"
    # a fusion without metadata takes the deepest common scope of what it calls
    assert scopes["fusion.7"] == "leaf_loop/bookkeeping"
    assert scopes["fusion.8"] == "leaf_loop"
    # no metadata and nothing called: no scope, never a guess
    assert scopes["copy.3"] == ""
    # names the compiler merged keep their common leading part
    assert scopes["merged.4"] == "root_histogram"
    # a compiler-made instruction can carry its scope alone, with no primitive
    assert scopes["alone.1"] == "split_scan"
    assert scopes["while.1"] == "leaf_loop"
    # what never runs as an event of its own is not in the map
    assert not {"x", "arg", "gte", "tuple.1", "constant.9", "mul.1", "exp.1"} & set(scopes)
    assert obs_jit.scope_path("jit(f)/vmap(jit(g))/transpose(jvp(bookkeeping))/mul") == "bookkeeping"


# ------------------------------------------------------------------- coverage
def test_every_scope_of_the_grower_and_the_launch_scan_is_published(scopes_cache,
                                                                     monkeypatch):
    """Each ``jax.named_scope`` name in ops/grower.py and boosting/launch.py
    shows up in ``op_scopes()`` of tiny CPU trains of the job shapes that
    reach it."""
    X, y = _data()
    for extra in (
        {},
        {"hist_mode": "seg"},
        {"hist_mode": "seg", "grow_fused": "off"},
        {"train_steps_per_launch": 2, "bagging_fraction": 0.6, "bagging_freq": 1},
        # a sampling booster on the segment path: the in-bag window's scopes
        # (bag_compact, oob_score)
        {"hist_mode": "seg", "train_steps_per_launch": 2, "bagging_fraction": 0.6,
         "bagging_freq": 1},
    ):
        lgb.train(dict(_PARAMS, **extra), lgb.Dataset(X, y), 2)
    # a packed row of two plane groups: the go-left pass ahead of the
    # partition KERNEL (interpret mode: XLA's own partition would fuse the
    # pass into its sort keys, and a fusion has one scope)
    from lightgbm_tpu.ops.pallas import partition

    monkeypatch.setattr(partition, "_INTERPRET", True)
    Xw, yw = _data(n=600, f=243)
    lgb.train(dict(_PARAMS, hist_mode="seg", num_leaves=4), lgb.Dataset(Xw, yw), 1)
    # map by map: ``op_scopes()`` merges the maps of one module name and calls
    # an instruction two of them scope differently ambiguous, and a grow
    # program an earlier test of this process traced is such a second map
    published = set()
    for doc in obs_jit.op_scope_maps():
        for path in doc["scopes"].values():
            published.update(path.split("/"))
    used = set()
    for rel in ("lightgbm_tpu/ops/grower.py", "lightgbm_tpu/boosting/launch.py"):
        with open(os.path.join(ROOT, rel), encoding="utf-8") as fh:
            used.update(re.findall(r'named_scope\("([^"]+)"\)', fh.read()))
    assert {"split_scan", "bookkeeping", "candidate_refresh", "leaf_loop",
            "gradients", "sample", "score_update"} <= used
    # the double-buffered histogram scopes exist only under a mesh with
    # overlap_collectives and leaf_batch > 1 (tests/test_parallel.py's job)
    assert used - published <= {"histogram_db0", "histogram_db1"}
    assert "pack_tree" in set(op_scopes()["jit__pack_tree_arrays_impl"].values())


# ------------------------------------------------------- R1, R3: the capture
def test_warm_run_builds_no_map_and_retraces_do_not_move(scopes_cache, monkeypatch):
    X, y = _data(n=1700, seed=1)  # a shape no other test of this file traces
    built = []
    build = obs_jit._build_map
    monkeypatch.setattr(obs_jit, "_build_map",
                        lambda *a: built.append(a[1]) or build(*a))
    before = lgb.compile_counts_by_label()
    lgb.train(_PARAMS, lgb.Dataset(X, y), 2)
    cold = lgb.compile_counts_by_label()
    files = sorted(os.listdir(scopes_cache))
    assert built and any(f.startswith("jit_grow_tree-") for f in files)
    assert len(files) == len(set(built))  # one file per traced executable
    for f in files:
        with open(os.path.join(scopes_cache, f)) as fh:
            doc = json.load(fh)
        assert doc["schema"] == obs_jit.SCOPES_SCHEMA and doc["scopes"]
        assert f == f"{doc['module']}-{doc['signature']}.json"
    # R3: the capture's second lower/compile is no retrace of any label
    grew = {k: cold[k] - before.get(k, 0) for k in cold if cold[k] != before.get(k, 0)}
    assert grew.get("grow_tree") == 1, grew

    # the same job again, every in-memory executable gone: every label
    # traces again, and pays one existence check
    del built[:]
    jax.clear_caches()
    lgb.train(_PARAMS, lgb.Dataset(X, y), 2)
    warm = lgb.compile_counts_by_label()
    assert warm["grow_tree"] == cold["grow_tree"] + 1  # it did trace
    assert built == []  # R1
    assert sorted(os.listdir(scopes_cache)) == files


# ------------------------------------------------------------ R4: ambiguous
def test_two_executables_of_one_module_name(scopes_cache):
    os.makedirs(scopes_cache)
    common = {"schema": obs_jit.SCOPES_SCHEMA, "module": "jit_f", "label": "f",
              "code": obs_jit._code_fingerprint(), "capture_s": 0.0}
    for sig, scopes in (("a" * 16, {"fusion.1": "split_scan", "copy.2": "partition"}),
                        ("b" * 16, {"fusion.1": "bookkeeping", "copy.2": "partition",
                                    "sort.3": "leaf_ids"})):
        with open(os.path.join(scopes_cache, f"jit_f-{sig}.json"), "w") as fh:
            json.dump(dict(common, signature=sig, scopes=scopes), fh)
    # another code's map of the same module is not this program's
    with open(os.path.join(scopes_cache, "jit_f-" + "c" * 16 + ".json"), "w") as fh:
        json.dump(dict(common, code="0" * 16, signature="c" * 16,
                       scopes={"copy.2": "histogram"}), fh)
    assert op_scopes()["jit_f"] == {
        "fusion.1": obs_jit.AMBIGUOUS, "copy.2": "partition", "sort.3": "leaf_ids"}
    kept = [d for d in op_scope_maps() if d["module"] == "jit_f"]
    assert sorted(d["signature"] for d in kept) == ["a" * 16, "b" * 16]


def test_launch_scans_of_one_shape_and_another_configuration_do_not_share_a_map():
    """The scan's body closes over the booster's configuration, which its
    arguments do not show: a quantized and a plain booster on tables of one
    shape used to share a key, and the first to compile wrote the map both
    read (on the chip, PR 34: no scope ``quantize`` in the quantized cell's
    trace after the plain cell had run)."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(2000, 6)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    base = {"objective": "binary", "verbosity": -1, "num_leaves": 7,
            "train_steps_per_launch": 4}
    quant = {"use_quantized_grad": True, "num_grad_quant_bins": 4,
             "quant_train_renew_leaf": True, "seed": 3}
    before = set(obs_jit._traced)
    lgb.train(base, lgb.Dataset(X, y), 4)
    lgb.train({**base, **quant}, lgb.Dataset(X, y), 4)
    new = [k for k in set(obs_jit._traced) - before if k[0] == "jit__launch_impl"]
    assert len(new) == 2 and new[0][1] != new[1][1]


# ------------------------------------------------- R2: a later process reads
_WRITER = """
import numpy as np, jax
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
import lightgbm_tpu as lgb
rng = np.random.default_rng(0)
X = rng.normal(size=(1500, 6)); y = (X[:, 0] > 0).astype(float)
lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1}, lgb.Dataset(X, y), 2)
"""
_READER = """
import jax, jax.stages
def refuse(*a, **k):
    raise AssertionError("op_scopes() compiled")
jax.stages.Lowered.compile = refuse
from lightgbm_tpu.obs import op_scopes, compile_count
scopes = op_scopes()
assert compile_count() == 0
paths = set(scopes["jit_grow_tree"].values())
assert any("split_scan" in p for p in paths) and any("bookkeeping" in p for p in paths), paths
print("READ", len(scopes["jit_grow_tree"]))
"""


def test_a_later_process_reads_the_map_without_compiling(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    for script in (_WRITER, _READER):
        out = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
    assert "READ" in out.stdout


# ---------------------------------------- the compilation timeline (PR 37)
from lightgbm_tpu.obs import get_flight, get_session, get_tracer  # noqa: E402

_STAGES = ("trace_s", "lower_s", "backend_compile_s", "cache_retrieval_s")


def _compile_spans(label=None):
    return [s for s in get_tracer().spans() if s["cat"] == "compile"
            and (label is None or s["name"] == "compile/" + label)]


@pytest.fixture
def fresh_ring():
    tracer = get_tracer()
    tracer.configure(active=True)
    tracer.reset()
    yield tracer
    tracer.configure(active=True)


@pytest.fixture
def own_cache(tmp_path):
    """A persistent compilation cache of the test's own that keeps every
    program; the process's cache and settings come back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    before = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (str(tmp_path / "cache"), 0.0, -1, True)):
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    try:
        yield str(tmp_path / "cache")
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


def test_one_compile_span_a_traced_call_and_none_on_a_warm_one(fresh_ring):
    f = obs_jit.instrumented_jit(lambda x: (x * 3.0).sum(), label="pr37/f")
    x = jax.numpy.arange(64.0)
    f(x)
    (span,) = _compile_spans("pr37/f")
    a = span["args"]
    assert a["label"] == "pr37/f" and a["module"].startswith("jit_")
    assert all(a[k] >= 0 for k in _STAGES)
    assert a["trace_s"] > 0 and a["lower_s"] > 0 and a["backend_compile_s"] > 0
    # the retrieval happens inside the backend's compile-or-get-cached
    assert a["cache_retrieval_s"] <= a["backend_compile_s"]
    assert a["trace_s"] + a["lower_s"] + a["backend_compile_s"] <= a["call_s"]
    assert span["dur"] == pytest.approx(a["call_s"] * 1e6, abs=2)
    f(x)
    f(x + 1)
    assert len(_compile_spans("pr37/f")) == 1  # a warm call builds nothing
    f(jax.numpy.arange(32.0))  # another shape traces again
    assert len(_compile_spans("pr37/f")) == 2


def test_an_inner_instrumented_call_is_part_of_the_outer_span(fresh_ring):
    inner = obs_jit.instrumented_jit(lambda x: x * 2.0, label="pr37/inner")
    outer = obs_jit.instrumented_jit(lambda x: inner(x).sum() + inner(x + 1).sum(),
                                     label="pr37/outer")
    outer(jax.numpy.arange(16.0))
    assert not _compile_spans("pr37/inner")
    (span,) = _compile_spans("pr37/outer")
    # the inner traces lie inside the outer one: counted once
    assert span["args"]["trace_s"] <= span["args"]["call_s"]


def test_cache_hit_is_false_on_a_miss_true_on_a_hit_none_unasked(fresh_ring, own_cache):
    def g(x):
        return (x * 5.0 + 1.0).sum()

    x = jax.numpy.arange(48.0)
    obs_jit.instrumented_jit(g, label="pr37/miss")(x)
    (miss,) = _compile_spans("pr37/miss")
    assert miss["args"]["cache_hit"] is False and miss["args"]["cache_retrieval_s"] == 0
    jax.clear_caches()  # the executable is gone from memory, not from the directory
    obs_jit.instrumented_jit(g, label="pr37/hit")(x)
    (hit,) = _compile_spans("pr37/hit")
    assert hit["args"]["cache_hit"] is True
    assert 0 < hit["args"]["cache_retrieval_s"] <= hit["args"]["backend_compile_s"]
    # a program the cache does not keep: neither a hit nor a miss
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 3600.0)
    obs_jit.instrumented_jit(lambda x: (x - 7.0).sum(), label="pr37/unasked")(x)
    (unasked,) = _compile_spans("pr37/unasked")
    assert unasked["args"]["cache_hit"] is None


def test_the_second_compile_for_op_scopes_has_a_span_of_its_own(fresh_ring, scopes_cache):
    f = obs_jit.instrumented_jit(lambda x: (x * 11.0).sum(), label="pr37/scoped")
    f(jax.numpy.arange(24.0))
    assert os.listdir(scopes_cache)  # the map was written: a second lower().compile()
    (own,) = _compile_spans("pr37/scoped")
    (second,) = [s for s in _compile_spans("op_scopes") if s["args"]["of"] == "pr37/scoped"]
    assert second["ts"] >= own["ts"] + own["dur"]  # after the call, never inside its numbers
    assert second["args"]["call_s"] > 0  # jax answers it from memory where it can
    assert obs_jit.compile_counts_by_label()["pr37/scoped"] == 1


def test_a_compilation_outside_any_instrumented_call_and_the_sum(fresh_ring):
    heard = []

    def listen(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            heard.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        x = jax.numpy.arange(40.0)
        jax.numpy.tanh(x * 13.0).block_until_ready()  # eager: nobody's label
        obs_jit.instrumented_jit(lambda v: (v / 17.0).sum(), label="pr37/sum")(x)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    spans = _compile_spans()
    loose = [s for s in spans if s["name"] == "compile/uninstrumented"]
    assert loose and all(s["args"]["module"] for s in loose)
    # what the harness's CompileClock hears is the sum over the spans
    assert sum(s["args"]["backend_compile_s"] for s in spans) == pytest.approx(
        sum(heard), rel=1e-6)


def test_compile_events_counters_and_trace_spans_off(fresh_ring):
    ses = get_session()
    ses.reset()
    ses.configure(enabled=True)
    flight = get_flight()
    try:
        obs_jit.instrumented_jit(lambda x: (x * 19.0).sum(), label="pr37/on")(
            jax.numpy.arange(8.0))
        assert ses.counters["compile/traces"] >= 1
        events = [e for e in flight.events() if e.get("event") == "compile"]
        assert events[-1]["label"] == "pr37/on" and events[-1]["seconds"] > 0
        assert "hit" in events[-1]
        fresh_ring.configure(active=False)
        obs_jit.instrumented_jit(lambda x: (x * 23.0).sum(), label="pr37/off")(
            jax.numpy.arange(8.0))
        assert not _compile_spans("pr37/off")  # the ring takes nothing ...
        assert [e for e in flight.events() if e.get("label") == "pr37/off"]  # ... the flight ring does
    finally:
        ses.configure(enabled=False)
        ses.reset()


def test_baked_bytes_of_a_launch_scan_are_its_labels_and_weights(fresh_ring):
    X, y = _data(n=1200, f=5)
    w = np.linspace(0.5, 1.5, len(y))
    params = {"objective": "regression", "num_leaves": 7, "verbosity": -1,
              "train_steps_per_launch": 2}
    b = lgb.train(params, lgb.Dataset(X, y, weight=w), 4)
    (span,) = _compile_spans("grow/scan2")
    labels_and_weights = b.objective.label.nbytes + b.objective.weight.nbytes
    assert labels_and_weights == 2 * 4 * len(y)
    # beside them only the two per-feature tables the grower reads off the booster
    tables = b._num_bins.nbytes + b._nan_bins.nbytes
    assert span["args"]["baked_bytes"] == labels_and_weights + tables
    assert span["args"]["trace_s"] > 0
    launches = [s for s in get_tracer().spans() if s["name"] == "train/launch"]
    assert [s["args"].get("first", False) for s in launches] == [True, False]
    assert span["parent_id"] in {s["span_id"] for s in get_tracer().spans()
                                 if s["name"] in ("train/launch", "train/launch_dispatch")}
