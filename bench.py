"""Benchmark: boosting iterations/sec on a Higgs-like workload, single chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "platform",
"device_kind", "n_devices", ...}.  The headline needs a TPU: without one it
exits non-zero and prints no number (the ``--*-sweep`` modes are CPU-pinned
counting harnesses, not device measurements).
Baseline: the reference CPU trains Higgs-10.5M x 28 at ~3.8 iters/sec
(500 iters in 130.094 s, 255 leaves, 16 threads — docs/Experiments.rst:108,
see BASELINE.md).  This benchmark runs the same shape of work (binary
objective, 255 leaves, max_bin 255, 28 features) on however many rows fit a
single chip comfortably, and reports iterations/sec; vs_baseline is the ratio
against 3.8 iters/s.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _make_data(n_rows: int, n_features: int):
    rng = np.random.default_rng(42)
    X = rng.normal(size=(n_rows, n_features)).astype(np.float32)
    w = rng.normal(size=n_features)
    logits = X @ w * 0.5 + rng.normal(scale=1.0, size=n_rows)
    y = (logits > 0).astype(np.float64)
    return X, y


_PARAMS = {
    "objective": "binary",
    "num_leaves": 255,
    "max_bin": 255,
    "learning_rate": 0.1,
    "min_data_in_leaf": 100,
    "verbosity": -1,
    "metric": "none",
    # best-known training config at this shape: K=4 frontier batching was
    # the round-8 sweep peak (+8% over serial); the commit-rate clamp
    # (leaf_batch_adaptive, default on) protects the tail where batching
    # over-speculates, and grow_fused='auto' rides the fused grow step on
    # the seg fast path (identical XLA composition off TPU)
    "leaf_batch": 4,
}


def _train_bench(X, y, timed_iters: int, warmup_iters: int = 2, params=None):
    """(iters/sec, booster, compile stats) for the Higgs-shaped workload."""
    import jax

    import lightgbm_tpu as lgb

    params = params or _PARAMS
    dtrain = lgb.Dataset(X, y, params=params)
    booster = lgb.Booster(params, dtrain)
    c0 = lgb.compile_count()
    for _ in range(warmup_iters):
        booster.update()
    jax.block_until_ready(booster._score)
    c_warm = lgb.compile_count()
    t0 = time.perf_counter()
    for _ in range(timed_iters):
        booster.update()
    jax.block_until_ready(booster._score)
    ips = timed_iters / (time.perf_counter() - t0)
    if booster.degraded:
        # a run-time kernel failure latched a fallback path: whatever was
        # timed is not the program this benchmark names
        raise RuntimeError(
            "training degraded to a fallback path during the benchmark "
            "(see the '[resilience]' warning above); refusing to report it"
        )
    stats = {
        "compiles_warmup": c_warm - c0,
        "recompiles_timed": lgb.compile_count() - c_warm,
    }
    return ips, booster, stats


def _time_op(fn, *args, reps: int = 3):
    """Seconds for one jitted call (min over reps, after a compile run)."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def _train_phases(X, y, iters_per_sec):
    """Per-tree training-phase breakdown from the telemetry event stream.

    A short instrumented re-fit with ``telemetry`` + ``obs_sync_timing``
    (each phase blocks on its device values, so phase walls measure device
    time rather than dispatch time) yields per-iteration phase timings; the
    headline run stays uninstrumented."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.registry import get_session

    m = min(len(y), 1_000_000)  # bound the instrumented re-fit's cost
    ses = get_session()
    ses.reset()
    params = {**_PARAMS, "telemetry": True, "obs_sync_timing": True}
    dtrain = lgb.Dataset(X[:m], y[:m], params=params)
    booster = lgb.Booster(params, dtrain)
    try:
        for _ in range(5):
            booster.update()
        events = [
            e for e in booster.telemetry()["events"]
            if e.get("event") == "iteration"
        ]
    finally:
        ses.configure(enabled=False)
        ses.reset()
    # steady state only: iterations that retraced measure compile, not run
    steady = [e for e in events if e.get("compiles_delta", 0) == 0] or events
    n = max(1, len(steady))
    phases = {}
    for e in steady:
        for k, v in e["phases"].items():
            phases[k] = phases.get(k, 0.0) + v
    out = {f"{k}_ms": round(v / n, 1) for k, v in sorted(phases.items())}
    out["tree_ms"] = round(1000.0 / iters_per_sec, 1)
    out["wall_ms"] = round(sum(e["wall_ms"] for e in steady) / n, 1)
    trees = sum(e.get("trees_materialized", 0) for e in steady)
    out["splits_per_tree"] = round(
        sum(e.get("splits", 0) for e in steady) / max(1, trees), 1
    )
    out["recompiles_after_warmup"] = sum(
        e.get("compiles_delta", 0) for e in events[2:]
    )
    out["rows"] = m
    out["note"] = (
        "telemetry event stream, obs_sync_timing on (phase walls include "
        "device time); wall_ms is the instrumented re-fit, tree_ms the "
        "headline run"
    )
    try:
        out["grow_decomposition"] = _grow_decomposition(
            booster, len(y), m, out["tree_ms"]
        )
    except Exception as e:
        out["grow_decomposition"] = {"error": repr(e)}
    try:
        out["hist_engine_sweep"] = _hist_engine_sweep(booster, m)
    except Exception as e:
        out["hist_engine_sweep"] = {"error": repr(e)}
    return out


def _grow_decomposition(booster, n_rows: int, m: int, tree_ms: float):
    """Round-8-style primitive-throughput decomposition, emitted by the
    bench itself so bookkeeping_ms stays comparable round over round.

    partition / histogram cost per steady-state tree is measured as jitted
    per-ROW throughput of proxies for the path the bench ACTUALLY ran
    (ordered mode on CPU: windowed gather -> compare -> stable sort ->
    write-back for partition, gather + segment-sum ``leaf_histogram`` for
    the smaller child) — one call at the full-data window divided by rows,
    scaled by the trained trees' actual partitioned/histogrammed row
    totals.  Timing the seg-path primitives here instead would compare a
    different (and on CPU far costlier, full-array-sort) lowering against
    the ordered headline and drive the remainder negative.
    ``bookkeeping_ms`` is the remainder of the headline tree time
    (dispatch, fusion boundaries, state writes, score updates) — the fixed
    share that ``leaf_batch`` amortizes and the fused grow step collapses.
    Separately, the fused grow step is timed against the two-launch
    seg partition+histogram pair it replaces, at the average window
    (identical XLA composition off TPU; one kernel launch on it)."""
    import functools

    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops.grower import _candidate_for_leaf
    from lightgbm_tpu.ops.pallas.grow_step import fused_grow_step
    from lightgbm_tpu.ops.pallas.seg import pack_rows, padded_rows, seg_hist
    from lightgbm_tpu.ops.segpart import sort_partition

    trees = [t for t in booster.models_ if t.num_leaves > 1]
    if not trees:
        return {"error": "no grown trees"}
    s_calls = part_rows = hist_rows = 0
    for t in trees:
        ic = np.asarray(t.internal_count, dtype=np.int64)
        lc = np.asarray(t.leaf_count, dtype=np.int64)

        def _cnt(ch):
            return int(ic[ch]) if ch >= 0 else int(lc[-ch - 1])

        s_calls += len(ic)
        part_rows += int(ic.sum())
        hist_rows += sum(
            min(_cnt(int(t.left_child[i])), _cnt(int(t.right_child[i])))
            for i in range(len(ic))
        )
    s_per_tree = s_calls / len(trees)
    scale = n_rows / float(m)  # headline rows vs instrumented re-fit rows
    avg_part = max(1, part_rows // s_calls)
    avg_hist = max(1, hist_rows // s_calls)

    gp = booster._grower_params
    B = int(gp.max_bin)
    wide = B > 256
    bins = booster._bins
    f_used = int(bins.shape[1])
    g = jnp.full((m,), 0.5, jnp.float32)
    h = jnp.ones((m,), jnp.float32)
    msk = jnp.ones((m,), jnp.float32)
    n_pad = padded_rows(m)
    seg = pack_rows(bins, g, h, msk, n_pad, wide=wide)
    cmv = jnp.zeros((256,), jnp.float32)
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)

    part_fn = jax.jit(
        functools.partial(sort_partition, f=f_used, n_pad=n_pad, wide=wide)
    )
    hist_fn = jax.jit(
        functools.partial(
            seg_hist, f=f_used, num_bins=B, n_pad=n_pad, wide=wide
        )
    )
    fused_fn = jax.jit(
        functools.partial(
            fused_grow_step, f=f_used, num_bins=B, n_pad=n_pad, wide=wide
        )
    )
    hist_r = jax.random.uniform(jax.random.PRNGKey(0), (f_used, B, 3))
    fm = jnp.ones((f_used,), bool)

    def scan_fn(hh):
        return _candidate_for_leaf(
            hh, jnp.float32(1.0), jnp.float32(2.0), jnp.float32(m),
            booster._num_bins, booster._nan_bins, fm, gp,
        )

    # ---- benched-path proxies (ordered mode off-TPU): one full-window
    # call each, per-row scaled by the trees' measured row totals
    from lightgbm_tpu.ops.histogram import leaf_histogram

    bins_i32 = bins.astype(jnp.int32)
    bins_pad2 = jnp.concatenate(
        [bins_i32, jnp.zeros((1, f_used), jnp.int32)], axis=0
    )
    g_pad = jnp.concatenate([g, jnp.zeros((1,), jnp.float32)])
    h_pad = jnp.concatenate([h, jnp.zeros((1,), jnp.float32)])
    m_pad = jnp.concatenate([msk, jnp.zeros((1,), jnp.float32)])
    order0 = jnp.arange(m + 1, dtype=jnp.int32)
    featrow = bins_pad2[:, 0]

    @jax.jit
    def part_proxy(order, begin, cnt, featrow, tbin):
        idx = jax.lax.dynamic_slice(order, (begin,), (m,))
        valid = jnp.arange(m, dtype=jnp.int32) < cnt
        gl = (featrow[idx] <= tbin) & valid
        perm = jnp.argsort(jnp.where(gl, 0, 1).astype(jnp.int32), stable=True)
        order = jax.lax.dynamic_update_slice(order, idx[perm], (begin,))
        return order, jnp.sum(gl)

    @jax.jit
    def hist_proxy(order):
        idx = jax.lax.dynamic_slice(order, (0,), (m,))
        return leaf_histogram(
            bins_pad2[idx], g_pad[idx], h_pad[idx], m_pad[idx], B,
            method="auto", axis_name=None,
        )

    t_part_full = _time_op(part_proxy, order0, i32(0), i32(m), featrow,
                           i32(B // 2))
    t_hist_full = _time_op(hist_proxy, order0)
    t_scan = _time_op(jax.jit(scan_fn), hist_r)
    # seg-path per-call comparison at the average partition window: the
    # fused step vs the two launches it replaces (plus the election the
    # pair performs outside the kernels)
    t_part = _time_op(
        part_fn, seg, i32(0), i32(avg_part), i32(0), i32(B // 2), i32(1),
        i32(-1), i32(0), cmv,
    )
    t_hist = _time_op(hist_fn, seg, i32([0, avg_hist]))
    t_fused = _time_op(
        fused_fn, seg, i32([0]), i32([avg_part]), i32([0]), i32([B // 2]),
        i32([1]), i32([-1]), i32([0]), cmv[None],
    )

    n_trees = len(trees)
    partition_ms = (part_rows / n_trees) * (t_part_full / m) * scale * 1e3
    histogram_ms = (hist_rows / n_trees) * (t_hist_full / m) * scale * 1e3
    split_scan_ms = 2 * s_per_tree * t_scan * 1e3
    bookkeeping_ms = tree_ms - partition_ms - histogram_ms - split_scan_ms
    return {
        "partition_ms": round(partition_ms, 1),
        "histogram_ms": round(histogram_ms, 1),
        "split_scan_ms": round(split_scan_ms, 1),
        "bookkeeping_ms": round(bookkeeping_ms, 1),
        "bookkeeping_share": round(bookkeeping_ms / max(tree_ms, 1e-9), 3),
        "splits_per_tree": round(s_per_tree, 1),
        # per-call comparison at the average partition window: the fused
        # step vs the two launches it replaces
        "two_launch_call_ms": round((t_part + t_hist) * 1e3, 2),
        "fused_step_call_ms": round(t_fused * 1e3, 2),
        "grow_fused": bool(gp.grow_fused),
        "leaf_batch_effective": int(gp.leaf_batch),
    }


def _hist_engine_sweep(booster, m: int):
    """Histogram-engine v2 sweep: per-call seg-histogram cost per engine
    variant, scaled to a per-tree ``histogram_ms`` figure comparable to
    ``train_phases``.

    Variants: ``bf16_full_pass`` (the pre-v2 engine: one masked pass over
    the whole padded array — also what the bf16 kernel's launch pattern
    amortizes on TPU), ``default`` (the shipped engine: int8-by-default
    repacked kernel on TPU, capacity-bucketed windowed pass on CPU),
    ``int8`` (quantized accumulation explicitly on), and live-plane skip
    at ``feature_fraction`` 1.0 vs 0.5.  On CPU the reference ignores the
    ``live`` mask, so the 0.5 leg repacks only the live plane groups'
    features — cost is per-plane, so this is the honest stand-in for the
    kernel's zero-trip dead groups.  Asserts the v2 engine is >=2x the
    full pass (when windowing engages) and that ff=0.5 is measurably
    cheaper than ff=1.0."""
    import functools

    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops.pallas.seg import (
        _CPU_WINDOW_ROWS, hist_bpad, hist_group, hist_ngroups, pack_rows,
        padded_rows, seg_hist, seg_hist_ref,
    )
    from lightgbm_tpu.ops.quantize import hist_acc_scales

    trees = [t for t in booster.models_ if t.num_leaves > 1]
    if not trees:
        return {"error": "no grown trees"}
    s_calls = hist_rows = 0
    for t in trees:
        ic = np.asarray(t.internal_count, dtype=np.int64)
        lc = np.asarray(t.leaf_count, dtype=np.int64)

        def _cnt(ch):
            return int(ic[ch]) if ch >= 0 else int(lc[-ch - 1])

        s_calls += len(ic)
        hist_rows += sum(
            min(_cnt(int(t.left_child[i])), _cnt(int(t.right_child[i])))
            for i in range(len(ic))
        )
    s_per_tree = s_calls / len(trees)
    avg_hist = max(1, hist_rows // s_calls)

    gp = booster._grower_params
    B = int(gp.max_bin)
    wide = B > 256
    bins = booster._bins
    f_used = int(bins.shape[1])
    g = jnp.full((m,), 0.5, jnp.float32)
    h = jnp.ones((m,), jnp.float32)
    msk = jnp.ones((m,), jnp.float32)
    n_pad = padded_rows(m)
    seg = pack_rows(bins, g, h, msk, n_pad, wide=wide)
    scal = jnp.asarray([0, avg_hist], jnp.int32)
    qs = hist_acc_scales(g, h, msk)

    def mk(f=f_used, **kw):
        return jax.jit(functools.partial(
            seg_hist, f=f, num_bins=B, n_pad=n_pad, wide=wide, **kw
        ))

    full_fn = jax.jit(functools.partial(
        seg_hist_ref, f=f_used, num_bins=B, n_pad=n_pad, wide=wide
    ))
    bpad = hist_bpad(B)
    gb = hist_group(f_used, bpad)
    ng = hist_ngroups(f_used, bpad)
    live_groups = max(1, (ng + 1) // 2)  # ff=0.5 tree mask, group granular
    on_tpu = jax.default_backend() == "tpu"

    t_full = _time_op(full_fn, seg, scal)
    t_def = _time_op(mk(), seg, scal)
    t_int8 = _time_op(mk(quant_scales=qs), seg, scal)
    if on_tpu:
        t_ff10 = _time_op(
            mk(live=jnp.ones((ng,), jnp.int32)), seg, scal
        )
        live_half = (jnp.arange(ng) < live_groups).astype(jnp.int32)
        t_ff05 = _time_op(mk(live=live_half), seg, scal)
        ff_note = "live mask zero-trips dead plane groups in-kernel"
    else:
        f_half = min(f_used, live_groups * gb)
        seg_half = pack_rows(bins[:, :f_half], g, h, msk, n_pad, wide=wide)
        t_ff10 = t_def
        t_ff05 = _time_op(mk(f=f_half), seg_half, scal)
        ff_note = (
            "cpu proxy: repacked to the live plane groups' features only "
            "(kernel cost is per-plane; CPU reference ignores `live`)"
        )

    def h_ms(t):
        return round(s_per_tree * t * 1e3, 1)

    out = {
        "rows": m,
        "avg_hist_window": avg_hist,
        "plane_groups": ng,
        "live_groups_at_ff_0.5": live_groups,
        "per_call_ms": {
            "bf16_full_pass": round(t_full * 1e3, 3),
            "default": round(t_def * 1e3, 3),
            "int8": round(t_int8 * 1e3, 3),
            "ff_1.0": round(t_ff10 * 1e3, 3),
            "ff_0.5": round(t_ff05 * 1e3, 3),
        },
        "histogram_ms": {
            "bf16_full_pass": h_ms(t_full),
            "default": h_ms(t_def),
            "int8": h_ms(t_int8),
            "ff_1.0": h_ms(t_ff10),
            "ff_0.5": h_ms(t_ff05),
        },
        "speedup_vs_full_pass": round(t_full / t_def, 2),
        "ff_0.5_vs_1.0": round(t_ff05 / t_ff10, 3),
        "ff_note": ff_note,
    }
    # acceptance: the v2 engine cuts per-call histogram cost >=2x against
    # the pre-v2 full pass whenever its lever is engaged (windowing on
    # CPU above the threshold; int8+repack kernel on TPU), and ff=0.5
    # histogram cost lands measurably below ff=1.0
    if on_tpu or n_pad > _CPU_WINDOW_ROWS:
        assert t_full / t_def >= 2.0, (t_full, t_def)
    assert t_ff05 < t_ff10, (t_ff05, t_ff10)
    return out


def _leaf_batch_sweep(X, y, timed_iters: int):
    """iters/sec per leaf_batch K — the frontier-batched grower's headline:
    K splits per compiled step amortize the fixed per-split program cost."""
    ks = [
        int(k)
        for k in os.environ.get("BENCH_LEAF_BATCH_SWEEP", "1,2,4,8").split(",")
        if k.strip()
    ]
    out = {}
    for k in ks:
        ips, _b, _st = _train_bench(
            X, y, timed_iters, warmup_iters=1,
            params={**_PARAMS, "leaf_batch": k},
        )
        out[str(k)] = round(ips, 4)
    return out


def mesh_layout_sweep() -> dict:
    """Named-mesh layout sweep on the 8-virtual-CPU-device mesh.

    For each layout spec (data (8,1), feature, hybrid (4,2) — all through
    the single ``parallel/mesh.py`` grow path) train a fixed workload and
    record iters/sec plus the analytic-vs-measured collective byte totals;
    for the data layout additionally compare ``overlap_collectives`` on vs
    off (double-buffered histogram psums).  Runs standalone via
    ``python bench.py --mesh-sweep`` (the device-count flag must be set
    before the backend initializes, so this is its own process).
    """
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.registry import get_session

    # layout COMPARISON shape, not the headline: small enough that five
    # cases (incl. 255-leaf compiles) fit a CPU-fallback bench budget
    n_rows = int(os.environ.get("BENCH_MESH_ROWS", 64_000))
    n_features = 28
    timed_iters = int(os.environ.get("BENCH_MESH_ITERS", 5))
    X, y = _make_data(n_rows, n_features)
    ses = get_session()

    cases = {
        "serial": {},
        # pin overlap off/on explicitly — "auto" engages at leaf_batch>1,
        # which would make the pair measure the same program
        "data": {"tree_learner": "data", "overlap_collectives": "off"},
        "data_overlap": {"tree_learner": "data", "overlap_collectives": "on"},
        "feature": {"tree_learner": "feature"},
        "hybrid": {"tree_learner": "data", "mesh_layout": "hybrid"},
    }
    out = {}
    for name, extra in cases.items():
        ses.configure(enabled=False)
        ses.reset()
        params = dict(
            _PARAMS,
            num_leaves=int(os.environ.get("BENCH_MESH_LEAVES", 63)),
            telemetry=True,
            **extra,
        )
        ips, booster, stats = _train_bench(
            X, y, timed_iters, params=params
        )
        rec = {
            "iters_per_sec": round(ips, 4),
            "recompiles_timed": stats["recompiles_timed"],
        }
        spec = getattr(booster, "_mesh_spec", None)
        if spec is not None:
            rec["mesh"] = {"data": spec.data, "feature": spec.feature}
            tel = booster.telemetry()
            iters = [
                e for e in tel["events"] if e["event"] == "iteration"
            ]
            analytic = sum(
                e["collective"]["psum_bytes"]
                for e in iters if "collective" in e
            )
            measured = sum(
                e["collective_measured"]["psum_bytes"]
                for e in iters if "collective_measured" in e
            )
            rec["analytic_psum_bytes"] = int(analytic)
            rec["measured_psum_bytes"] = int(measured)
            if measured and analytic:
                rec["measured_vs_analytic"] = round(measured / analytic, 4)
            rec["overlap"] = bool(
                booster._grower_params.overlap_collectives
            )
        ses.configure(enabled=False)
        ses.reset()
        out[name] = rec
    return out


def serve_sweep() -> dict:
    """Offered-load sweep of the serving plane (``lgb.serve``).

    For each offered load (requests/sec of fixed-size requests) a paced
    client drives the micro-batcher for a few seconds; we record achieved
    request p50/p99 latency (measured at the caller, enqueue->result),
    achieved rows/sec throughput, and the batcher's fill/flush/miss
    counters.  The trade this quantifies: at low load every request rides
    its own deadline flush (latency ~= deadline), at high load batches
    fill before the deadline and throughput approaches the bucket-ladder
    ceiling.  Runs standalone via ``python bench.py --serve-sweep``.
    """
    import threading

    import lightgbm_tpu as lgb

    n_rows = int(os.environ.get("BENCH_SERVE_ROWS", 50_000))
    n_features = 28
    n_trees = int(os.environ.get("BENCH_SERVE_TREES", 20))
    req_rows = int(os.environ.get("BENCH_SERVE_REQ_ROWS", 8))
    duration_s = float(os.environ.get("BENCH_SERVE_SECS", 3.0))
    loads = [
        int(v)
        for v in os.environ.get(
            "BENCH_SERVE_LOADS", "50,200,1000,4000"
        ).split(",")
        if v.strip()
    ]
    deadline_ms = float(os.environ.get("BENCH_SERVE_DEADLINE_MS", 5.0))
    max_batch = int(os.environ.get("BENCH_SERVE_MAX_BATCH", 4096))

    X, y = _make_data(n_rows, n_features)
    params = dict(_PARAMS, num_leaves=63)
    booster = lgb.train(params, lgb.Dataset(X, y, params=params), n_trees)
    rng = np.random.default_rng(7)
    Xq = rng.normal(size=(req_rows, n_features)).astype(np.float32)

    out = {
        "req_rows": req_rows,
        "duration_s": duration_s,
        "deadline_ms": deadline_ms,
        "max_batch": max_batch,
        "n_trees": len(booster.models_),
        "loads": {},
    }
    server = lgb.serve(
        booster, deadline_ms=deadline_ms, max_batch=max_batch, port=0
    )
    try:
        for load in loads:
            # paced open-loop client: one request every 1/load seconds,
            # latency measured enqueue->result at the caller
            lat_lock = threading.Lock()
            latencies: list = []
            pending: list = []
            interval = 1.0 / load
            t_end = time.perf_counter() + duration_s

            def reap(fut, t0):
                fut.result(timeout=60.0)
                with lat_lock:
                    latencies.append((time.perf_counter() - t0) * 1e3)

            t_next = time.perf_counter()
            n_sent = 0
            while time.perf_counter() < t_end:
                t0 = time.perf_counter()
                fut = server.predict_async(Xq)
                th = threading.Thread(target=reap, args=(fut, t0))
                th.start()
                pending.append(th)
                n_sent += 1
                t_next += interval
                sleep = t_next - time.perf_counter()
                if sleep > 0:
                    time.sleep(sleep)
            for th in pending:
                th.join(timeout=60.0)
            lat = sorted(latencies)

            def pct(q):
                return round(lat[min(len(lat) - 1, int(q * (len(lat) - 1)))], 3)

            stats = server.stats()
            out["loads"][str(load)] = {
                "offered_rps": load,
                "achieved_rps": round(n_sent / duration_s, 1),
                "rows_per_sec": round(n_sent * req_rows / duration_s, 1),
                "p50_ms": pct(0.50) if lat else None,
                "p99_ms": pct(0.99) if lat else None,
                "batch_fill": round(stats["batch_fill"], 4),
                "deadline_miss_rate": round(stats["deadline_miss_rate"], 4),
            }
    finally:
        server.stop()
    return out


def _tensor_flop_model(n_rows: int, n_trees: int, depth: int, f: int) -> dict:
    """Analytic MAC counts for the three tensor-forest contractions.

    The matmul engine trades the walker's D gather rounds per tree for
    dense int8/f32 contractions sized for a systolic MXU: per row it is
    deliberately FLOP-inflated (every node of every tree is evaluated),
    which is the right trade exactly when the hardware's matmul
    throughput dwarfs its gather throughput.  These counts feed the
    BENCH_NOTES roofline argument."""
    p_tree = (1 << depth) - 1
    lp = 1 << depth
    p = n_trees * p_tree
    sel_macs = 2 * n_rows * f * p        # hi/lo digit matmuls, int8 -> i32
    route_macs = n_rows * n_trees * p_tree * lp  # path-sign scoring, int8
    leaf_macs = n_rows * n_trees * lp    # one-hot . leaf values, f32
    return {
        "select_int8_macs": int(sel_macs),
        "route_int8_macs": int(route_macs),
        "leaf_f32_macs": int(leaf_macs),
        "total_macs": int(sel_macs + route_macs + leaf_macs),
        "macs_per_row": int((sel_macs + route_macs + leaf_macs) // n_rows),
        # the walker's per-row work for comparison: D node visits per tree,
        # each a handful of gathers + compares (no dense math)
        "walker_node_visits_per_row": int(n_trees * depth),
    }


def pred_engine_sweep() -> dict:
    """Walker vs matmul prediction-engine A/B (``--pred-engine-sweep``).

    Grid: rows x depth x trees (env-tunable, defaults 64k/1M rows,
    depth {4,6}, trees {50,200,500}).  One model per depth is trained
    small and its trees replicated to each target count (same trick as
    the headline predict bench), so every cell predicts through the
    exact streaming path a user would hit.  Each cell runs both engines
    on identical inputs: warmup predict (ladder compiles) then one timed
    predict, recording rows/sec, the phase breakdown (bin / device
    contract-or-walk / host), recompiles in the timed run, and byte
    parity between the two engines' outputs.  The analytic MXU FLOP
    model for each shape rides along for the BENCH_NOTES roofline
    analysis — on CPU fallback the matmul engine's FLOP inflation is
    expected to show as a slowdown; the model quantifies the MXU
    throughput at which the trade inverts."""
    import lightgbm_tpu as lgb

    row_grid = [
        int(v)
        for v in os.environ.get(
            "BENCH_PRED_ROWS", "64000,1000000"
        ).split(",")
        if v.strip()
    ]
    tree_grid = [
        int(v)
        for v in os.environ.get("BENCH_PRED_TREES", "50,200,500").split(",")
        if v.strip()
    ]
    depth_grid = [
        int(v)
        for v in os.environ.get("BENCH_PRED_DEPTHS", "4,6").split(",")
        if v.strip()
    ]
    train_rows = int(os.environ.get("BENCH_PRED_TRAIN_ROWS", 100_000))
    n_features = 28
    max_rows = max(row_grid)
    X, y = _make_data(max(max_rows, train_rows), n_features)

    out = {
        "train_rows": train_rows,
        "n_features": n_features,
        "cells": [],
    }
    for depth in depth_grid:
        params = dict(
            _PARAMS,
            num_leaves=1 << depth,
            max_depth=depth,
            max_bin=255,
        )
        base = lgb.train(
            params,
            lgb.Dataset(X[:train_rows], y[:train_rows], params=params),
            25,
        )
        orig_models = list(base.models_)
        orig_recs = list(base._bin_records)
        for n_trees in tree_grid:
            while len(base.models_) < n_trees:
                base.models_.extend(orig_models)
                base._bin_records.extend(orig_recs)
            del base.models_[n_trees:]
            del base._bin_records[n_trees:]
            base._bump_model_version()
            for n_rows in row_grid:
                Xp = X[:n_rows]
                cell = {
                    "depth": depth,
                    "trees": n_trees,
                    "rows": n_rows,
                    "flop_model": _tensor_flop_model(
                        n_rows, n_trees, depth, n_features
                    ),
                }
                preds = {}
                for eng in ("walk", "matmul"):
                    base.predict(Xp, pred_engine=eng)  # ladder warmup
                    c0 = lgb.compile_count()
                    t0 = time.perf_counter()
                    preds[eng] = np.asarray(
                        base.predict(Xp, pred_engine=eng)
                    )
                    dt = time.perf_counter() - t0
                    stats = dict(base.last_predict_stats)
                    cell[eng] = {
                        "rows_per_sec": round(n_rows / dt),
                        "wall_ms": round(dt * 1e3, 1),
                        "engine_resolved": stats.get("engine", "walk"),
                        "recompiles_timed": lgb.compile_count() - c0,
                        "phases_ms": {
                            "bin": round(float(stats.get("bin_ms", 0.0)), 1),
                            "device": round(
                                float(stats.get("walk_ms", 0.0)), 1
                            ),
                            "host": round(float(stats.get("host_ms", 0.0)), 1),
                            "transfer": round(
                                float(stats.get("transfer_ms", 0.0)), 1
                            ),
                        },
                    }
                cell["byte_identical"] = bool(
                    preds["walk"].tobytes() == preds["matmul"].tobytes()
                )
                cell["matmul_speedup"] = round(
                    cell["matmul"]["rows_per_sec"]
                    / max(1, cell["walk"]["rows_per_sec"]),
                    3,
                )
                out["cells"].append(cell)
    return out


_INGEST_CELL_SCRIPT = r"""
import json, os, resource, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
csv_path, chunk_rows = sys.argv[1], int(sys.argv[2])
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.obs.registry import get_session

get_session().configure(enabled=True)
params = {
    "objective": "binary", "max_bin": 255, "verbosity": -1,
    "bin_construct_sample_cnt": 50000, "data_random_seed": 1,
    "ingest_chunk_rows": chunk_rows,
}
# settle the allocator baseline (interpreter + jax + a tiny construct)
# so the reported delta isolates THIS construct's footprint; ru_maxrss
# is process-lifetime-monotone, hence one fresh process per cell
rng = np.random.default_rng(0)
Xs = rng.normal(size=(256, 28))
ys = (Xs[:, 0] > 0).astype(np.float64)
lgb.Dataset(Xs, ys, params=params).construct()
base = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
t0 = time.perf_counter()
ds = lgb.Dataset(csv_path, params=params).construct()
wall = time.perf_counter() - t0
peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
n = int(ds.bins.shape[0])
print(json.dumps({
    "rows": n,
    "wall_s": round(wall, 2),
    "rows_per_sec": round(n / wall),
    "peak_rss_bytes": int(peak),
    "rss_delta_bytes": int(peak - base),
    # 0 for the one-shot path: only stream_pack sets this gauge
    "chunks_streamed": int(
        get_session().gauges.get("ingest/chunks_total", 0.0)
    ),
}))
"""


def ingest_sweep() -> dict:
    """Chunked-vs-one-shot ingest A/B (``--ingest-sweep``).

    Writes a Higgs-shaped label+28-feature CSV once (1M rows by default,
    generated chunk-wise so the bench itself stays lean), then builds a
    Dataset from that file in a FRESH subprocess per cell — ``ru_maxrss``
    is process-lifetime-monotone, so peak-RSS cells cannot share a
    process.  One cell runs the one-shot loader (``ingest_chunk_rows=0``:
    np.loadtxt materializes the full f64 matrix); the others stream the
    same file through the two-pass chunked ingest at chunk sizes
    {64k, 256k, 1M}.  Each cell reports wall, rows/s, lifetime peak RSS
    and the delta over a settled baseline; the headline ratios compare
    each chunked cell's RSS delta and wall against one-shot.  Byte parity
    between the two paths is asserted in-suite (tests/test_ingest.py),
    not here — the bench measures the memory/wall trade only."""
    import shutil
    import subprocess
    import tempfile

    n_rows = int(os.environ.get("BENCH_INGEST_ROWS", 1_000_000))
    n_features = 28
    chunk_grid = [
        int(v)
        for v in os.environ.get(
            "BENCH_INGEST_CHUNKS", "65536,262144,1000000"
        ).split(",")
        if v.strip()
    ]
    td = tempfile.mkdtemp(prefix="lgbtpu_ingest_bench_")
    csv_path = os.path.join(td, "higgs_like.csv")
    try:
        rng = np.random.default_rng(42)
        wvec = rng.normal(size=n_features)
        with open(csv_path, "w") as fh:
            done = 0
            while done < n_rows:
                m = min(100_000, n_rows - done)
                Xc = rng.normal(size=(m, n_features))
                yc = (
                    Xc @ wvec * 0.5 + rng.normal(size=m) > 0
                ).astype(np.float64)
                np.savetxt(
                    fh,
                    np.column_stack([yc, Xc]),
                    delimiter=",",
                    fmt="%.5f",
                )
                done += m
        csv_bytes = os.path.getsize(csv_path)

        def run_cell(chunk_rows: int) -> dict:
            r = subprocess.run(
                [
                    sys.executable,
                    "-c",
                    _INGEST_CELL_SCRIPT,
                    csv_path,
                    str(chunk_rows),
                ],
                capture_output=True,
                text=True,
                timeout=1800,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            if r.returncode != 0:
                raise RuntimeError(
                    f"ingest cell chunk_rows={chunk_rows} failed:\n"
                    + r.stderr[-4000:]
                )
            return json.loads(r.stdout.strip().splitlines()[-1])

        out = {
            "rows": n_rows,
            "n_features": n_features,
            "csv_bytes": int(csv_bytes),
            "raw_f64_bytes": int(n_rows * n_features * 8),
            "cells": [],
        }
        one_shot = run_cell(0)
        out["cells"].append(dict(one_shot, mode="one_shot", chunk_rows=0))
        for cr in chunk_grid:
            cell = run_cell(cr)
            cell.update(
                mode="chunked",
                chunk_rows=cr,
                rss_reduction_vs_one_shot=round(
                    one_shot["rss_delta_bytes"]
                    / max(1, cell["rss_delta_bytes"]),
                    2,
                ),
                wall_vs_one_shot=round(
                    cell["wall_s"] / one_shot["wall_s"], 3
                ),
            )
            out["cells"].append(cell)
        return out
    finally:
        shutil.rmtree(td, ignore_errors=True)


_LAUNCH_CELL_SCRIPT = r"""
import json, os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
n_rows, n_launch, rounds, leaves, mesh = (int(v) for v in sys.argv[1:6])
if mesh:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.obs.jit import compile_counts_by_label

rng = np.random.default_rng(0)
X = rng.normal(size=(n_rows, 28))
y = X @ rng.normal(size=28) * 0.5 + rng.normal(size=n_rows) * 0.1
params = {
    "objective": "regression", "num_leaves": leaves, "verbosity": -1,
    "min_data_in_leaf": 20, "seed": 0,
    "train_steps_per_launch": n_launch,
}
if mesh:
    params.update({"tree_learner": "data", "num_machines": 8})
ds = lgb.Dataset(X, y, free_raw_data=False)

# warmup run in-process: compiles the grow/scan executable once so the
# timed run measures steady-state launches, not tracing
lgb.train(dict(params), ds, num_boost_round=2 * n_launch)
c0 = dict(compile_counts_by_label())

t0 = time.perf_counter()
booster = lgb.train(dict(params), ds, num_boost_round=rounds)
wall_s = time.perf_counter() - t0
c1 = compile_counts_by_label()

# exact whole-run totals (the _host_overhead_ms sample window is bounded)
host_total = float(booster._host_overhead_total_ms)
host_n = int(booster._host_overhead_n)
print(json.dumps({
    "steps_per_launch": n_launch,
    "rows": n_rows,
    "rounds": rounds,
    "mesh": "data8" if mesh else "serial",
    "wall_s": round(wall_s, 3),
    "iter_ms": round(wall_s / rounds * 1e3, 2),
    "iters_per_s": round(rounds / wall_s, 2),
    "dispatches": (rounds + n_launch - 1) // n_launch,
    # wall between device dispatches (callbacks, telemetry, Python loop),
    # amortized over the boosting iterations each dispatch covers
    "host_overhead_ms_per_iter": round(host_total / rounds, 4),
    "host_overhead_ms_per_dispatch": round(
        host_total / max(1, host_n), 4
    ),
    # retrace ledger for the timed run: the scan executable (and the
    # sharded grow beneath it) must show ZERO fresh compiles after warmup
    "timed_run_compiles": {
        k: int(c1.get(k, 0) - c0.get(k, 0))
        for k in sorted(set(c0) | set(c1))
        if (c1.get(k, 0) - c0.get(k, 0)) > 0
        and (k.startswith("grow/") or k.startswith("parallel/"))
    },
}))
"""


def launch_sweep() -> dict:
    """Device-resident boosting A/B (``--launch-sweep``).

    For N in {1, 2, 4, 8} train the same 20k x 28 regression model with
    ``train_steps_per_launch=N`` — serial and under the ``tree_learner=
    data`` 8-device mesh — and record per-iteration wall, the host
    overhead between device dispatches, and the steady-state retrace
    ledger.  Each cell is a fresh subprocess (cold jit caches + compile
    counters); a warmup train inside the cell absorbs tracing so the
    timed run measures launch steady state.  The model bytes are
    N-invariant (tests/test_launch_scan.py); this sweep measures only
    where the host round-trip time goes."""
    import subprocess

    n_rows = int(os.environ.get("BENCH_LAUNCH_ROWS", 20_000))
    rounds = int(os.environ.get("BENCH_LAUNCH_ROUNDS", 24))
    leaves = int(os.environ.get("BENCH_LAUNCH_LEAVES", 15))
    n_grid = [
        int(v)
        for v in os.environ.get("BENCH_LAUNCH_N", "1,2,4,8").split(",")
        if v.strip()
    ]
    out = {
        "rows": n_rows,
        "n_features": 28,
        "num_leaves": leaves,
        "rounds": rounds,
        "cells": [],
    }
    for mesh in (0, 1):
        for n in n_grid:
            r = subprocess.run(
                [
                    sys.executable,
                    "-c",
                    _LAUNCH_CELL_SCRIPT,
                    str(n_rows),
                    str(n),
                    str(rounds),
                    str(leaves),
                    str(mesh),
                ],
                capture_output=True,
                text=True,
                timeout=3600,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            if r.returncode != 0:
                raise RuntimeError(
                    f"launch cell n={n} mesh={mesh} failed:\n"
                    + r.stderr[-4000:]
                )
            out["cells"].append(json.loads(r.stdout.strip().splitlines()[-1]))
    return out


_FLEET_CELL_SCRIPT = r"""
import json, os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
n_rows, m, iters, leaves = (int(v) for v in sys.argv[1:5])
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import create_booster
from lightgbm_tpu.boosting.fleet import FleetTrainer
from lightgbm_tpu.obs.jit import compile_counts_by_label

rng = np.random.default_rng(0)
X = rng.normal(size=(n_rows, 28))
y = X @ rng.normal(size=28) * 0.5 + rng.normal(size=n_rows) * 0.1
base = {
    "objective": "regression", "num_leaves": leaves, "verbosity": -1,
    "min_data_in_leaf": 20, "seed": 0,
}
param_sets = [
    dict(base, seed=i, learning_rate=0.05 + 0.01 * i) for i in range(m)
]
ds = lgb.Dataset(X, y, free_raw_data=False)

# solo reference: one member trained alone through the standard update
# path (what M sequential runs would each pay per iteration)
solo = create_booster(dict(param_sets[0]), ds)
t0 = time.perf_counter()
solo.update()
solo_compile_s = time.perf_counter() - t0
solo.update()  # settle
t0 = time.perf_counter()
for _ in range(iters):
    solo.update()
# the solo path pipelines its host fetch one iteration behind — drain it
# (models_ property) and block on the score so the timed window covers
# ALL the work an iteration dispatched
import jax
_ = solo.models_
jax.block_until_ready(solo._score)
solo_iter_ms = (time.perf_counter() - t0) / iters * 1e3
c0 = compile_counts_by_label()

boosters = [create_booster(dict(p), ds) for p in param_sets]
trainer = FleetTrainer(boosters)
t0 = time.perf_counter()
trainer.update()
fleet_compile_s = time.perf_counter() - t0
trainer.update()
t0 = time.perf_counter()
for _ in range(iters):
    trainer.update()
fleet_iter_ms = (time.perf_counter() - t0) / iters * 1e3
c1 = compile_counts_by_label()

print(json.dumps({
    "m": m,
    "rows": n_rows,
    "solo_iter_ms": round(solo_iter_ms, 1),
    "sequential_iter_ms": round(solo_iter_ms * m, 1),
    "fleet_iter_ms": round(fleet_iter_ms, 1),
    "fleet_iter_per_member_ms": round(fleet_iter_ms / m, 1),
    "speedup_vs_sequential": round(solo_iter_ms * m / fleet_iter_ms, 2),
    "solo_compile_s": round(solo_compile_s, 1),
    "fleet_compile_s": round(fleet_compile_s, 1),
    "fleet_grow_executables": int(
        c1.get("fleet/grow", 0) - c0.get("fleet/grow", 0)
    ),
    # dispatch ledger per boosting iteration: M sequential runs issue M
    # grow dispatches (each with its own per-leaf histogram launches);
    # the fleet's custom_vmap hist rule folds the member axis into the
    # segment ids, so ONE launch per leaf covers all M members
    "grow_dispatches_per_iter": {"sequential": m, "fleet": 1},
    "hist_launch_reduction": m,
}))
"""


def fleet_sweep() -> dict:
    """Vmapped model-fleet A/B (``--fleet-sweep``).

    For each fleet size M in {1, 4, 16, 32} train M same-shape regression
    members (seed + learning-rate sweep) at 64k x 28 two ways — M solo
    runs through the standard update path vs ONE FleetTrainer whose
    vmapped grow batches all members per launch — and record per-iteration
    wall, compile time, grow-executable counts and the dispatch ledger.
    Each cell runs in a fresh subprocess so compile caches and counters
    start cold.  The analytic fleet psum model (one stacked [M, ...]
    payload per collective step under ``tree_learner=data``) rides along
    from ``parallel.mesh.fleet_psum_bytes_per_iteration`` — the same
    formula the perf gate pins."""
    import subprocess

    from lightgbm_tpu.parallel.mesh import (
        MeshSpec,
        fleet_psum_bytes_per_iteration,
    )

    n_rows = int(os.environ.get("BENCH_FLEET_ROWS", 64_000))
    iters = int(os.environ.get("BENCH_FLEET_ITERS", 3))
    leaves = int(os.environ.get("BENCH_FLEET_LEAVES", 15))
    m_grid = [
        int(v)
        for v in os.environ.get("BENCH_FLEET_M", "1,4,16,32").split(",")
        if v.strip()
    ]
    out = {
        "rows": n_rows,
        "n_features": 28,
        "num_leaves": leaves,
        "timed_iters": iters,
        "cells": [],
    }
    for m in m_grid:
        r = subprocess.run(
            [
                sys.executable,
                "-c",
                _FLEET_CELL_SCRIPT,
                str(n_rows),
                str(m),
                str(iters),
                str(leaves),
            ],
            capture_output=True,
            text=True,
            timeout=3600,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        if r.returncode != 0:
            raise RuntimeError(
                f"fleet cell m={m} failed:\n" + r.stderr[-4000:]
            )
        cell = json.loads(r.stdout.strip().splitlines()[-1])
        cell["analytic_psum_bytes_data8"] = fleet_psum_bytes_per_iteration(
            n_splits=leaves - 1,
            n_features=28,
            num_bins=255,
            fleet=m,
            spec=MeshSpec("data", data=8, feature=1),
        )
        out["cells"].append(cell)
    return out


def main() -> None:
    if "--fleet-sweep" in sys.argv:
        # standalone, CPU-pinned: each M cell is its own subprocess so the
        # compile counters and jit caches start cold
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        print(json.dumps({"fleet_sweep": fleet_sweep()}))
        return
    if "--launch-sweep" in sys.argv:
        # standalone, CPU-pinned: each (N, mesh) cell is its own subprocess
        # so jit caches and compile counters start cold
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        print(json.dumps({"launch_sweep": launch_sweep()}))
        return
    if "--ingest-sweep" in sys.argv:
        # standalone, CPU-pinned: each cell is its own subprocess, so the
        # parent only orchestrates and writes the CSV fixture
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        print(json.dumps({"ingest_sweep": ingest_sweep()}))
        return
    if "--pred-engine-sweep" in sys.argv:
        # standalone, CPU-pinned like --serve-sweep: cross-engine parity
        # and phase shape, plus the analytic MXU model for the roofline
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        print(json.dumps({"pred_engine_sweep": pred_engine_sweep()}))
        return
    if "--serve-sweep" in sys.argv:
        # standalone, CPU-pinned like --mesh-sweep: the sweep measures the
        # batching/latency trade, not kernel speed
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        print(json.dumps({"serve_sweep": serve_sweep()}))
        return
    if "--mesh-sweep" in sys.argv:
        # standalone: 8 virtual CPU devices, CPU pinned before backend init
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        try:
            jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass
        print(json.dumps({"mesh_layout_sweep": mesh_layout_sweep()}))
        return
    # a device benchmark: no TPU is an error, never a CPU number under the
    # same metric name
    import jax

    from lightgbm_tpu.utils.compile_cache import use_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            f"bench.py: needs a TPU; jax.devices()[0] is {dev.platform!r} "
            f"({dev.device_kind}). Refusing to report a device metric from it."
        )
    compile_cache_dir = use_compile_cache()
    # the headline target is defined at Higgs scale (10.5M rows,
    # docs/Experiments.rst:108); a secondary 1M point keeps round-over-round
    # comparability
    n_rows = int(os.environ.get("BENCH_ROWS", 10_500_000))
    n_features = 28
    timed_iters = int(os.environ.get("BENCH_ITERS", 10))

    X, y = _make_data(n_rows, n_features)
    iters_per_sec, booster, train_compiles = _train_bench(X, y, timed_iters)
    baseline = 3.8  # reference CPU iters/sec on Higgs (BASELINE.md)

    # phase breakdown BEFORE the predict section replicates models_; a phase
    # that fails fails the run (no {"error": ...} under exit 0)
    train_phases = _train_phases(X, y, iters_per_sec)
    sweep_iters = int(os.environ.get("BENCH_SWEEP_ITERS", min(timed_iters, 3)))
    leaf_batch_sweep = _leaf_batch_sweep(X, y, sweep_iters)

    secondary_rows = int(os.environ.get("BENCH_ROWS_SECONDARY", 1_000_000))
    iters_per_sec_secondary = None
    if secondary_rows and secondary_rows < n_rows:
        Xs, ys = X[:secondary_rows], y[:secondary_rows]
        iters_per_sec_secondary, _, _ = _train_bench(Xs, ys, timed_iters)

    # batch-inference throughput. The fork's 84k preds/s (original.md) was
    # measured on a 376-tree model; replicate the trained trees to the same
    # count so the comparison is apples-to-apples.
    n_trees_target = 376
    orig_models = list(booster.models_)
    orig_recs = list(booster._bin_records)
    while len(booster.models_) < n_trees_target:
        booster.models_.extend(orig_models)
        booster._bin_records.extend(orig_recs)
    del booster.models_[n_trees_target:]
    del booster._bin_records[n_trees_target:]
    booster._bump_model_version()
    pred_rows = min(n_rows, 500_000)
    Xp = X[:pred_rows]
    t0 = time.perf_counter()
    booster.predict(Xp)  # warmup: bucket-ladder executables compile here
    pred_warmup_dt = time.perf_counter() - t0
    t0 = time.perf_counter()
    booster.predict(Xp)
    pred_dt = time.perf_counter() - t0
    preds_per_sec = pred_rows / pred_dt
    # phase-resolved breakdown of the timed run (streaming engine /
    # forest-walk stats): which pipeline stage regressed is visible
    # round-over-round instead of one opaque preds_per_sec scalar
    pred_stats = dict(booster.last_predict_stats)
    pred_phases = {
        k: round(float(pred_stats.get(k, 0.0)), 1)
        for k in ("bin_ms", "transfer_ms", "walk_ms", "host_ms")
    }
    pred_phases["path"] = pred_stats.get("path", "unknown")
    pred_phases["chunks"] = pred_stats.get("chunks", 1)
    pred_phases["compiles_in_timed_run"] = pred_stats.get("compiles", 0)

    out = {
        "metric": f"higgs_like_{n_rows}_rows_boosting_iters_per_sec",
        "value": round(iters_per_sec, 4),
        "unit": "iters/sec",
        "vs_baseline": round(iters_per_sec / baseline, 4),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": len(jax.devices()),
        "compile_cache_dir": compile_cache_dir,
        "rows": n_rows,
        "baseline_rows": 10_500_000,
        "note": "vs_baseline divides by the reference CPU's 3.8 iters/s on 10.5M rows (BASELINE.md); when 'rows' != baseline_rows the per-row throughput differs by rows/baseline_rows",
        "preds_per_sec": round(preds_per_sec),
        "pred_rows": pred_rows,
        "preds_vs_fork_84k": round(preds_per_sec / 84000.0, 2),
        "pred_warmup_s": round(pred_warmup_dt, 2),
        "pred_phases": pred_phases,
        "train_phases": train_phases,
        "train_compiles": train_compiles,
        "leaf_batch_sweep_iters_per_sec": leaf_batch_sweep,
    }
    if iters_per_sec_secondary is not None:
        out[f"iters_per_sec_{secondary_rows}_rows"] = round(
            iters_per_sec_secondary, 4
        )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
