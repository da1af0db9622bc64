#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One ``lgb.train`` call holds warm-up and the timed window; a callback of the
harness reads the clock at the boundaries the program gives it (every
iteration, or every launch of eight).  The last stdout line is the result,
passed through ``contract.validate_line`` before it is printed; a run that
cannot produce a conforming line exits non-zero and prints none.

``--rehearse`` (tiny rows, whatever backend JAX has) exercises the control
flow and the validator without a chip; it reports ``platform`` as JAX does
and is no measurement.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as this file can read it

import argparse
import gc
import math
import os
import shutil
import sys
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


from benchmark import contract, data as bdata

REHEARSE_ROWS = 40_000
TRACE_DIR = os.path.join(ROOT, ".bench_trace")

# Warnings of the program that mean a fast path was lost (chip_smoke.py's list).
FALLBACK_WARNINGS = (
    "segment-resident training is unavailable",
    "[resilience] fused Pallas grow step failed",
    "train_steps_per_launch=",
    "distributed tree_learner requested but",
)


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T0:8.2f}s] {msg}", file=sys.stderr, flush=True)


class Logs:
    """The program's logger: everything to stderr, warnings kept."""

    def __init__(self) -> None:
        self.warnings: List[str] = []

    def info(self, msg: str) -> None:
        print(msg, file=sys.stderr)

    def warning(self, msg: str) -> None:
        self.warnings.append(str(msg))
        print(f"[Warning] {msg}", file=sys.stderr)

    def fallbacks(self) -> List[str]:
        return [w for w in self.warnings if any(s in w for s in FALLBACK_WARNINGS)]


class CompileClock:
    """Backend compilations (a persistent-cache hit counts its retrieval):
    count and seconds, from ``jax.monitoring``'s duration events."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self._EVENT:
            self.seconds += float(duration)
            self.count += 1


class WindowClock:
    """The harness's callback.  Warm-up ends at the first boundary with
    ``warmup_iterations`` done; the window runs from there to the first
    boundary at or after ``seconds``; then training is stopped.  In a traced
    run the profiler covers the window's first ``trace_iterations``."""

    order = 1000  # after the program's own callbacks: their time is inside

    def __init__(self, *, seconds: float, warmup_iterations: int,
                 trace_iterations: int, trace_dir: Optional[str],
                 compiles: CompileClock) -> None:
        self.seconds = float(seconds)
        self.warmup = int(warmup_iterations)
        self.trace_iters = int(trace_iterations)
        self.trace_dir = trace_dir
        self.compiles = compiles
        # (clock on arrival, iterations done, clock on leaving) at every
        # boundary; the two clocks differ by the profiler's start and stop
        self.marks: List[list] = []
        self.evals: List[Optional[float]] = []  # first metric of the first valid set
        self.t0 = self.t1 = None
        self.i0 = self.i1 = None
        self.compiles_before_window = None
        self.compile_s_before_window = None
        self.tracing = False
        self.trace_mark = None  # (t_start, i_start, t_stop, i_stop)

    def _block(self, model) -> None:
        import jax

        score = getattr(model, "_score", None)
        if score is not None:
            jax.block_until_ready(score)

    def _start_trace(self, done: int) -> None:
        import jax

        from benchmark.trace_reduce import BEGIN_MARK

        # the program's Python is not traced call by call: that slows the
        # host the idle share is about.  TraceMe spans (dispatches, the
        # harness's marks) are.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.tracing = True
        with jax.profiler.TraceAnnotation(BEGIN_MARK):
            pass
        # the profiler's start is not free: the traced window starts after it
        self.trace_mark = [time.perf_counter(), done, None, None]

    def _stop_trace(self) -> None:
        import jax

        from benchmark.trace_reduce import END_MARK

        with jax.profiler.TraceAnnotation(END_MARK):
            pass
        jax.profiler.stop_trace()
        self.tracing = False

    def __call__(self, env) -> None:
        import jax

        with jax.profiler.TraceAnnotation("bench/boundary"):
            self._block(env.model)
            now = time.perf_counter()
            done = env.iteration + 1 - env.begin_iteration
            self.marks.append([now, done, now])
            ev = env.evaluation_result_list
            self.evals.append(float(ev[0][2]) if ev else None)
            if self.t0 is None:
                if done >= self.warmup:
                    self.t0, self.i0 = now, done
                    self.compiles_before_window = self.compiles.count
                    self.compile_s_before_window = self.compiles.seconds
                    if self.trace_dir is not None:
                        self._start_trace(done)
                        self.marks[-1][2] = time.perf_counter()
                return
            if self.tracing and done - self.i0 >= self.trace_iters:
                self.trace_mark[2], self.trace_mark[3] = now, done
                self._stop_trace()
                self.marks[-1][2] = time.perf_counter()  # collecting takes seconds
            if now - self.t0 >= self.seconds and not self.tracing:
                self.t1, self.i1 = now, done
                from lightgbm_tpu.callback import EarlyStopException

                raise EarlyStopException(env.iteration, ev or [])


class Checks(dict):
    """Each number compared beside its limit; printed last on the line and on
    stderr.  A number that is not finite fails whatever its limit."""

    def add(self, name: str, value: float, limit: float) -> None:
        value = float(value)
        self[name] = {"value": value if math.isfinite(value) else 1e300,
                      "limit": float(limit)}

    def ok(self) -> bool:
        return all(c["value"] <= c["limit"] for c in self.values())


def _devices_or_none(cell, rehearse: bool):
    """The cell's chips as JAX shows them, or None (and the reason on stderr)."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not rehearse and (platform != "tpu" or len(devices) < cell.chips):
        print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); JAX has "
              f"{len(devices)} x {platform!r} ({devices[0].device_kind}). "
              "No result from anything else.", file=sys.stderr)
        return None
    if rehearse and len(devices) < cell.chips:
        print(f"benchmark: rehearsal of {cell.name} needs {cell.chips} devices "
              f"(XLA_FLAGS=--xla_force_host_platform_device_count={cell.chips})",
              file=sys.stderr)
        return None
    return devices


def _compile_cache() -> str:
    import jax

    from lightgbm_tpu.utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()  # $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    # persist every program, also those that compile in under a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def _memory_peak(devices, rehearse: bool):
    """(peak bytes on the fullest device, the stats it was read from).

    On this runtime a program's temporaries are RESERVED, not "in use"
    (PERF.md section 2: a probe with 1 GiB live and 3.2 GB of temporaries reads
    peak_bytes_in_use 1.08 GB, peak_bytes_reserved 3.22 GB), and they are held
    while the live arrays are: the chip's peak is the two together."""
    best, best_stats = 0, {}
    for d in devices:
        stats = d.memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))
        if peak >= best:
            best = peak
            best_stats = {k: int(v) for k, v in stats.items()
                          if k.startswith(("peak_", "bytes_limit"))}
    if rehearse and best <= 0:  # the CPU backend keeps no such count
        import resource

        best = int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024
    return best, best_stats


def _path_checks(checks: Checks, booster, logs: Logs, launches, clock: WindowClock,
                 compiles: CompileClock, expect: Dict[str, Any], rehearse: bool) -> None:
    """The program a user gets ran, or the run is not correct."""
    checks.add("compiles_in_window", compiles.count - clock.compiles_before_window, 0)
    checks.add("degraded", float(bool(booster.degraded)), 0)
    checks.add("fallback_warnings", len(logs.fallbacks()), 0)
    if "mesh_devices" in expect:
        mesh = getattr(booster, "_mesh", None)
        checks.add("mesh_devices_missing",
                   expect["mesh_devices"] - (mesh.size if mesh is not None else 0), 0)
    if rehearse:  # the CPU resolves other programs; they are not the cell's
        return
    mode = expect.get("hist_mode", "seg")
    checks.add("hist_mode_not_" + mode, float(booster._grower_params.hist_mode != mode), 0)
    want = int(expect.get("launch_steps", 0))
    if want:
        bad = [e for e in launches if e.get("steps_per_launch") != want]
        wrong = len(bad) + abs(len(launches) * want - clock.i1)
    else:
        wrong = len(launches)
    checks.add("wrong_program_launches", wrong, 0)


def _traced_metrics(cell, manifest, facts, device, trace_dir, keep_trace, rehearse):
    """Per-layer metrics and the breakdown from the traced sub-window; adds
    ``busy_s`` and ``window_s`` to ``device``."""
    from benchmark import readers, trace_reduce

    t = time.perf_counter()
    xplane = trace_reduce.find_xplane(trace_dir)
    raw = trace_reduce.read_xplane(xplane)
    trace = trace_reduce.build(*raw, n_devices=cell.chips, allow_no_device=rehearse)
    facts["trace"] = trace
    if trace.busy_s is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
    if keep_trace:
        trace_reduce.save_recorded(raw, keep_trace)
        if os.path.getsize(xplane) < 40 * 2**20:
            shutil.copy(xplane, keep_trace + ".xplane.pb")
    metrics = {}
    for m in cell.per_layer:
        value = readers.read_metric(manifest, m["name"], facts)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    log(f"trace reduced in {time.perf_counter() - t:.1f}s")
    shutil.rmtree(trace_dir, ignore_errors=True)
    return metrics, trace.breakdown()


def run(args) -> int:
    manifest = contract.Manifest(ROOT)
    cell = manifest.cell(args.workload)
    cfg, job = cell.config, cell.job
    traced, rehearse = bool(args.trace), bool(args.rehearse)
    if args.seconds <= 0:
        raise contract.ContractError("--seconds must be > 0")

    # ---- the chip, or nothing
    import jax

    devices = _devices_or_none(cell, rehearse)
    if devices is None:
        return 2
    # a rehearsal leaves the process's JAX settings alone
    cache_dir = None if rehearse else _compile_cache()

    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.flight import get_flight

    logs = Logs()
    lgb.register_logger(logs)
    compiles = CompileClock()

    # ---- inputs from the seed
    n_features = int(cfg["features"])
    rows = int(cfg["rows"]) if "rows" in cfg else int(cfg["rows_per_chip"]) * cell.chips
    if rehearse:
        rows = REHEARSE_ROWS * cell.chips
    recipe = cfg["data"]
    params = dict(cfg["params"], **job.get("extra_params", {}))
    t = time.perf_counter()
    blocks, y = bdata.make_blocks(args.seed, rows, n_features, recipe=recipe)
    valid_rows = int(round(rows * float(job.get("valid_fraction", 0.0))))
    vblocks = vy = None
    if valid_rows:
        vblocks, vy = bdata.make_blocks(args.seed, valid_rows, n_features,
                                        valid=True, recipe=recipe)
    data_s = time.perf_counter() - t
    log(f"data {rows} x {n_features} (+{valid_rows} valid) in {data_s:.1f}s")

    t = time.perf_counter()
    dtrain = lgb.Dataset(blocks, y, params=dict(params))
    dtrain.construct()
    valid_sets = []
    if valid_rows:
        valid_sets = [lgb.Dataset(vblocks, vy, reference=dtrain)]
        valid_sets[0].construct()
    dataset_construct_s = time.perf_counter() - t
    log(f"Dataset construct {dataset_construct_s:.1f}s")

    # ---- one lgb.train call: warm-up, then the window
    trace_dir = None
    if traced:
        trace_dir = os.path.join(TRACE_DIR, cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    clock = WindowClock(
        seconds=args.seconds, warmup_iterations=job["warmup_iterations"],
        trace_iterations=job["trace_iterations"], trace_dir=trace_dir,
        compiles=compiles,
    )
    callbacks = [clock]
    if int(job.get("early_stopping_rounds", 0)) > 0:
        callbacks.append(lgb.early_stopping(int(job["early_stopping_rounds"]),
                                            verbose=False))

    def launch_events():
        return [e for e in get_flight().events() if e.get("event") == "launch"]

    earlier = launch_events()  # another train call of this process (tests, readings.py)
    try:
        booster = lgb.train(params, dtrain, num_boost_round=1_000_000,
                            valid_sets=valid_sets or None, callbacks=callbacks)
    finally:
        if clock.tracing:
            clock._stop_trace()
    if clock.t1 is None:
        raise RuntimeError("training ended before the window closed: "
                           f"{len(clock.marks)} boundaries, warm-up end {clock.t0}")
    setup_s = clock.t0 - T0
    window_s = clock.t1 - clock.t0
    iters = clock.i1 - clock.i0
    log(f"window: {iters} iterations in {window_s:.2f}s after {setup_s:.1f}s of set-up")

    checks = Checks()
    launches = launch_events()
    if earlier:  # the ring drops its oldest: if the last earlier event is gone, all are
        at = [i for i, e in enumerate(launches) if e is earlier[-1]]
        launches = launches[at[0] + 1:] if at else launches
    _path_checks(checks, booster, logs, launches, clock,
                 compiles, job.get("expect", {}), rehearse)
    path_ok = checks.ok()
    memory_peak, memory_stats = _memory_peak(devices[: cell.chips], rehearse)
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": memory_peak,
    }

    # ---- the model the user gets; then free the program's state
    follow_n = int(job.get("follow_trees", 3))
    tree_dumps = [t["tree_structure"] for t in booster.dump_model()["tree_info"]]
    del booster, dtrain, valid_sets
    gc.collect()
    jax.clear_caches()

    # ---- correct: the plain reference over what the timed path produced
    t = time.perf_counter()
    ref = contract.load_module(manifest.reference_path(cell.config_name),
                       f"benchmark_reference_{cell.config_name}")
    detail = [] if args.control else None
    numbers = ref.follow_model(
        tree_dumps[:follow_n], blocks=blocks, y=y, params=params, recipe=recipe,
        valid_blocks=vblocks, valid_y=vy,
        valid_metric=clock.evals[:follow_n] if valid_rows else None, detail=detail,
    )
    builder_readings = {}
    if args.control:  # a builder's reading, never part of a benchmark run
        builder_readings = {"detail": detail, "control": ref.follow_model(
            tree_dumps[:follow_n], blocks=blocks, y=y, params=params, recipe=recipe,
            valid_blocks=vblocks, valid_y=vy, control=args.control,
        )}
    limits = cfg["limits"]
    for name, value in numbers.items():
        if name not in limits:
            raise contract.ContractError(f"{cell.name}: no limit for {name!r}")
        checks.add(name, value, limits[name])
    checks.add("trees_followed", follow_n - min(follow_n, len(tree_dumps)), 0)
    reference_s = time.perf_counter() - t
    log(f"reference followed {min(follow_n, len(tree_dumps))} trees in {reference_s:.1f}s")

    # ---- metrics
    breakdown = None
    if not traced:
        values = {"setup_s": setup_s, "train_iters_per_s": iters / window_s}
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise contract.ContractError(f"end-to-end metric {m['name']!r} has no "
                                             "clock in this harness")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        facts = {
            "rows": rows, "features": n_features, "chips": cell.chips,
            "marks": clock.marks, "t0": clock.t0, "trace_mark": clock.trace_mark,
            "dataset_construct_s": dataset_construct_s,
            "compile_s": clock.compile_s_before_window, "tree_dumps": tree_dumps,
            "device_kind": devices[0].device_kind,
        }
        metrics, breakdown = _traced_metrics(cell, manifest, facts, device, trace_dir,
                                             args.keep_trace, rehearse)

    line: Dict[str, Any] = {
        "correct": checks.ok(),
        "attempted": int(iters),
        "failed": 0 if path_ok else int(iters),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["workload"] = cell.name
    line["seed"] = int(args.seed)
    line["facts"] = {
        "rows": rows, "features": n_features, "window_s": window_s,
        "iterations": int(iters), "data_s": data_s,
        "dataset_construct_s": dataset_construct_s,
        "compile_s_before_window": clock.compile_s_before_window,
        "reference_s": reference_s, "compile_cache_dir": cache_dir,
        "memory_stats": memory_stats, "rehearse": rehearse, **builder_readings,
    }
    line["checks"] = checks  # last: each number compared beside its limit

    for name, c in checks.items():
        print(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g})"
              f"{'' if c['value'] <= c['limit'] else '  <-- FAILS'}", file=sys.stderr)
    sys.stderr.flush()
    errs = contract.validate_line(
        line, required=cell.per_layer if traced else cell.end_to_end,
        traced=traced, chips=cell.chips, rehearse=rehearse,
    )
    if errs:
        for e in errs:
            print(f"benchmark: result line breaks the contract: {e}", file=sys.stderr)
        return 3
    print(contract.dumps_line(line), flush=True)
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny rows on whatever backend JAX has; no measurement")
    ap.add_argument("--control", default="",
                    help="also read the configuration's lower-precision control "
                         "(bfloat16) at the same trees, into facts.control; a "
                         "builder's reading, not part of a benchmark run")
    ap.add_argument("--keep-trace", default="",
                    help="also write the reduced events of the traced window here")
    args = ap.parse_args(argv)
    try:
        return run(args)
    except contract.ContractError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 4
    finally:
        # run() routes the program's log lines to stderr; give the process
        # its logger back (tests call main() in their own process)
        log_mod = sys.modules.get("lightgbm_tpu.utils.log")
        if log_mod is not None:
            log_mod.unregister_logger()


if __name__ == "__main__":
    sys.exit(main())
