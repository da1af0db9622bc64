#!/usr/bin/env python3
"""On-chip smoke test: lgb.train -> Booster.predict -> lgb.serve on the TPU.

    python3 chip_smoke.py          # from the root of a checkout, on the chip

Drives the main path once through the entry points a user calls, at Higgs
width (28 features, 255 leaves, max_bin 255; depth cut to a few boosting
rounds, data synthetic from a seed), in ONE process — the chip belongs to
one process, so nothing here starts a child that needs it.  It proves the
system starts and computes the right thing on the chip; it measures
nothing (walls and compile seconds are reported as set-up facts, not as
benchmark results).

Contract (see README "Running"):

* exits non-zero, printing no result line, unless
  ``jax.devices()[0].platform == "tpu"``;
* a leg that fails raises — no leg is caught and continued past — and the
  exit code is non-zero;
* stdout carries exactly two lines, both JSON: first the report (versions,
  compile-cache directory, per-leg ``ok`` / ``wall_s`` / ``compile_s`` and
  facts), then, LAST, the verdict with exactly these keys and no others:
  ``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``
  — the device as JAX reports it.  Everything else (library logs,
  progress) goes to stderr.

The legs are plain functions of their sizes so tests/test_chip_smoke.py can
run them at toy size on CPU; nothing in this file reads a flag or an
environment switch to shrink itself.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import sys
import time
import urllib.request
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

# Higgs-shaped model: the widths of the reference's own headline experiment
# (BASELINE.md).  Everything not listed stays at its default.
TRAIN_PARAMS = {
    "objective": "binary",
    "num_leaves": 255,
    "max_bin": 255,
    "min_data_in_leaf": 100,
}
N_ROWS = 1_000_000
N_FEATURES = 28


@dataclasses.dataclass(frozen=True)
class Expect:
    """What the default path must resolve to where the legs run.  main()
    demands ON_CHIP; the CPU test states the CPU's own answers, so the legs
    need no platform switch."""

    hist_mode: str  # GrowerParams.hist_mode after resolution
    steps_per_launch: int  # what train_steps_per_launch='auto' resolves to
    predict_path: str  # Booster.last_predict_stats["path"]
    mosaic: bool  # the compiled grow programs carry Pallas/Mosaic calls


ON_CHIP = Expect(
    hist_mode="seg", steps_per_launch=8, predict_path="forest_walk", mosaic=True
)

# Warnings that mean a fast path was lost; any of them fails the run.
FALLBACK_WARNINGS = (
    "segment-resident training is unavailable",
    "[resilience] fused Pallas grow step failed",
    "train_steps_per_launch=",  # "[launch] train_steps_per_launch=N ignored"
    "prediction fast path (forest-walk kernel) unavailable",
    "distributed tree_learner requested but",
)
# raw-score agreement with the float64 host walk: tests/test_forest_walk.py
WALK_ATOL = 1e-5
# data-parallel vs one-chip training metric at the same iteration: the
# trees differ only by reduction order and the histogram accumulator
# (int8+refine on one chip, bf16 under a mesh), i.e. near-tie flips
MULTICHIP_AUC_BAND = 0.01


# ------------------------------------------------------------------ helpers


class SmokeFailure(AssertionError):
    """A check of this script did not hold."""


def require(cond, msg: str) -> None:
    """The script's assertion: unlike ``assert`` it survives ``python -O``."""
    if not cond:
        raise SmokeFailure(msg)


class _Logs:
    """Library logger: everything to stderr, warnings kept for the
    fallback check."""

    def __init__(self) -> None:
        self.warnings: List[str] = []

    def info(self, msg: str) -> None:
        print(msg, file=sys.stderr)

    def warning(self, msg: str) -> None:
        self.warnings.append(str(msg))
        print(f"[Warning] {msg}", file=sys.stderr)

    def fallbacks(self) -> List[str]:
        return [w for w in self.warnings if any(s in w for s in FALLBACK_WARNINGS)]


class _CompileClock:
    """Seconds JAX spent in backend compilation (a persistent-cache hit
    counts its retrieval), summed from jax.monitoring's duration events."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self._EVENT:
            self.seconds += float(duration)


@contextlib.contextmanager
def _timed(result: Dict[str, Any], clock: Optional[_CompileClock]):
    """Fill ``wall_s`` / ``compile_s`` of a leg's result around its body."""
    t0 = time.perf_counter()
    c0 = clock.seconds if clock is not None else 0.0
    try:
        yield
    finally:
        result["wall_s"] = round(time.perf_counter() - t0, 3)
        if clock is not None:
            result["compile_s"] = round(clock.seconds - c0, 3)


def make_data(n_rows: int, n_features: int, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """Seeded synthetic binary task at the given shape (float32 features,
    a noisy linear logit — learnable, not separable)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n_rows, n_features)).astype(np.float32)
    w = rng.normal(size=n_features)
    logits = x @ w * 0.5 + rng.normal(scale=1.0, size=n_rows)
    return x, (logits > 0).astype(np.float64)


def auc(y: np.ndarray, score: np.ndarray) -> float:
    """AUC by the repo's own host metric (float64 NumPy, ties averaged)."""
    from lightgbm_tpu.metrics import _weighted_auc

    return _weighted_auc(
        (np.asarray(y) > 0).astype(np.float64), np.asarray(score, np.float64), None
    )


def _program_text(fn, args, kwargs=None) -> str:
    """StableHLO text of a jitted entry lowered with the operands it was
    (or would be) called with."""
    return fn.lower(*args, **(kwargs or {})).as_text()


def _check_booster(
    booster, logs: _Logs, n_trees: int, expect: Expect, what: str
) -> None:
    """The checks every trained booster must pass: tree count, real trees,
    the expected histogram mode, no degradation, no fallback warning."""
    from lightgbm_tpu.obs.flight import get_flight

    require(
        booster.num_trees() == n_trees,
        f"{what}: {booster.num_trees()} trees, expected {n_trees}",
    )
    stumps = [i for i, t in enumerate(booster.models_) if t.num_leaves <= 1]
    require(not stumps, f"{what}: trees {stumps} have a single leaf")
    mode = booster._grower_params.hist_mode
    require(
        mode == expect.hist_mode,
        f"{what}: hist_mode resolved to {mode!r}, not {expect.hist_mode!r}",
    )
    degr = [e for e in get_flight().events() if e.get("event") == "degradation"]
    require(
        not booster.degraded and not degr,
        f"{what}: degraded to a fallback path: {degr}",
    )
    require(not logs.fallbacks(), f"{what}: fallback warnings: {logs.fallbacks()}")


def _launch_events() -> List[Dict[str, Any]]:
    from lightgbm_tpu.obs.flight import get_flight

    return [e for e in get_flight().events() if e.get("event") == "launch"]


# --------------------------------------------------------------------- legs
# Each leg fills ``res`` as it goes (so a failure leaves its partial facts on
# the report line) and sets res["ok"] only at its end.


def leg_train(
    x, y, x_valid, y_valid, *, rounds_a: int, rounds_b: int, eval_rows: int,
    expect: Expect, logs: _Logs, res: Dict[str, Any],
    clock: Optional[_CompileClock] = None,
):
    """Run A: ``rounds_a`` rounds, no valid set (on the chip: full 8-step
    launch windows + a serial tail, so both the scan program and the
    Booster.update() program run).  Run B: ``rounds_b`` rounds with a valid
    set and early stopping (every iteration a serial update()).  Returns
    run A's booster."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops.grower import int8_acc_eligible

    with _timed(res, clock):
        dtrain = lgb.Dataset(x, y, params=dict(TRAIN_PARAMS))
        # ---- run A
        t0 = time.perf_counter()
        a = lgb.train(dict(TRAIN_PARAMS), dtrain, num_boost_round=rounds_a)
        res["run_a_wall_s"] = round(time.perf_counter() - t0, 3)
        launches = _launch_events()
        gp = a._grower_params
        res.update(
            hist_mode=gp.hist_mode,
            grow_fused=bool(gp.grow_fused),
            int8_hist=bool(int8_acc_eligible(gp)),
            launch_windows=len(launches),
            steps_per_launch=sorted({e["steps_per_launch"] for e in launches}),
        )
        _check_booster(a, logs, rounds_a, expect, "run A")
        n = expect.steps_per_launch
        windows = rounds_a // n if n > 1 else 0
        require(
            len(launches) == windows
            and all(e["steps_per_launch"] == n for e in launches),
            f"run A: {len(launches)} launch windows of "
            f"{res['steps_per_launch']} steps; expected {windows} of {n}",
        )
        # the programs that ran — the scan (lgb.train's windows) and the
        # one-iteration grow (Booster.update()) — must carry Mosaic kernels
        fn, args, kwargs = a._grow_call(
            a._ones_mask, a._ones_mask, a._ones_mask, a._full_feature_mask, None
        )
        res["update_custom_calls"] = _program_text(fn, args, kwargs).count(
            "tpu_custom_call"
        )
        require(
            (res["update_custom_calls"] > 0) == expect.mosaic,
            f"Booster.update() grow program has {res['update_custom_calls']} "
            f"Mosaic calls (expected some: {expect.mosaic})",
        )
        if windows:
            runner = a._launch_runner_for(n)
            scan_args, _ = runner._operands(int(a._iter))
            res["scan_custom_calls"] = _program_text(runner._fn, scan_args).count(
                "tpu_custom_call"
            )
            require(
                (res["scan_custom_calls"] > 0) == expect.mosaic,
                f"grow/scan{n} has {res['scan_custom_calls']} Mosaic calls "
                f"(expected some: {expect.mosaic})",
            )
        require(
            gp.grow_fused or not expect.mosaic,
            "grow_fused did not resolve on for Booster.update()",
        )
        res["train_auc"] = round(auc(y[:eval_rows], a.predict(x[:eval_rows])), 5)
        require(res["train_auc"] > 0.75, f"training AUC {res['train_auc']} too low")

        # ---- run B: a valid set at metric_freq=1 clamps N to 1
        t0 = time.perf_counter()
        dvalid = lgb.Dataset(x_valid, y_valid, reference=dtrain)
        b = lgb.train(
            dict(TRAIN_PARAMS), dtrain, num_boost_round=rounds_b,
            valid_sets=[dvalid],
            callbacks=[lgb.early_stopping(rounds_b, verbose=False)],
        )
        res["run_b_wall_s"] = round(time.perf_counter() - t0, 3)
        require(not _launch_events(), "run B (valid set) used a launch window")
        _check_booster(b, logs, rounds_b, expect, "run B")
        vals = dict(b.best_score).get("valid_0", {})
        res["run_b_valid"] = {k: round(float(v), 5) for k, v in vals.items()}
        require(
            bool(vals) and all(np.isfinite(v) for v in vals.values()),
            f"run B validation metrics: {vals}",
        )
        res["ok"] = True
    return a


def leg_predict(
    booster, x, *, n_rows: int, n_sample: int, expect: Expect, logs: _Logs,
    res: Dict[str, Any], clock: Optional[_CompileClock] = None,
) -> None:
    """Booster.predict at ``n_rows`` rows on the expected path, checked
    against the float64 host walk and a model-string round trip."""
    import lightgbm_tpu as lgb

    with _timed(res, clock):
        xs = x[:n_rows]
        raw = booster.predict(xs, raw_score=True)
        res["path"] = dict(booster.last_predict_stats).get("path")
        require(
            res["path"] == expect.predict_path,
            f"predict took path {res['path']!r}, not {expect.predict_path!r}",
        )
        require(
            raw.shape == (len(xs),) and bool(np.isfinite(raw).all()),
            f"raw scores: shape {raw.shape}, finite {np.isfinite(raw).all()}",
        )
        # float64 host reference: sum of Tree.predict over the forest
        sample = np.asarray(xs[:n_sample], np.float64)
        ref = np.zeros(len(sample))
        for tree in booster.models_:
            ref += tree.predict(sample)
        res["max_abs_err_vs_host_f64"] = float(np.abs(raw[:n_sample] - ref).max())
        require(
            res["max_abs_err_vs_host_f64"] <= WALK_ATOL,
            f"raw scores differ from the float64 host walk by "
            f"{res['max_abs_err_vs_host_f64']} > {WALK_ATOL}",
        )
        prob = booster.predict(xs[:n_sample])
        require(bool(np.all((prob >= 0) & (prob <= 1))), "probabilities outside [0, 1]")
        # model text round trip: same text back, same predictions
        text = booster.model_to_string()
        loaded = lgb.Booster(model_str=text)
        require(loaded.model_to_string() == text, "model text round trip differs")
        res["roundtrip_max_abs_diff"] = float(
            np.abs(loaded.predict(sample, raw_score=True) - raw[:n_sample]).max()
        )
        require(
            res["roundtrip_max_abs_diff"] <= WALK_ATOL,
            f"reloaded model predicts {res['roundtrip_max_abs_diff']} away",
        )
        require(not logs.fallbacks(), f"fallback warnings: {logs.fallbacks()}")
        res["ok"] = True


def leg_serve(
    booster, x, *, sizes=(1, 8, 1000), logs: _Logs, res: Dict[str, Any],
    clock: Optional[_CompileClock] = None,
) -> None:
    """lgb.serve on an ephemeral port: POST /predict at a few sizes, values
    bit-equal to Booster.predict, no compile after warm-up."""
    import lightgbm_tpu as lgb

    res["requests"] = 0
    with _timed(res, clock):
        rows = {n: np.asarray(x[:n], np.float64) for n in sizes}
        expected = {n: booster.predict(rows[n]) for n in sizes}
        server = lgb.serve(booster, port=-1)
        try:
            warm = lgb.compile_counts_by_label()
            for _ in range(2):
                for n in sizes:
                    req = urllib.request.Request(
                        server.url + "/predict",
                        data=json.dumps({"rows": rows[n].tolist()}).encode("utf-8"),
                        headers={"Content-Type": "application/json"},
                    )
                    with urllib.request.urlopen(req, timeout=120) as resp:
                        require(resp.status == 200, f"HTTP {resp.status} at {n} rows")
                        doc = json.loads(resp.read().decode("utf-8"))
                    got = np.asarray(doc["predictions"], np.float64)
                    require(
                        got.shape == expected[n].shape
                        and np.array_equal(got, expected[n]),
                        f"serve != Booster.predict at {n} rows (shape {got.shape})",
                    )
                    res["requests"] += 1
            after = lgb.compile_counts_by_label()
            new = {k: v - warm.get(k, 0) for k, v in after.items() if v != warm.get(k, 0)}
            require(not new, f"compiles after serve warm-up: {new}")
        finally:
            server.stop()
        require(not logs.fallbacks(), f"fallback warnings: {logs.fallbacks()}")
        res["ok"] = True


def leg_multichip(
    x, y, *, rounds: int, eval_rows: int, n_devices: int, ref_booster,
    expect: Expect, logs: _Logs, res: Dict[str, Any],
    clock: Optional[_CompileClock] = None,
) -> None:
    """tree_learner=data through lgb.train over ``n_devices`` devices; the
    training metric must sit within MULTICHIP_AUC_BAND of the one-chip
    booster's at the same iteration."""
    import lightgbm_tpu as lgb

    res["ran"] = True
    with _timed(res, clock):
        params = dict(TRAIN_PARAMS, tree_learner="data")
        b = lgb.train(params, lgb.Dataset(x, y, params=params), num_boost_round=rounds)
        _check_booster(b, logs, rounds, expect, "multichip")
        require(
            b._mesh is not None and b._mesh.size == n_devices,
            f"mesh is {b._mesh}, expected {n_devices} devices",
        )
        res["mesh_shape"] = {k: int(v) for k, v in b._mesh.shape.items()}
        res["score_devices"] = sorted(d.id for d in b._score.sharding.device_set)
        shard_rows = max(s.data.shape[-1] for s in b._score.addressable_shards)
        require(
            len(res["score_devices"]) == n_devices
            and shard_rows < b._score.shape[-1],
            f"score is not row-sharded over {n_devices} devices: devices "
            f"{res['score_devices']}, {shard_rows} of {b._score.shape[-1]} "
            "rows per shard",
        )
        fn, args, kwargs = b._grow_call(
            b._ones_mask, b._ones_mask, b._ones_mask, b._full_feature_mask, None
        )
        text = _program_text(fn, args, kwargs)
        res["all_reduces"] = text.count("all_reduce")
        res["custom_calls"] = text.count("tpu_custom_call")
        require(res["all_reduces"] > 0, "no collective in the sharded grow program")
        require(
            (res["custom_calls"] > 0) == expect.mosaic,
            f"sharded grow program has {res['custom_calls']} Mosaic calls "
            f"(expected some: {expect.mosaic})",
        )
        xe, ye = x[:eval_rows], y[:eval_rows]
        res["train_auc"] = round(auc(ye, b.predict(xe)), 5)
        res["one_chip_auc_same_iter"] = round(
            auc(ye, ref_booster.predict(xe, num_iteration=rounds)), 5
        )
        require(
            abs(res["train_auc"] - res["one_chip_auc_same_iter"])
            <= MULTICHIP_AUC_BAND,
            f"data-parallel AUC {res['train_auc']} vs one-chip "
            f"{res['one_chip_auc_same_iter']} at iteration {rounds} "
            f"(band {MULTICHIP_AUC_BAND})",
        )
        res["ok"] = True


# --------------------------------------------------------------------- main


def _versions() -> Dict[str, str]:
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu", "numpy"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = "absent"
    return out


def emit(report: Dict[str, Any], device: Dict[str, Any]) -> None:
    """The script's whole stdout: the report line, then the verdict line.
    The verdict's key set is a contract with whoever runs the script —
    exactly ``ok`` and ``device`` {``platform``, ``kind``, ``count``}; new
    facts go into the report, never into the verdict."""
    print(json.dumps(report))
    verdict = {
        "ok": bool(report["ok"]),
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    }
    print(json.dumps(verdict), flush=True)


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; jax.devices()[0] is {dev.platform!r} "
            f"({dev.device_kind}). Refusing to print a result from it.",
            file=sys.stderr,
        )
        return 2

    import lightgbm_tpu as lgb
    from lightgbm_tpu.native import load_native
    from lightgbm_tpu.utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    logs = _Logs()
    lgb.register_logger(logs)
    clock = _CompileClock()
    n_dev = len(jax.devices())
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": n_dev}
    out: Dict[str, Any] = {
        "ok": False,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": n_dev,
        "versions": _versions(),
        "compile_cache_dir": cache_dir,
        "binning": "native" if load_native() is not None else "numpy",
        "legs": {
            name: {"ok": False} for name in ("train", "predict", "serve", "multichip")
        },
    }
    legs = out["legs"]
    t_start = time.perf_counter()
    try:
        x, y = make_data(N_ROWS + 100_000, N_FEATURES, seed=42)
        x, x_valid, y, y_valid = x[:N_ROWS], x[N_ROWS:], y[:N_ROWS], y[N_ROWS:]
        booster = leg_train(
            x, y, x_valid, y_valid, rounds_a=20, rounds_b=4, eval_rows=100_000,
            expect=ON_CHIP, logs=logs, res=legs["train"], clock=clock,
        )
        leg_predict(
            booster, x, n_rows=500_000, n_sample=2_000, expect=ON_CHIP,
            logs=logs, res=legs["predict"], clock=clock,
        )
        leg_serve(booster, x, logs=logs, res=legs["serve"], clock=clock)
        if n_dev >= 4:
            leg_multichip(
                x, y, rounds=16, eval_rows=100_000, n_devices=n_dev,
                ref_booster=booster, expect=ON_CHIP, logs=logs,
                res=legs["multichip"], clock=clock,
            )
        else:
            legs["multichip"] = {"ran": False, "reason": f"{n_dev} device(s)"}
        out["ok"] = all(leg["ok"] for leg in legs.values() if leg.get("ran", True))
    except BaseException as e:
        # not a catch-and-continue: say what failed on the report line (the
        # caller may only see the tail of the output), then fail
        out["error"] = f"{type(e).__name__}: {e}"[:2000]
        raise
    finally:
        out["warnings"] = logs.warnings[-20:]
        out["wall_s"] = round(time.perf_counter() - t_start, 3)
        out["compile_s"] = round(clock.seconds, 3)
        emit(out, device)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
