"""chip_smoke.py's control flow at toy size on CPU (tier-1, seconds).

The script proves the main path on the chip; this file proves the script:
its legs run end to end, it refuses to produce a result off-TPU, the
compile-cache helper obeys ``JAX_COMPILATION_CACHE_DIR``, and a latched
degradation fails the train leg.
"""

import importlib.util
import json
import os
import sys

import pytest

import jax

import lightgbm_tpu as lgb
from lightgbm_tpu.resilience import chaos
from lightgbm_tpu.utils import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SPEC = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(_REPO, "chip_smoke.py")
)
chip_smoke = importlib.util.module_from_spec(_SPEC)
sys.modules["chip_smoke"] = chip_smoke  # dataclasses resolves the module
_SPEC.loader.exec_module(chip_smoke)

# what the default path resolves to on the CPU backend
ON_CPU = chip_smoke.Expect(
    hist_mode="ordered", steps_per_launch=1, predict_path="stream_bin",
    mosaic=False,
)
_TOY = dict(num_leaves=15, max_bin=63, min_data_in_leaf=5, verbosity=-1)


@pytest.fixture
def logs():
    sink = chip_smoke._Logs()
    lgb.register_logger(sink)
    yield sink
    lgb.unregister_logger()


@pytest.fixture
def toy(monkeypatch):
    monkeypatch.setattr(
        chip_smoke, "TRAIN_PARAMS", dict(chip_smoke.TRAIN_PARAMS, **_TOY)
    )
    x, y = chip_smoke.make_data(3500, 28, seed=1)
    return x[:3000], y[:3000], x[3000:], y[3000:]


def test_legs_run_at_toy_size(toy, logs):
    x, y, xv, yv = toy
    legs = {k: {"ok": False} for k in ("train", "predict", "serve", "multichip")}
    booster = chip_smoke.leg_train(
        x, y, xv, yv, rounds_a=5, rounds_b=3, eval_rows=1000, expect=ON_CPU,
        logs=logs, res=legs["train"],
    )
    chip_smoke.leg_predict(
        booster, x, n_rows=2000, n_sample=200, expect=ON_CPU, logs=logs,
        res=legs["predict"],
    )
    chip_smoke.leg_serve(
        booster, x, sizes=(1, 8, 100), logs=logs, res=legs["serve"]
    )
    chip_smoke.leg_multichip(
        x, y, rounds=4, eval_rows=1000, n_devices=jax.device_count(),
        ref_booster=booster, expect=ON_CPU, logs=logs, res=legs["multichip"],
    )
    assert all(leg["ok"] for leg in legs.values()), legs
    assert legs["train"]["train_auc"] > 0.8
    assert legs["predict"]["max_abs_err_vs_host_f64"] <= chip_smoke.WALK_ATOL
    assert legs["serve"]["requests"] == 6
    assert legs["multichip"]["mesh_shape"]["data"] == jax.device_count()
    assert legs["multichip"]["all_reduces"] > 0


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == ""  # no result line from a CPU run
    assert "'cpu'" in err


def test_verdict_is_last_line_with_exact_keys(capsys):
    """The last stdout line is the verdict whose key set the caller checks
    exactly; everything else the run learned rides on the line before."""
    report = {"ok": True, "legs": {"train": {"ok": True}}, "wall_s": 1.0}
    chip_smoke.emit(report, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 2
    assert json.loads(lines[0]) == report
    verdict = json.loads(lines[1])
    assert verdict == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert type(verdict["ok"]) is bool and type(verdict["device"]["count"]) is int


def test_degradation_fails_train_leg(toy, logs, monkeypatch):
    """A fused-step failure latches the fallback and training completes —
    exactly what the smoke run must refuse to call a pass."""
    x, y, xv, yv = toy
    monkeypatch.setitem(chip_smoke.TRAIN_PARAMS, "hist_mode", "seg")
    expect = chip_smoke.Expect(
        hist_mode="seg", steps_per_launch=1, predict_path="stream_bin",
        mosaic=False,
    )
    chaos.force_pallas_raise(at_iteration=1)
    try:
        with pytest.raises(chip_smoke.SmokeFailure, match="degraded"):
            chip_smoke.leg_train(
                x, y, xv, yv, rounds_a=3, rounds_b=2, eval_rows=500,
                expect=expect, logs=logs, res={"ok": False},
            )
    finally:
        chaos.reset()
    assert any("fused Pallas grow step failed" in w for w in logs.warnings)


def test_compile_cache_helper(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: calls.append((k, v))
    )
    # placed from outside: the helper sets nothing
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert calls == []
    # not placed: one fixed directory inside the checkout, every time
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first = compile_cache.use_compile_cache()
    second = compile_cache.use_compile_cache()
    assert first == second == os.path.join(_REPO, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", first)] * 2
