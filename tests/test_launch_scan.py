"""Device-resident boosting (train_steps_per_launch / boosting/launch.py).

The acceptance oracle is BYTE parity: for every eligible config, training
with N>1 iterations fused into one compiled ``lax.scan`` launch must
produce a model dump byte-identical to the N=1 serial loop — across
plain/bagging/GOSS/extra-trees/feature-fraction/multiclass, under
``tree_learner=data`` mesh specs, and composed with ``train_fleet``.  The
second oracle is the compile counter: one train run compiles the scan
executable exactly once (label ``grow/scanN``), proving every launch after
warmup reuses the warm program.  Host-boundary semantics (eval, early
stopping, checkpoints) bucket to launch boundaries; the validator clamps N
to divide every active period.
"""

import os
import re
import tempfile

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import create_booster
from lightgbm_tpu.boosting.launch import (
    clamp_steps,
    launch_ineligible_reason,
    resolve_launch_steps,
)
from lightgbm_tpu.obs.jit import compile_counts_by_label
from lightgbm_tpu.resilience import NumericsError

RNG = np.random.default_rng(0)
N, F = 400, 12
X = RNG.normal(size=(N, F)).astype(np.float32)
Y = (X[:, 0] * 2 + np.sin(3 * X[:, 1]) + RNG.normal(scale=0.1, size=N)).astype(
    np.float32
)
YBIN = (Y > np.median(Y)).astype(np.float32)
YCLS = RNG.integers(0, 3, size=N).astype(np.float32)

BASE = {
    "objective": "regression",
    "num_leaves": 15,
    "learning_rate": 0.1,
    "min_data_in_leaf": 5,
    "verbosity": -1,
    "seed": 7,
}

# configs whose N=1 vs N>1 dumps must be byte-identical
VARIANTS = {
    "plain": {},
    "bagging": {"bagging_fraction": 0.7, "bagging_freq": 1},
    "bagging_freq2": {
        "bagging_fraction": 0.6, "bagging_freq": 2, "bagging_seed": 11,
    },
    "goss": {
        # learning_rate 0.5 -> GOSS warmup of 2 iterations, so N=4 launches
        # cross the warmup boundary INSIDE the scan
        "boosting": "goss", "learning_rate": 0.5,
        "top_rate": 0.3, "other_rate": 0.3,
    },
    "extra_trees": {"extra_trees": True, "extra_seed": 5},
    "feature_fraction": {"feature_fraction": 0.8},
    "multiclass": {"objective": "multiclass", "num_class": 3},
}


def _strip(dump: str) -> str:
    """Mask the config echoes that legitimately differ between the serial
    reference and the launch run (the requested N itself, and throwaway
    checkpoint paths) — every other byte must match."""
    dump = re.sub(r"\[train_steps_per_launch: [^\]]*\]\n?", "", dump)
    dump = re.sub(r"\[checkpoint_(dir|interval): [^\]]*\]\n?", "", dump)
    return dump


def _label_for(name):
    if name == "multiclass":
        return YCLS
    if name == "binary":
        return YBIN
    return Y


def _fit(extra, label=Y, rounds=8, **train_kw):
    p = dict(BASE)
    p.update(extra)
    ds = lgb.Dataset(X, label=label)
    return lgb.train(p, ds, num_boost_round=rounds, **train_kw)


def _dump(extra, label=Y, rounds=8, **train_kw):
    return _strip(_fit(extra, label, rounds, **train_kw).model_to_string())


_REF_CACHE = {}


def _reference(name):
    if name not in _REF_CACHE:
        extra = dict(VARIANTS[name])
        extra["train_steps_per_launch"] = 1
        _REF_CACHE[name] = _dump(extra, _label_for(name))
    return _REF_CACHE[name]


# ------------------------------------------------------------ byte parity


@pytest.mark.parametrize("name", sorted(VARIANTS))
@pytest.mark.parametrize("n", [2, 4])
def test_launch_parity(name, n):
    extra = dict(VARIANTS[name])
    extra["train_steps_per_launch"] = n
    assert _dump(extra, _label_for(name)) == _reference(name)


def test_launch_parity_n8_full_run_is_one_launch():
    # N == num_boost_round: the whole training run is ONE device dispatch
    extra = {"train_steps_per_launch": 8}
    assert _dump(extra) == _reference("plain")


def test_launch_parity_mesh_data_parallel():
    # conftest forces 8 virtual CPU devices; the psums scan inside shard_map
    extra = {"tree_learner": "data", "num_machines": 8}
    ref = _dump({**extra, "train_steps_per_launch": 1})
    for n in (2, 4):
        assert _dump({**extra, "train_steps_per_launch": n}) == ref


def test_launch_parity_fleet():
    def fleet_dumps(n):
        p = dict(BASE)
        p.update({"num_fleet": 3, "seed": 3, "train_steps_per_launch": n,
                  "bagging_fraction": 0.8, "bagging_freq": 1})
        ds = lgb.Dataset(X, label=Y)
        return [
            _strip(b.model_to_string())
            for b in lgb.train_fleet(p, ds, num_boost_round=8)
        ]

    ref = fleet_dumps(1)
    assert fleet_dumps(2) == ref
    assert fleet_dumps(4) == ref


def test_fleet_mixed_boost_from_average_first_round_fallback():
    # regression: a member with boost_from_average OFF forces the fleet's
    # first-round serial fallback; that fallback must be decided BEFORE
    # any earlier member's boost_from_average score mutation, or the
    # serial round re-applies the boost (models_ still empty) and the
    # earlier member's scores are silently doubled.  Same member count /
    # bagging config as test_launch_parity_fleet so the fleet executables
    # stay warm (boost_from_average is host-side prologue work only).
    def dumps(n):
        members = [
            dict(BASE, seed=3 + i, bagging_fraction=0.8, bagging_freq=1,
                 train_steps_per_launch=n,
                 boost_from_average=(i != 2))
            for i in range(3)
        ]
        ds = lgb.Dataset(X, label=Y)
        return [
            _strip(b.model_to_string())
            for b in lgb.train_fleet(members, ds, num_boost_round=8)
        ]

    ref = dumps(1)
    assert dumps(4) == ref


def test_launch_realigns_after_unaligned_init_model():
    # continue training from an init_model whose iteration count is NOT a
    # multiple of launch_n: the loop must dispatch serially until the
    # window start re-aligns, so periodic host work (eval here) fires on
    # exactly the iterations the serial continuation acts on
    Xv = RNG.normal(size=(100, F)).astype(np.float32)
    Yv = (Xv[:, 0] * 2 + np.sin(3 * Xv[:, 1])
          + RNG.normal(scale=0.1, size=100)).astype(np.float32)
    base = dict(BASE, metric="l2", metric_freq=2)
    ds = lgb.Dataset(X, label=Y)
    init = lgb.train(
        dict(base, train_steps_per_launch=1), ds, num_boost_round=3
    )

    def continue_from_init(n):
        fired = []

        def record(env):
            if env.evaluation_result_list:
                fired.append(env.iteration)

        vs = lgb.Dataset(Xv, label=Yv)
        b = lgb.train(
            dict(base, train_steps_per_launch=n), ds, num_boost_round=5,
            valid_sets=[vs], init_model=init, callbacks=[record],
        )
        return fired, _strip(b.model_to_string())

    ref_fired, ref_dump = continue_from_init(1)
    lau_fired, lau_dump = continue_from_init(2)
    assert lau_fired == ref_fired
    assert lau_dump == ref_dump


def test_launch_parity_early_finish_inside_window():
    # a gain ceiling stops boosting mid-window: the scan's finished latch
    # must reproduce the serial stop point and the rolled-back final round
    extra = {
        "num_leaves": 4, "learning_rate": 0.9, "min_data_in_leaf": 300,
        "min_gain_to_split": 5.0,
    }
    ref_b = _fit({**extra, "train_steps_per_launch": 1}, rounds=12)
    lau_b = _fit({**extra, "train_steps_per_launch": 4}, rounds=12)
    assert lau_b.current_iteration() == ref_b.current_iteration()
    assert _strip(lau_b.model_to_string()) == _strip(ref_b.model_to_string())


# ---------------------------------------------- host-boundary semantics


def test_early_stopping_at_launch_boundary():
    Xv = RNG.normal(size=(100, F)).astype(np.float32)
    Yv = (Xv[:, 0] * 2 + np.sin(3 * Xv[:, 1])
          + RNG.normal(scale=0.1, size=100)).astype(np.float32)

    def fit(n):
        extra = {
            "learning_rate": 0.3, "early_stopping_round": 2,
            "metric": "l2", "metric_freq": 2, "train_steps_per_launch": n,
        }
        p = dict(BASE)
        p.update(extra)
        ds = lgb.Dataset(X, label=Y)
        vs = lgb.Dataset(Xv, label=Yv)
        return lgb.train(p, ds, num_boost_round=40, valid_sets=[vs])

    b1, b2 = fit(1), fit(2)
    # eval fires on the same iterations (metric_freq == N), so early stop
    # lands on the same boundary with the same best model after truncation
    assert b2.best_iteration == b1.best_iteration
    assert _strip(b2.model_to_string(num_iteration=b2.best_iteration)) == \
        _strip(b1.model_to_string(num_iteration=b1.best_iteration))


def test_checkpoint_resume_at_launch_boundary():
    extra = {"bagging_fraction": 0.7, "bagging_freq": 1}
    ref = _dump({**extra, "train_steps_per_launch": 1}, rounds=12)
    with tempfile.TemporaryDirectory() as td:
        ckdir = os.path.join(td, "ck")
        ck = {"checkpoint_dir": ckdir, "checkpoint_interval": 4,
              "train_steps_per_launch": 4}
        assert _dump({**extra, **ck}, rounds=12) == ref
        # kill-and-resume: drop the final checkpoint, resume from iter 8
        for f in os.listdir(ckdir):
            if "12" in f:
                os.remove(os.path.join(ckdir, f))
        resumed = _dump({**extra, **ck}, rounds=12, resume_from=ckdir)
        assert resumed == ref


def test_numerics_error_names_launch_window():
    init = np.zeros(N, np.float64)
    init[0] = np.nan
    p = dict(BASE)
    p.update({"check_numerics": True, "train_steps_per_launch": 4})
    ds = lgb.Dataset(X, label=Y, init_score=init)
    with pytest.raises(NumericsError, match=r"launch window \[0, 4\)"):
        lgb.train(p, ds, num_boost_round=8)


# ------------------------------------------------------- compile counter


def test_one_compile_per_scan_length():
    before = dict(compile_counts_by_label())
    _fit({"train_steps_per_launch": 2}, rounds=8)  # 4 launches
    after = compile_counts_by_label()
    assert after.get("grow/scan2", 0) - before.get("grow/scan2", 0) == 1


def test_host_overhead_gauge_populated():
    from lightgbm_tpu.obs.registry import get_session

    ses = get_session()
    ses.reset()
    try:
        b = _fit({"train_steps_per_launch": 2, "telemetry": True}, rounds=8)
        # wall between device dispatches: set at every dispatch after the first
        assert b.telemetry()["gauges"]["train/host_overhead_ms"] >= 0.0
    finally:
        ses.configure(enabled=False)
        ses.reset()
    # the booster keeps no per-dispatch samples of its own (they were for a
    # bench report that is gone): the gauge is the one reading
    assert not [k for k in vars(b) if k.startswith("_host_overhead")]


# ------------------------------------------------------------- validator


def test_clamp_steps_pure():
    assert clamp_steps(8, []) == 8
    assert clamp_steps(8, [4]) == 4
    assert clamp_steps(8, [6]) == 2
    assert clamp_steps(8, [5]) == 1
    assert clamp_steps(8, [4, 6]) == 2
    assert clamp_steps(1, [7]) == 1
    assert clamp_steps(8, [0, -3, 8]) == 8  # inactive periods ignored


def test_config_validation_rejects_bad_values():
    with pytest.raises(ValueError):
        lgb.Config.from_params({"train_steps_per_launch": 0})
    with pytest.raises(ValueError):
        lgb.Config.from_params({"train_steps_per_launch": "sometimes"})


def _booster(extra):
    p = dict(BASE)
    p.update(extra)
    return create_booster(p, lgb.Dataset(X, label=Y))


def test_ineligible_configs_fall_back_to_serial():
    b = _booster({"linear_tree": True, "train_steps_per_launch": 4})
    assert launch_ineligible_reason(b) is not None
    assert resolve_launch_steps(b, has_eval_work=False) == 1
    # and the train entry point still works (serial fallback, same model)
    p = dict(BASE)
    p.update({"linear_tree": True})
    ref = _strip(
        lgb.train({**p, "train_steps_per_launch": 1},
                  lgb.Dataset(X, label=Y), num_boost_round=4
                  ).model_to_string()
    )
    got = _strip(
        lgb.train({**p, "train_steps_per_launch": 4},
                  lgb.Dataset(X, label=Y), num_boost_round=4
                  ).model_to_string()
    )
    assert got == ref


def test_resolve_clamps_to_eval_period():
    b = _booster({"metric_freq": 2, "train_steps_per_launch": 8})
    assert resolve_launch_steps(b, has_eval_work=True) == 2
    # without eval work the period is inactive
    assert resolve_launch_steps(b, has_eval_work=False) == 8


def test_resolve_clamps_to_checkpoint_interval(tmp_path):
    b = _booster({
        "train_steps_per_launch": 8,
        "checkpoint_dir": str(tmp_path), "checkpoint_interval": 6,
    })
    assert resolve_launch_steps(b, has_eval_work=False) == 2


def test_eligible_booster_resolves_requested_n():
    b = _booster({"train_steps_per_launch": 4})
    assert launch_ineligible_reason(b) is None
    assert resolve_launch_steps(b, has_eval_work=False) == 4
