"""Golden parity vs the reference implementation on its own examples.

The goldens under tests/golden/ were generated ONCE by running the REFERENCE
CLI (built from /root/reference with cmake, CPU-only) on
examples/{regression,binary_classification,lambdarank,
multiclass_classification}/train.conf — see tests/golden/generate.py.  Each
golden records the reference's eval trajectory, its trained model file, and
that model's predictions on the example test set.

Tests here assert, WITHOUT needing the reference binary:
  * cross-loading: a reference-trained model file loads into our Booster and
    reproduces the reference's own predictions (tight tolerance — this is
    deterministic);
  * training parity: training on the same example data with the example's
    params lands within tolerance of the reference's final train metric
    (loose tolerance — bagging/feature_fraction RNG streams differ by
    design, reference Random vs jax.random).

The reverse cross-load (reference binary loading OUR model file) was
validated manually with the built CLI; it cannot run in CI without the
binary.  Pattern: reference tests/python_package_test/test_consistency.py:67.
"""

import json
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import lightgbm_tpu as lgb  # noqa: E402

GOLDEN = Path(__file__).parent / "golden"
REF_EXAMPLES = Path("/root/reference/examples")

# per-example LOOSE band, used only when the example's own conf engages a
# cross-engine RNG stream (bagging / feature_fraction — reference Random
# vs jax.random draw different subsets by design).  Deterministic confs
# get the tight band below: same data, same binning, same greedy split
# rule must land within 1% (VERDICT item 6).
CASES = {
    "regression": ("regression", "l2", 0.05),
    "binary_classification": ("binary", "binary_logloss", 0.08),
    "lambdarank": ("rank", "ndcg@3", 0.05),
    "multiclass_classification": ("multiclass", "multi_logloss", 0.08),
}
DETERMINISTIC_RTOL = 0.01


def _conf_is_stochastic(conf: dict) -> bool:
    """True when the conf engages any cross-engine RNG stream."""
    ff = float(conf.get("feature_fraction", 1.0))
    bf = float(conf.get("bagging_fraction", 1.0))
    bfreq = int(conf.get("bagging_freq", 0))
    return (
        ff < 1.0
        or (bfreq > 0 and bf < 1.0)
        or conf.get("boosting", "gbdt") in ("dart", "goss", "rf")
        or float(conf.get("pos_bagging_fraction", 1.0)) < 1.0
        or float(conf.get("neg_bagging_fraction", 1.0)) < 1.0
    )


def _parse_conf(path: Path) -> dict:
    params = {}
    for line in path.read_text().splitlines():
        line = line.split("#", 1)[0].strip()
        if "=" in line:
            k, v = line.split("=", 1)
            params[k.strip()] = v.strip()
    return params


def _load_example(name: str, stem: str):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import _load_text_file

    d = REF_EXAMPLES / name
    cfg = Config.from_params({})
    tr = _load_text_file(str(d / f"{stem}.train"), cfg)
    te = _load_text_file(str(d / f"{stem}.test"), cfg)

    def _dense(m, width):
        if hasattr(m, "toarray"):
            m = m.toarray()
            if m.shape[1] < width:
                m = np.pad(m, ((0, 0), (0, width - m.shape[1])))
        return np.asarray(m, dtype=np.float64)

    width = max(
        tr["data"].shape[1], te["data"].shape[1]
    )
    out = {
        "X": _dense(tr["data"], width),
        "y": np.asarray(tr["label"]),
        "Xt": _dense(te["data"], width),
        "yt": np.asarray(te["label"]),
    }
    q = d / f"{stem}.train.query"
    if q.exists():
        out["group"] = np.loadtxt(q, dtype=np.int64, ndmin=1)
    qt = d / f"{stem}.test.query"
    if qt.exists():
        out["group_t"] = np.loadtxt(qt, dtype=np.int64, ndmin=1)
    return out


@pytest.mark.skipif(not REF_EXAMPLES.exists(), reason="reference not mounted")
@pytest.mark.parametrize("name", list(CASES))
def test_reference_model_cross_loads(name):
    """Reference model file -> our Booster -> reference's own predictions."""
    stem, _, _ = CASES[name]
    model_file = GOLDEN / f"{name}.model.txt"
    preds_file = GOLDEN / f"{name}.preds.txt"
    if not model_file.exists():
        pytest.skip("goldens not generated")
    ex = _load_example(name, stem)
    booster = lgb.Booster(model_str=model_file.read_text())
    want = np.loadtxt(preds_file, dtype=np.float64, ndmin=1)
    got = booster.predict(ex["Xt"])
    if got.ndim == 2:  # multiclass: reference prints one row per sample
        want = want.reshape(got.shape)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.skipif(not REF_EXAMPLES.exists(), reason="reference not mounted")
@pytest.mark.parametrize("name", list(CASES))
def test_training_parity_on_example(name):
    """Our training on the example data reaches the reference's final train
    metric within tolerance."""
    stem, metric, rtol = CASES[name]
    evals_file = GOLDEN / f"{name}.evals.json"
    if not evals_file.exists():
        pytest.skip("goldens not generated")
    evals = json.loads(evals_file.read_text())
    ref_key = next(k for k in evals if k.endswith(metric))
    ref_final = evals[ref_key][-1][1]

    conf = _parse_conf(REF_EXAMPLES / name / "train.conf")
    if not _conf_is_stochastic(conf):
        # deterministic pipeline end to end -> tight band (VERDICT item 6)
        rtol = min(rtol, DETERMINISTIC_RTOL)
    ex = _load_example(name, stem)
    params = {
        k: v
        for k, v in conf.items()
        if k
        not in (
            "task",
            "data",
            "valid_data",
            "output_model",
            "is_training_metric",
            "metric_freq",
            "label_column",
        )
    }
    params["verbosity"] = -1
    num_rounds = int(params.pop("num_trees", 100))
    d = lgb.Dataset(ex["X"], ex["y"], group=ex.get("group"))
    ev = {}
    lgb.train(
        params,
        d,
        num_boost_round=num_rounds,
        valid_sets=[d],
        valid_names=["training"],
        callbacks=[lgb.record_evaluation(ev)],
    )
    metric_key = next(k for k in ev["training"] if k == metric or metric in k)
    ours_final = ev["training"][metric_key][-1]
    is_higher_better = metric.startswith("ndcg") or metric == "auc"
    if is_higher_better:
        assert ours_final >= ref_final * (1 - rtol), (ours_final, ref_final)
    else:
        assert ours_final <= ref_final * (1 + rtol), (ours_final, ref_final)


def test_forcedbins_golden_parity():
    """Forced bin bounds vs the reference CLI on identical data: the
    reference's model (trained with forcedbins_filename) cross-loads and
    reproduces its predictions, our forced-bins training splits at the
    same forced thresholds, and final train l2 matches within tolerance
    (fixtures from tests/golden/generate_forcedbins.py)."""
    model_file = GOLDEN / "forcedbins.model.txt"
    if not model_file.exists():
        pytest.skip("forced-bins goldens not generated")
    arr = np.loadtxt(GOLDEN / "forcedbins.train.csv", delimiter=",")
    y, X = arr[:, 0], arr[:, 1:]
    # cross-load: reference model + its own predictions
    ref = lgb.Booster(model_str=model_file.read_text())
    want = np.loadtxt(GOLDEN / "forcedbins.preds.txt", ndmin=1)
    np.testing.assert_allclose(ref.predict(X), want, rtol=1e-4, atol=1e-5)
    # the reference's feature-0 split thresholds honor the forced bounds:
    # every 1.25-adjacent threshold IS a forced bound
    params = {
        "objective": "regression", "learning_rate": 0.2, "num_leaves": 8,
        "max_bin": 16, "min_data_in_leaf": 20, "verbosity": -1,
        "forcedbins_filename": str(GOLDEN / "forcedbins.bounds.json"),
    }
    ds = lgb.Dataset(X, y, params=params)
    b = lgb.train(params, ds, 8)
    ub0 = ds.bin_mappers[0].bin_upper_bound
    for forced in (-3.0, 1.25, 2.5):
        assert forced in ub0
    # both engines must find the step at the forced 1.25 boundary: compare
    # the feature-0 thresholds used by the first tree
    def _f0_thresholds(booster):
        s = booster.model_to_string()
        tree0 = s.split("Tree=1")[0]
        feats, thrs = None, None
        for line in tree0.splitlines():
            if line.startswith("split_feature="):
                feats = [int(t) for t in line.split("=")[1].split()]
            if line.startswith("threshold="):
                thrs = [float(t) for t in line.split("=")[1].split()]
        return {t for f, t in zip(feats, thrs) if f == 0}
    ours, refs = _f0_thresholds(b), _f0_thresholds(ref)
    assert 1.25 in refs and 1.25 in ours
    # training quality parity on the same data/params
    mse_ref = float(np.mean((ref.predict(X) - y) ** 2))
    mse_ours = float(np.mean((b.predict(X) - y) ** 2))
    assert mse_ours <= mse_ref * 1.05, (mse_ours, mse_ref)


# scenario names only; the FULL per-scenario params travel WITH the
# fixtures (scen_<name>.params.json, written by generate_scenarios.py
# from its single SCENARIOS table) so regenerating goldens can never
# desync the test's training configuration
_SCENARIO_NAMES = [
    "cegb", "goss", "monotone_advanced", "monotone_basic", "quantized",
    "widebin", "obj_tweedie", "obj_poisson", "obj_quantile", "obj_huber",
    "obj_gamma", "obj_fair", "obj_mape", "obj_l1", "dart", "bagging",
    "obj_xentropy", "obj_xentlambda", "weighted", "interaction",
    "forcedsplits", "categorical", "linear", "bundle",
]


@pytest.mark.parametrize("name", _SCENARIO_NAMES)
def test_scenario_golden_parity(name):
    """Feature-scenario goldens (tests/golden/generate_scenarios.py): the
    reference's model cross-loads bit-consistently, and our training with
    the same feature engaged reaches the reference's final train metric
    (the scenario's own metric, from its params.json) within tolerance.
    Covers monotone (basic+advanced), CEGB, quantized gradients,
    max_bin=1024, GOSS, and the tweedie/poisson/quantile/huber objective
    families against the reference's own runs."""
    model_file = GOLDEN / f"scen_{name}.model.txt"
    if not model_file.exists():
        pytest.skip("scenario goldens not generated")
    arr = np.loadtxt(GOLDEN / f"scen_{name}.train.csv", delimiter=",")
    y, X = arr[:, 0], arr[:, 1:]
    ref = lgb.Booster(model_str=model_file.read_text())
    want = np.loadtxt(GOLDEN / f"scen_{name}.preds.txt", ndmin=1)
    np.testing.assert_allclose(ref.predict(X), want, rtol=1e-4, atol=1e-5)
    params = json.loads((GOLDEN / f"scen_{name}.params.json").read_text())
    params["verbosity"] = -1
    rounds = int(params.pop("num_trees", 10))
    # aux files travel as scen_<name>.<filename>; rewrite path params
    for k, v in list(params.items()):
        if k.endswith("_filename") and v:
            params[k] = str(GOLDEN / f"scen_{name}.{v}")
    metric = params.get("metric", "l2")
    evals = json.loads((GOLDEN / f"scen_{name}.evals.json").read_text())
    ref_key = next(k for k in evals if k.endswith(metric))
    ref_final = evals[ref_key][-1][1]
    wfile = GOLDEN / f"scen_{name}.train.csv.weight"
    weight = np.loadtxt(wfile, ndmin=1) if wfile.exists() else None
    ds = lgb.Dataset(X, y, weight=weight, params=params)
    ev = {}
    b = lgb.train(
        params, ds, rounds, valid_sets=[ds], valid_names=["training"],
        callbacks=[lgb.record_evaluation(ev)],
    )
    metric_key = next(k for k in ev["training"] if metric in k)
    ours_final = ev["training"][metric_key][-1]
    # stochastic modes (goss, quantized, dart drops, bagging draws) run
    # different RNG streams by design and get a wider band; deterministic
    # modes track much closer in practice.  additive-over-|ref| band: all
    # these metrics are lower-is-better but NLL-style ones
    # (poisson/tweedie/gamma) can go NEGATIVE, where a multiplicative
    # bound would invert into a stricter-than-parity test
    rtol = 0.15 if name in ("goss", "quantized", "dart", "bagging") else 0.05
    assert ours_final <= ref_final + rtol * abs(ref_final) + 1e-9, (
        ours_final, ref_final,
    )
    if name == "categorical":
        # both engines must actually have used categorical (bitset) splits
        for bst in (ref, b):
            assert "cat_threshold=" in bst.model_to_string()
    if name == "bundle":
        # EFB must actually have engaged on our side, and both models must
        # speak original-feature space (numeric one-hot thresholds, ids
        # within the raw column count)
        ds.construct()
        assert ds.bundle_layout is not None and ds.bundle_layout.has_bundles
        assert ds.num_planes < len(ds.used_features)
        for bst in (ref, b):
            txt = bst.model_to_string()
            assert "cat_threshold=" not in txt
            for line in txt.splitlines():
                if line.startswith("split_feature="):
                    ids = [int(t) for t in line.split("=")[1].split()]
                    assert all(0 <= i < X.shape[1] for i in ids)
    if name == "forcedsplits":
        # both engines must root at the forced feature 2 with the SAME
        # bin-snapped threshold (both snap the forced 0.5 to the nearest
        # bin upper bound; equal-count binning on identical data agrees)
        roots = []
        for bst in (ref, b):
            tree0 = bst.model_to_string().split("Tree=1")[0]
            feats = thrs = None
            for line in tree0.splitlines():
                if line.startswith("split_feature="):
                    feats = [int(t) for t in line.split("=")[1].split()]
                if line.startswith("threshold="):
                    thrs = [float(t) for t in line.split("=")[1].split()]
            roots.append((feats[0], thrs[0]))
        assert roots[0][0] == roots[1][0] == 2, roots
        assert abs(roots[0][1] - 0.5) < 0.05, roots  # snapped near 0.5
        assert abs(roots[0][1] - roots[1][1]) < 1e-6, roots
    if name.startswith("monotone"):
        # the produced model must actually satisfy the constraints
        rng2 = np.random.default_rng(0)
        base_pts = rng2.normal(size=(200, X.shape[1]))
        for fi, sign in ((0, 1), (1, -1)):
            lo, hi = base_pts.copy(), base_pts.copy()
            lo[:, fi] -= 1.0
            hi[:, fi] += 1.0
            d = b.predict(hi) - b.predict(lo)
            assert (sign * d >= -1e-9).all(), f"constraint violated on f{fi}"


@pytest.mark.parametrize("stem", ["forcedbins", "scen_monotone_basic"])
def test_shap_contrib_golden_parity(stem):
    """TreeSHAP contributions vs the reference CLI's predict_contrib=true
    on the SAME model file — deterministic, so the comparison is tight
    (fixtures from tests/golden/generate_contribs.py; reference analog
    src/treelearner/../tree.cpp TreeSHAP / pred_contrib)."""
    contribs_file = GOLDEN / f"{stem}.contribs.txt"
    if not contribs_file.exists():
        pytest.skip("contrib goldens not generated")
    arr = np.loadtxt(GOLDEN / f"{stem}.train.csv", delimiter=",")
    X = arr[:500, 1:]
    b = lgb.Booster(model_str=(GOLDEN / f"{stem}.model.txt").read_text())
    want = np.loadtxt(contribs_file, delimiter="\t", ndmin=2)
    got = b.predict(X, pred_contrib=True)
    assert got.shape == want.shape  # [n, F+1] incl. the expected-value col
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # contributions must sum to the raw prediction (SHAP identity)
    raw = b.predict(X, raw_score=True)
    np.testing.assert_allclose(got.sum(axis=1), raw, rtol=1e-6, atol=1e-6)


def test_refit_golden_parity():
    """Booster.refit vs the reference CLI's task=refit on the same model
    and data (reference GBDT::RefitTree; deterministic, so leaf values
    compare tightly — fixtures from tests/golden/generate_refit.py)."""
    model_file = GOLDEN / "refit.model.txt"
    if not model_file.exists():
        pytest.skip("refit goldens not generated")
    arr = np.loadtxt(GOLDEN / "refit.refit.csv", delimiter=",")
    y2, X = arr[:, 0], arr[:, 1:]
    b = lgb.Booster(model_str=model_file.read_text())
    ours = b.refit(X, y2, decay_rate=0.9)
    ref = lgb.Booster(
        model_str=(GOLDEN / "refit.refit_model.txt").read_text()
    )

    def _leaf_values(booster):
        vals = []
        for line in booster.model_to_string().splitlines():
            if line.startswith("leaf_value="):
                vals.extend(float(t) for t in line.split("=")[1].split())
        return np.asarray(vals)

    lv_ours, lv_ref = _leaf_values(ours), _leaf_values(ref)
    assert lv_ours.shape == lv_ref.shape
    np.testing.assert_allclose(lv_ours, lv_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        ours.predict(X), ref.predict(X), rtol=1e-5, atol=1e-6
    )


def test_position_debias_golden_parity():
    """Unbiased lambdarank vs the reference on the same data + .position
    sidecar (reference Metadata::LoadPositions + RankingObjective position
    bias factors): their model cross-loads, the .position sidecar loads
    through our text path, and our final train ndcg@3 lands within
    tolerance of the reference's trajectory."""
    model_file = GOLDEN / "position.model.txt"
    if not model_file.exists():
        pytest.skip("position goldens not generated")
    evals = json.loads((GOLDEN / "position.evals.json").read_text())
    ref_ndcg = evals["training:ndcg@3"][-1][1]
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import _load_text_file

    loaded = _load_text_file(str(GOLDEN / "position.train.csv"),
                             Config.from_params({}))
    X, y = np.asarray(loaded["data"]), np.asarray(loaded["label"])
    assert loaded.get("position") is not None  # sidecar picked up
    ref = lgb.Booster(model_str=model_file.read_text())
    assert np.isfinite(ref.predict(X)).all()
    params = {
        "objective": "lambdarank", "learning_rate": 0.15, "num_leaves": 31,
        "min_data_in_leaf": 10, "verbosity": -1, "metric": "ndcg",
        "eval_at": [3], "lambdarank_position_bias_regularization": 0.5,
    }
    ds = lgb.Dataset(str(GOLDEN / "position.train.csv"), params=params)
    # Train with the persistent compilation cache OFF.  The 3/8
    # "flake" this test had was never model nondeterminism: with the cache
    # off, the trained model dump is bit-identical across PYTHONHASHSEED
    # values and device counts.  The cache directory the suite shares
    # (conftest.py: JAX_COMPILATION_CACHE_DIR or <checkout>/.jax_cache) may
    # also be written by non-suite processes (smokes, debug shells) under
    # other XLA topologies, and certain
    # cache states serve this test's lambdarank programs an executable
    # whose scores go NON-FINITE (observed: booster._score NaN, trees stop
    # growing, ndcg frozen ~0.63-0.84).  Which entry gets hit varies with
    # PYTHONHASHSEED via jaxpr-metadata ordering in the cache key — hence
    # the intermittent look.  Compiling this test's programs from scratch
    # (~3 s) makes the quality bar deterministic again: the cache is switched
    # off for the test, not moved — only use_compile_cache() places a cache.
    import jax
    from jax.experimental.compilation_cache import compilation_cache as _cc

    prev = jax.config.jax_enable_compilation_cache
    try:
        _cc.reset_cache()
        jax.config.update("jax_enable_compilation_cache", False)
        ev = {}
        lgb.train(
            params, ds, 10, valid_sets=[ds], valid_names=["training"],
            callbacks=[lgb.record_evaluation(ev)],
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        _cc.reset_cache()
    key = next(k for k in ev["training"] if "ndcg" in k)
    ours = ev["training"][key][-1]
    assert ours >= ref_ndcg * 0.95, (ours, ref_ndcg)
