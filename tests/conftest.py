"""Test config: make an 8-device virtual CPU mesh available.

Multi-chip hardware isn't available in CI; sharding tests run over
``--xla_force_host_platform_device_count=8`` as the reference's distributed
tests run N CLI processes on localhost (tests/distributed/_test_distributed.py).
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# Run the whole suite on the virtual CPU mesh: correctness tests don't need
# the chip, and serial-vs-sharded comparisons must run on ONE platform so
# reduction-order diffs don't flip tied splits.
# LGBM_TPU_NATIVE=1 keeps the TPU visible instead, expanding the suite with
# the `native_tpu` tier:  LGBM_TPU_NATIVE=1 pytest -m native_tpu
_NATIVE_RUN = os.environ.get("LGBM_TPU_NATIVE") == "1"
if not _NATIVE_RUN:
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

if not _NATIVE_RUN:
    # the env var only counts before jax is first imported; a plugin or an
    # earlier import may have got there first, so set the config too
    jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the suite compiles ~1000+ XLA programs and
# drops its in-memory executables after every module
# (_free_compiled_programs below); caching compiled artifacts on disk brings
# the shared ones back without fresh LLVM work, within a run and on repeat
# runs.  LGBM_TPU_NO_JAX_CACHE=1 opts out.  Where the cache lives is the
# shared helper's decision (JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache).
#
# JAX's default thresholds apply (entries of any size, compile time >= 1 s):
# under the virtual 8-device platform, tiny entries written by one process
# occasionally deserialize into corrupted executables in a second process
# (observed as NaN scores from a donated scatter-add that is byte-correct
# when compiled fresh).  Big entries carry the warm-start value and read
# back cleanly.
if not os.environ.get("LGBM_TPU_NO_JAX_CACHE"):
    from lightgbm_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy / multi-process tests — the default tier is "
        "`-m 'not slow'` (<5 min); run the full suite without the filter",
    )
    config.addinivalue_line(
        "markers",
        "native_tpu: needs a real TPU; run with "
        "`LGBM_TPU_NATIVE=1 pytest -m native_tpu` when hardware is attached",
    )


# Measured-slow tests (round-3 full-suite --durations on the CI CPU): the
# compile-heavy end-to-end combinations.  Every kernel ORACLE (seg sort /
# partition / histogram / forest-walk vs reference semantics), the golden
# parity tests, one consistency example and the serial-vs-sharded equality
# oracle stay in the default tier.  Centralized here so the tier is one
# list, not 40 scattered decorators.
_SLOW_TESTS = {
    "test_consistency.py::test_training_parity_on_example[lambdarank]",
    "test_consistency.py::test_training_parity_on_example[multiclass_classification]",
    "test_consistency.py::test_training_parity_on_example[binary_classification]",
    "test_launcher.py::test_two_process_pre_partition_training",
    "test_launcher.py::test_two_process_psum",
    "test_launcher.py::test_two_process_binning_sync",
    "test_launcher.py::test_two_process_bagging_by_query",
    "test_parallel.py::test_booster_data_parallel_multiclass_valid",
    "test_parallel.py::test_booster_data_parallel_padded_rows",
    "test_parallel.py::test_booster_data_parallel_xentlambda_padded",
    "test_parallel.py::test_booster_data_parallel_bagging_runs",
    "test_booster.py::test_categorical_feature",
    "test_booster.py::test_early_stopping_and_best_iteration_predict",
    "test_booster.py::test_rf",
    "test_booster.py::test_sklearn_classifier",
    "test_monotone.py::test_intermediate_not_worse_than_basic",
    "test_monotone.py::test_advanced_not_worse_than_intermediate",
    "test_monotone.py::test_advanced_monotone_with_path_smooth",
    "test_monotone.py::test_advanced_monotone_with_categoricals",
    "test_dask.py::test_dask_regressor_two_workers_matches_single_process",
    "test_dask.py::test_dask_ranker_groups_not_split",
    "test_dask.py::test_dask_classifier_multiclass",
    "test_monotone.py::test_monotone_property[advanced]",
    "test_codegen.py::test_cpp_codegen_multiclass_softmax",
    "test_codegen.py::test_cpp_codegen_xentlambda_softplus",
    "test_feature_parallel.py::test_feature_parallel_seg_categorical_matches_serial",
    "test_categorical.py::test_e2e_categorical_nan_goes_right",
    "test_categorical.py::test_e2e_categorical_roundtrip_and_consistency",
    "test_categorical.py::test_e2e_categorical_beats_frequency_rank",
    "test_categorical.py::test_mixed_numeric_and_categorical",
    "test_cegb.py::test_coupled_penalty_steers_feature_choice",
    "test_cegb.py::test_coupled_penalty_paid_once_unlocks_feature",
    "test_cegb.py::test_split_penalty_prunes_growth",
    "test_cegb.py::test_huge_coupled_penalty_blocks_feature_entirely",
    "test_api_surface.py::test_booster_utilities",
    "test_api_surface.py::test_sequence_ingestion",
    "test_position_debias.py::test_position_bias_factors_update_and_change_gradients",
    "test_position_debias.py::test_position_none_unchanged",
    "test_histogram_int8.py::test_int8_training_path_matches_segment",
    "test_cv_ranking.py::test_ranking_cv_end_to_end",
    "test_quantized.py::test_quantized_training_close_to_exact[False]",
    "test_quantized.py::test_quantized_training_close_to_exact[True]",
    "test_extra_trees.py::test_extra_trees_randomizes_thresholds_but_learns",
    "test_forced_splits.py::test_root_split_is_forced",
    "test_predict.py::test_loaded_categorical_model_device_walker",
    "test_predict.py::test_pred_early_stop_matches_sequential_reference",
    "test_predict.py::test_pred_early_stop_multiclass_margin",
    "test_observability.py::test_register_logger_redirects_eval_lines",
    "test_voting.py::test_voting_quality_near_data_parallel",
    "test_voting.py::test_voting_trains_and_learns_high_f",
    "test_forest_walk.py::test_forest_walk_many_classes",
    "test_param_combos.py::test_combo_trains_and_roundtrips",
    "test_param_combos.py::test_objective_combos",
    # second-round trims (tier measured 7:30 -> target <5:00); each family
    # keeps a representative in the default tier
    "test_parallel.py::test_booster_data_parallel_matches_serial",
    "test_monotone.py::test_monotone_property[basic]",
    "test_forest_walk.py::test_forest_walk_wide_tree_four_half_lookup",
    "test_forest_walk.py::test_device_binned_walk_matches_slow_path",
    "test_voting.py::test_voting_aliases_to_data_below_cutover",
    "test_device_metrics.py::test_multi_logloss_device_matches_host",
    "test_inspection.py::test_trees_to_dataframe",
    "test_consistency.py::test_cli_train_predict_consistency",
    "test_refit.py::test_refit_changes_leaf_values_toward_new_labels",
    "test_booster.py::test_dart",
    "test_booster.py::test_goss_trains",
    "test_sparse.py::test_sparse_training_matches_dense",
    # bench-scale streaming-prediction A/B (500k rows); the <=5k-row parity
    # tests in test_streaming_predict.py stay tier-1
    "test_streaming_predict.py::test_500k_prediction_ab_chunked_vs_singleshot",
    "test_dask.py::test_dask_distributed_predict_matches_local",
    # round-21 launch-scan battery: each variant keeps its cheaper N in the
    # default tier; the duplicate scan length, the mesh/fleet compositions
    # (also exercised by the perf-gate launch scenario and the
    # tools/run_tests.sh N=1-vs-N=2 smoke) move here
    "test_launch_scan.py::test_launch_parity[2-bagging]",
    "test_launch_scan.py::test_launch_parity[2-bagging_freq2]",
    "test_launch_scan.py::test_launch_parity[2-goss]",
    "test_launch_scan.py::test_launch_parity[2-feature_fraction]",
    "test_launch_scan.py::test_launch_parity[2-extra_trees]",
    "test_launch_scan.py::test_launch_parity[2-multiclass]",
    "test_launch_scan.py::test_launch_parity_mesh_data_parallel",
    "test_launch_scan.py::test_launch_parity_fleet",
}


def pytest_collection_modifyitems(config, items):
    import pytest as _pytest

    on_tpu = False
    if _NATIVE_RUN:
        # asking for the native tier without a TPU is an error, not a skip
        on_tpu = any(d.platform == "tpu" for d in jax.devices())
        if not on_tpu:
            raise _pytest.UsageError(
                "LGBM_TPU_NATIVE=1 asks for the native_tpu tier, but jax "
                f"found no TPU (devices: {jax.devices()})"
            )
    skip_native = _pytest.mark.skip(
        reason="needs a real TPU (set LGBM_TPU_NATIVE=1 with hardware attached)"
    )
    for item in items:
        rel = item.nodeid.split("/")[-1]
        base = rel.split("[")[0]
        if rel in _SLOW_TESTS or base in _SLOW_TESTS:
            item.add_marker(_pytest.mark.slow)
        if "native_tpu" in item.keywords and not on_tpu:
            item.add_marker(skip_native)


@pytest.fixture(autouse=True, scope="module")
def _free_compiled_programs():
    """Drop JAX's executable caches after every test module.

    XLA:CPU holds several memory maps per loaded executable and JAX's
    caches keep every executable alive, so ONE process running the suite
    climbs ~300 maps per test and dies when it reaches ``vm.max_map_count``
    (65,530 here) after ~200 tests — seen as SIGSEGV/SIGABRT inside
    ``backend_compile_and_load`` or the compilation cache, at a suite
    position that moves with every test added before it (measured, PR 22).
    Clearing after every module returns the maps; programs shared across
    modules recompile (or come back from the persistent cache).  Clearing
    only above a map-count threshold was tried and ran slower, not faster."""
    yield
    import gc

    jax.clear_caches()
    gc.collect()


@pytest.fixture
def full_onehot():
    """A context manager inside which the seg histogram kernel resolves to
    its H = 1 form (the stats against the whole one-hot) at every bin
    width, so that the two-digit form can be held against it.  The digits
    are no part of a jitted function's cache key: both edges clear JAX's
    caches."""
    import contextlib

    from lightgbm_tpu.ops.pallas import seg

    @contextlib.contextmanager
    def ctx():
        orig = seg.hist_digits
        seg.hist_digits = lambda bpad: (1, bpad)
        jax.clear_caches()
        try:
            yield
        finally:
            seg.hist_digits = orig
            jax.clear_caches()

    return ctx


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax

    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs
