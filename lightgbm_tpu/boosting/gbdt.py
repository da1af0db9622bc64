"""GBDT boosting loop and the user-facing Booster.

Reference analogs: ``GBDT`` (src/boosting/gbdt.cpp — Init :59, TrainOneIter
:352, BoostFromAverage :327, UpdateScore :501, EvalAndCheckEarlyStopping
:482), model text IO (src/boosting/gbdt_model_text.cpp), the C-API ``Booster``
wrapper (src/c_api.cpp:166) and the python-package ``Booster``
(python-package/lightgbm/basic.py:3541) rolled into one class — there is no
C ABI layer here; the "native" side is XLA.

Per-iteration device work (all jitted, scores stay in HBM):
  gradients (objectives/) -> per-class grow_tree (ops/grower.py) ->
  score gather-update; valid scores advance by a bin-space tree walk
  (predict.add_tree_to_score).  Host work per iteration is O(num_leaves):
  materializing the tree into the model list (exactly the CUDA learner's
  host/device split, SURVEY §3.5).
"""

from __future__ import annotations

import math
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from ..config import Config
from ..dataset import Dataset
from ..metrics import Metric, create_metric
from ..obs.collectives import collectives_snapshot, measured_summary
from ..obs.device import sample_device_memory
from ..obs.flight import get_flight
from ..obs.health import HealthWatchdog
from ..obs.jit import compile_count as _obs_compile_count
from ..obs.registry import get_session
from ..obs.trace import get_tracer, traced_transfer
from ..objectives import ObjectiveFunction, create_objective
from ..resilience import NumericsError, chaos
from ..obs.jit import instrumented_jit
from ..ops.grower import (
    GrowerParams,
    bag_window_ok,
    cat_mask_width,
    fetch_tree_arrays,
    grow_tree,
    pack_tree_arrays_donated,
    unpack_tree_arrays,
)
from ..ops.score_lookup import leaf_ids_form, leaf_lookup, lookup_form
from ..predict import (
    BinTreeBatch,
    StreamingPredictor,
    _add_tree_to_score_impl,
    add_tree_to_score,
    count_valid_tree,
    row_mesh_of,
    valid_walk_form,
    stack_bin_trees,
    stack_real_trees,
)
from ..tree import Tree

_EPS = 1e-15
_MODEL_VERSION = "v4"


def _tree_score_impl(
    score: jnp.ndarray,  # [K, N] f32
    leaf_value: jnp.ndarray,  # [L] f32, ALREADY shrunk
    leaf_id: jnp.ndarray,  # [N] i32
    kk: jnp.ndarray,  # scalar i32 class row
) -> jnp.ndarray:
    """Train-score update (reference UpdateScore :501): the tree's output
    for every row, looked up without a gather, added into row ``kk``."""
    return score.at[kk].add(leaf_lookup(leaf_value, leaf_id))


def _tree_valid_score_impl(
    score: jnp.ndarray,  # [K, N] f32
    bins: jnp.ndarray,  # [N, F_used]
    nan_bins: jnp.ndarray,  # [F_used]
    split_feature: jnp.ndarray,  # [L-1]
    split_bin: jnp.ndarray,
    default_left: jnp.ndarray,
    left_child: jnp.ndarray,
    right_child: jnp.ndarray,
    leaf_value: jnp.ndarray,  # [L] ALREADY shrunk
    split_is_cat: jnp.ndarray,  # [L-1] bool
    cat_mask: jnp.ndarray,  # [L-1, Bm] bool
    kk: jnp.ndarray,  # scalar i32 class row
    row_mesh=None,  # static: predict.row_mesh_of(bins)
) -> jnp.ndarray:
    """Valid-score update: the new tree scored in bin space and added into
    row ``kk`` of the [K, N] score cache (one entry instead of a
    slice/score/set chain)."""
    new_row = _add_tree_to_score_impl(
        score[kk],
        bins,
        nan_bins,
        split_feature,
        split_bin,
        default_left,
        left_child,
        right_child,
        leaf_value,
        split_is_cat,
        cat_mask,
        row_mesh=row_mesh,
    )
    return score.at[kk].set(new_row)


# The per-iteration loop rebinds the score to the result, so the old cache is
# donated: it goes back to the allocator instead of coexisting with its
# successor for a full [K, N] f32.  The pipelined loop keeps the old caches
# as its snapshots (an iteration dispatched after training finished must
# leave no trace), so its entries (``_kept``) donate nothing.
_apply_tree_score = instrumented_jit(
    _tree_score_impl, label="_apply_tree_score", donate_argnums=(0,)
)
_apply_tree_score_kept = instrumented_jit(
    _tree_score_impl, label="_apply_tree_score_kept"
)
_apply_tree_valid_score = instrumented_jit(
    _tree_valid_score_impl, label="_apply_tree_valid_score",
    donate_argnums=(0,), static_argnames=("row_mesh",),
)
_apply_tree_valid_score_kept = instrumented_jit(
    _tree_valid_score_impl, label="_apply_tree_valid_score_kept",
    static_argnames=("row_mesh",),
)


def _ceil_pow2(x: int) -> int:
    return max(1, 1 << (int(x) - 1).bit_length())


class _EvalEntry:
    """Per-dataset eval state: device bins + score, metrics."""

    def __init__(self, name: str, dataset: Dataset, metrics: List[Metric]):
        self.name = name
        self.dataset = dataset
        self.metrics = metrics
        self.score: Optional[jnp.ndarray] = None  # [K, N(+pad)]
        self.dev_bins = None  # row-sharded over the booster mesh when set
        self.pad = 0  # mesh row padding of score/dev_bins

    @property
    def bins(self) -> jnp.ndarray:
        if self.dev_bins is None:
            return self.dataset.device_bins()
        return self.dev_bins


# forest-walk predict feed size; module-level so tests can shrink it to
# exercise the multi-chunk lookahead drain without 1M+ rows
_PREDICT_CHUNK = 1 << 20
# run the forest-walk kernel in Pallas interpret mode off-TPU (tests only:
# covers the chunked feed + device-binning pipeline without hardware)
_WALK_INTERPRET = False


class Booster:
    """LightGBM-compatible Booster (train + predict + model IO)."""

    def __init__(
        self,
        params: Optional[Dict[str, Any]] = None,
        train_set: Optional[Dataset] = None,
        model_file: Optional[str] = None,
        model_str: Optional[str] = None,
    ) -> None:
        self.params: Dict[str, Any] = dict(params or {})
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._pending: Optional[dict] = None  # async tree fetch in flight
        self._finished = False  # no-more-splits latch (pipelined path)
        self.models_: List[Tree] = []
        self._bin_records: List[Optional[dict]] = []  # bin-space mirror per tree
        self.train_set: Optional[Dataset] = None
        self._valid: List[_EvalEntry] = []
        self._iter = 0
        self.objective: Optional[ObjectiveFunction] = None
        self.feature_names: List[str] = []
        self.feature_infos: List[str] = []
        self.max_feature_idx = -1
        self.label_idx = 0
        self.num_class = 1
        self.num_tree_per_iteration = 1
        self.average_output = False
        self._loaded_params_str = ""
        self.config = Config.from_params(self.params)
        self.pandas_categorical = None
        self._stack_cache: Dict[Any, BinTreeBatch] = {}
        # bumped on EVERY models_/_bin_records mutation (append, pop, DART
        # renormalize, merge) so _stacked_bins never serves a stale batch
        # after rollback-then-retrain at the same tree count
        self._model_version = 0

        if model_file is not None:
            with open(model_file) as f:
                self._load_model_string(f.read())
            if self.config.pred_aot_compile:
                self.compile_predict()
            return
        if model_str is not None:
            self._load_model_string(model_str)
            if self.config.pred_aot_compile:
                self.compile_predict()
            return
        if train_set is None:
            raise ValueError("Booster needs train_set, model_file, or model_str")
        self._init_train(train_set)

    # ------------------------------------------------------------- pipelining
    # A device-to-host fetch blocks the host until the device is done, where
    # the reference pays nothing (in-process C++).  The pipelined update
    # path therefore copies the packed tree arrays back ASYNCHRONOUSLY and
    # materializes host Trees one iteration late, overlapping the transfer
    # with the next iteration's device compute.  models_/_bin_records are properties so ANY reader first
    # drains the in-flight fetch — host state is always consistent.

    @property
    def models_(self) -> List[Tree]:
        self._drain_pending()
        return self._models_store

    @models_.setter
    def models_(self, value: List[Tree]) -> None:
        self._models_store = value

    @property
    def _bin_records(self) -> List[Optional[dict]]:
        self._drain_pending()
        return self._bin_records_store

    @_bin_records.setter
    def _bin_records(self, value: List[Optional[dict]]) -> None:
        self._bin_records_store = value

    def _drain_pending(self) -> None:
        pend = getattr(self, "_pending", None)
        if pend is None:
            return
        self._pending = None
        self._process_pending(pend)

    def _process_pending(self, pend: dict) -> None:
        tracer = get_tracer()
        # the one place the pipelined loop blocks on the device: the packed
        # arrays of the tree dispatched an iteration ago
        with tracer.span("wait/fetch_tree", phase="host_materialize"):
            fetched = [
                None if ints_d is None
                else (np.asarray(ints_d), np.asarray(floats_d))
                for _kk, ints_d, floats_d, _nn, _L in pend["classes"]
            ]
        with tracer.span("train/host_tree", phase="host_materialize"):
            self._materialize_pending(pend, fetched)

    def _materialize_pending(self, pend: dict, fetched: list) -> None:
        decoded = []
        should_continue = False
        for (kk, _i, _f, nn, L), host in zip(pend["classes"], fetched):
            if host is None:
                decoded.append((kk, None))
                continue
            ta_host = unpack_tree_arrays(host[0], host[1], nn, L)
            if self.config.check_numerics:
                self._guard_tree(ta_host, pend.get("iter", self._iter - 1))
            if int(ta_host.num_leaves) > 1:
                should_continue = True
                self._note_commit_rate(ta_host)
            self._note_refine_rate(ta_host)
            decoded.append((kk, ta_host))
        if not should_continue:
            # no class found a positive-gain split: the iteration left no
            # trace (leaf values were zeroed on device), undo its counter and
            # latch finished — reference returns is_finished without
            # appending (gbdt.cpp:428)
            self._iter -= 1
            self._finished = True
            return
        for kk, ta_host in decoded:
            if ta_host is not None and int(ta_host.num_leaves) > 1:
                tree = Tree.from_device_arrays(
                    ta_host,
                    self.train_set.bin_mappers,
                    self.train_set.used_features,
                    bundle_layout=self._bundle,
                )
                if self.config.verbosity >= 2:
                    tree.validate()  # debug CHECK paths (tree.py)
                tree.apply_shrinkage(pend["rate"])
                nn = int(ta_host.num_leaves) - 1
                rec = {
                    "split_feature": np.asarray(ta_host.split_feature)[:nn],
                    "split_bin": np.asarray(ta_host.split_bin)[:nn],
                    "default_left": np.asarray(ta_host.default_left)[:nn],
                    "left_child": np.asarray(ta_host.left_child)[:nn],
                    "right_child": np.asarray(ta_host.right_child)[:nn],
                    "leaf_value": np.asarray(tree.leaf_value, dtype=np.float32),
                    "split_is_cat": np.asarray(ta_host.split_is_cat)[:nn],
                    "cat_mask": np.asarray(ta_host.cat_mask)[:nn],
                }
                self._cegb_mark_used(rec["split_feature"])
            else:
                tree = Tree.constant_tree(0.0)
                rec = {
                    "split_feature": np.zeros(0, np.int32),
                    "split_bin": np.zeros(0, np.int32),
                    "default_left": np.zeros(0, bool),
                    "left_child": np.zeros(0, np.int32),
                    "right_child": np.zeros(0, np.int32),
                    "leaf_value": np.asarray(tree.leaf_value, dtype=np.float32),
                }
            self._models_store.append(tree)
            self._bin_records_store.append(rec)
            self._bump_model_version()

    def _note_commit_rate(self, ta_host) -> None:
        """Frontier-batch commit-rate gauge + adaptive leaf_batch clamp.

        commit rate = splits committed / split slots offered
        = (num_leaves - 1) / (grow_steps * K).  Round-8 measured K=8 at
        3.4% SLOWER than serial near the 255-leaf cap: late batched steps
        mostly speculate (partition + histogram work for members whose gain
        an earlier member's children beat).  When the EMA commit rate drops
        below leaf_batch_min_commit_rate the cap halves.  Sticky DOWNWARD
        only: each K owns its own compiled loop, so a cap that oscillated
        would retrace on every flip — halving costs at most log2(K) traces
        per run."""
        k = int(self._grower_params.leaf_batch)
        if k <= 1 or self._mesh is not None:
            # mesh path: grower params are baked into the shard_map closure
            # at _init_train time; fused grow doesn't engage there either
            return
        steps = int(ta_host.grow_steps)
        if steps <= 0:
            return
        rate = (int(ta_host.num_leaves) - 1) / float(steps * k)
        ema = getattr(self, "_commit_rate_ema", None)
        ema = rate if ema is None else 0.7 * ema + 0.3 * rate
        self._commit_rate_ema = ema
        ses = get_session()
        ses.set_gauge("grower.commit_rate", ema)
        ses.set_gauge("grower.leaf_batch_effective", float(k))
        cfg = self.config
        if cfg.leaf_batch_adaptive and ema < cfg.leaf_batch_min_commit_rate:
            self._leaf_batch_cap = max(1, k // 2)
            self._commit_rate_ema = None  # fresh EMA window for the new K
            self._grower_params = self._make_grower_params()
            ses.set_gauge(
                "grower.leaf_batch_effective",
                float(self._grower_params.leaf_batch),
            )
            if self.config.verbosity >= 2:
                from ..utils.log import log_info

                log_info(
                    f"leaf_batch clamp: commit rate {ema:.3f} < "
                    f"{cfg.leaf_batch_min_commit_rate} at K={k}; "
                    f"continuing with K={self._grower_params.leaf_batch}"
                )

    def _note_refine_rate(self, ta_host) -> None:
        """Histogram-engine-v2 gauges from an already-fetched tree: the
        count of committed split decisions that took the int8 near-tie f32
        refine, and its rate over the tree's decisions (root + both
        children per committed split = 2*(num_leaves-1) + 1).  The
        watchdog's refine-rate rule reads the rate gauge."""
        ses = get_session()
        if not ses.enabled or not self._int8_engaged():
            return
        refines = int(ta_host.refine_count)
        decisions = 2 * max(0, int(ta_host.num_leaves) - 1) + 1
        ses.set_gauge("hist/near_tie_refine_rate", refines / decisions)
        ses.inc("hist/near_tie_refines_total", refines)

    def _int8_engaged(self) -> bool:
        """Host mirror of grow_tree's int8-accumulation engage decision
        (every input is a static — see ops.grower.int8_acc_eligible)."""
        from ..ops.grower import int8_acc_eligible

        p = getattr(self, "_grower_params", None)
        if p is None or self.train_set is None:
            return False
        return (
            p.hist_mode == "seg"
            and int(self._bins.shape[1]) > 0
            and int(self.train_set.num_data) > 1
            and int8_acc_eligible(
                p,
                quantized=self.config.use_quantized_grad,
                monotone=self._monotone is not None,
            )
        )

    def _note_live_plane(self, mask_host, f: int) -> None:
        """hist/live_plane_skip_ratio gauge: fraction of seg histogram
        plane groups skipped under this iteration's tree-level feature
        mask.  Pure host numpy (the mask is built host-side), mirroring
        grow_tree's seg_live derivation; skipped when the skip itself
        cannot engage (non-seg mode, feature-parallel shards)."""
        ses = get_session()
        if not ses.enabled:
            return
        p = getattr(self, "_grower_params", None)
        if p is None or p.hist_mode != "seg" or self._featpar:
            return
        from ..ops.grower import live_plane_fraction

        if mask_host is None:
            frac = 1.0  # full mask: every plane group stays live
        else:
            frac = live_plane_fraction(
                mask_host, f, int(p.max_bin), n_forced=int(p.n_forced)
            )
        ses.set_gauge("hist/live_plane_skip_ratio", 1.0 - frac)

    def _update_pipelined(self, grad, hess, mask, feature_mask, k: int) -> bool:
        """Dispatch one iteration's device work; defer host bookkeeping.

        The PREVIOUS iteration's pending fetch is processed AFTER this
        iteration's device work is queued, so the device-to-host transfer
        and host bookkeeping overlap device compute (steady-state wall time
        per iter = max(device tree time, fetch latency))."""
        prev = self._pending
        self._pending = None
        score_snapshot = self._score
        valid_snapshots = [e.score for e in self._valid]
        pend = []
        for kk in range(k):
            if self._class_need_train[kk] and self._bins.shape[1] > 0:
                qg, qh = self._quant_grow_inputs(grad[kk], hess[kk], kk)
                ta, leaf_id = self._grow_one(
                    qg,
                    qh,
                    mask,
                    feature_mask,
                    self._tree_rng(),
                )
                ta = self._quant_renew(ta, leaf_id, grad[kk], hess[kk], mask)
                with get_tracer().span("train/score_update", phase="score_update"):
                    shrunk = ta.leaf_value * self._shrinkage_rate
                    self._score = _apply_tree_score_kept(
                        self._score, shrunk, leaf_id, jnp.int32(kk)
                    )
                    self._score_valid_tree(
                        _apply_tree_valid_score_kept, ta, shrunk, kk
                    )
                    get_session().sync(self._score)
                # ta is dead after the pack (only .shape metadata is read
                # below): donation retires its ~18 buffers at dispatch
                # instead of Python GC.  The concatenated outputs can never
                # alias the inputs, so jax warns "not usable" on the one
                # trace — expected here, silenced to keep training quiet.
                with warnings.catch_warnings():
                    warnings.filterwarnings(
                        "ignore", message="Some donated buffers were not usable"
                    )
                    ints_d, floats_d = pack_tree_arrays_donated(ta)
                ints_d.copy_to_host_async()
                floats_d.copy_to_host_async()
                pend.append(
                    (kk, ints_d, floats_d, ta.split_feature.shape[0], ta.leaf_value.shape[0])
                )
            else:
                pend.append((kk, None, None, 0, 0))
        self._pending = {
            "classes": pend,
            "rate": self._shrinkage_rate,
            "iter": self._iter,
        }
        self._iter += 1
        if prev is not None:
            self._process_pending(prev)
            if self._finished:
                # the previous iteration found no split: training stopped
                # THERE, so the iteration just dispatched must leave no trace
                # — restore the score snapshots and drop it (its gradients
                # could differ under bagging, so zero-contribution is not
                # guaranteed otherwise)
                self._score = score_snapshot
                for e, s in zip(self._valid, valid_snapshots):
                    e.score = s
                self._pending = None
                self._iter -= 1
                return True
        return False

    # ================================================================ training
    def _init_train(self, train_set: Dataset) -> None:
        """Reference: GBDT::Init (src/boosting/gbdt.cpp:59)."""
        if not train_set._constructed:
            # merge booster params the dataset doesn't set itself — the
            # reference pushes train() params into the Dataset before lazy
            # construction (basic.py Dataset._update_params), so e.g.
            # categorical_feature/max_bin passed to train() must bind here
            merged = {**self.params, **train_set.params}
            if merged != train_set.params:
                train_set.params = merged
                train_set.config = type(train_set.config).from_params(merged)
        else:
            # dataset parameters are frozen at construction: a second booster
            # with conflicting binning-relevant params must error, not
            # silently train on the first booster's binning (reference
            # basic.py _update_params "Cannot change {} after constructed";
            # ADVICE r2)
            from ..config import _PARAM_ALIASES

            _frozen = (
                "max_bin", "max_bin_by_feature", "min_data_in_bin",
                "bin_construct_sample_cnt", "use_missing", "zero_as_missing",
                "feature_pre_filter", "pre_partition", "linear_tree",
            )
            dcfg = train_set.config
            # NOTE: train_set.params may already carry the FIRST booster's
            # merged value for these keys, so the comparison must run for
            # every frozen key, against the dataset's bound config value
            for key, val in self.params.items():
                canon = _PARAM_ALIASES.get(key, key)
                if canon in _frozen:
                    bound = getattr(dcfg, canon)
                    new = getattr(type(dcfg).from_params({key: val}), canon)
                    if new != bound:
                        raise ValueError(
                            f"Cannot change {canon} (bound {bound!r} -> "
                            f"requested {new!r}) after the Dataset was "
                            "constructed; build a new Dataset or pass "
                            "free_raw_data=False and call set params before "
                            "construction"
                        )
        train_set.construct()
        self.train_set = train_set
        self._first_step = True  # consumed by the first step's span
        cfg = self.config
        if cfg.telemetry:
            get_session().configure(
                enabled=True,
                sync_timing=cfg.obs_sync_timing,
                sink_path=cfg.telemetry_out,
                device_accounting=cfg.obs_device_accounting,
                measure_collectives=cfg.obs_collectives,
            )
        # live ops plane: the flight ring records the tail of every train
        # run (dump-on-fault lands next to the checkpoint dir when one is
        # configured, else next to the telemetry sink); the watchdog
        # evaluates alert rules once per update
        import os as _os

        fault_dir = cfg.checkpoint_dir or (
            _os.path.dirname(_os.path.abspath(cfg.telemetry_out))
            if cfg.telemetry_out
            else ""
        )
        flight = get_flight()
        flight.reset()  # ring events are per-run; capacity/dir persist
        flight.configure(
            capacity=cfg.flight_capacity,
            fault_dir=fault_dir,
            run_info={
                "objective": cfg.objective,
                "num_leaves": cfg.num_leaves,
                "leaf_batch": cfg.leaf_batch,
                "tree_learner": cfg.tree_learner,
            },
        )
        self._watchdog = HealthWatchdog() if cfg.health_watchdog else None
        self.objective = create_objective(cfg)
        md = train_set.metadata
        n = train_set.num_data

        # ---- distributed: tree_learner data/feature/voting over a device
        # mesh (reference parallel learners, src/treelearner/
        # data_parallel_tree_learner.cpp — parallel/__init__.py documents the
        # psum mapping). Rows are padded to a multiple of the mesh size with
        # weight-0 rows so shards stay equal-sized (static shapes).
        self._mesh = None
        self._pad_rows = 0
        self._multiproc = False  # process-local rows (pre_partition multi-host)
        self._featpar = 0  # feature-parallel shard count (rows replicated)
        self._proc_row_offset = 0
        self._mesh_spec = None
        if cfg.tree_learner in ("data", "feature", "voting"):
            import dataclasses as _dc

            from ..parallel import choose_devices
            from ..parallel.mesh import build_mesh, choose_spec

            devices = choose_devices()
            # named-mesh layout (parallel/mesh.py): the tree_learner maps to
            # a default mesh shape and mesh_layout overrides it — data
            # (rows sharded), feature (features sliced, rows replicated,
            # reference feature_parallel_tree_learner.cpp:37) or hybrid
            # (2-D).  Every shape runs the same jitted grow path.
            layout = cfg.mesh_layout
            if layout == "auto":
                layout = "feature" if cfg.tree_learner == "feature" else "data"
            spec = (
                choose_spec(layout, len(devices), train_set.num_planes)
                if devices is not None
                else None
            )
            if (
                spec is not None
                and spec.data > 1
                and self.objective is not None
                and self.objective.need_query
                # multi-process feeding keeps ALL devices: trimming by the
                # LOCAL row count would leave a mesh spanning processes
                # unevenly (non-uniform sharding); the equal-rows-divisible
                # check below enforces the no-padding invariant instead
                and not (jax.process_count() > 1 and cfg.pre_partition)
            ):
                # ranking rows can't be weight-0 padded: shrink the DATA
                # axis until rows divide it (the feature axis never pads)
                dd = spec.data
                while dd > 1 and n % dd != 0:
                    dd -= 1
                if dd != spec.data:
                    from ..utils.log import log_warning

                    log_warning(
                        f"ranking objective: {n} rows do not divide the "
                        f"{spec.data}-device data axis and query rows "
                        f"cannot be padded; sharding rows over {dd} "
                        "device(s) instead"
                    )
                spec = _dc.replace(spec, data=dd)
            if spec is not None and spec.size > 1:
                self._mesh_spec = spec
                self._mesh = build_mesh(spec, devices)
                self._featpar = spec.feature if spec.feature > 1 else 0
                nproc = jax.process_count()
                if nproc > 1 and cfg.pre_partition and self._featpar:
                    raise ValueError(
                        "feature-sliced mesh layouts need the full data on "
                        "every process (feature_parallel_tree_learner.cpp:37)"
                        " — they cannot combine with pre_partition row "
                        "partitioning; use the pure data layout for "
                        "multi-host training"
                    )
                if nproc > 1 and cfg.pre_partition:
                    # ---- process-local data feeding (reference: each machine
                    # loads only its partition under pre_partition,
                    # src/io/dataset_loader.cpp:210; distributed binning sync
                    # already ran at Dataset.construct).  Every per-row array
                    # is built from LOCAL rows and placed with
                    # make_array_from_process_local_data — no process ever
                    # holds the global matrix.  Local rows are weight-0
                    # padded to a common per-process width so shards stay
                    # equal-sized (static shapes).
                    from jax.experimental import multihost_utils

                    self._multiproc = True
                    if cfg.linear_tree:
                        raise ValueError(
                            "linear_tree is not supported with multi-process "
                            "pre_partition training"
                        )
                    pidx = jax.process_index()
                    nloc_dev = len(
                        [d for d in devices[: spec.size]
                         if d.process_index == pidx]
                    )
                    counts = multihost_utils.process_allgather(
                        np.asarray([n], np.int64)
                    ).reshape(-1)
                    if self.objective is not None and self.objective.need_query:
                        if int(counts.max()) != int(counts.min()) or n % nloc_dev:
                            raise ValueError(
                                "ranking with pre_partition needs equal "
                                "per-process row counts divisible by the "
                                "local device count (queries cannot be "
                                "weight-0 padded)"
                            )
                    lpad = -(-int(counts.max()) // nloc_dev) * nloc_dev
                    self._pad_rows = lpad - n
                    self._proc_row_counts = counts
                    self._proc_row_offset = int(counts[:pidx].sum())
                    self._n_global = int(counts.sum())
                    self._n_dev_global = lpad * nproc
                else:
                    # pad to a multiple of the DATA-axis size — the feature
                    # axis replicates rows, so padding by the total device
                    # count would over-pad any 2-D (or pure-feature) mesh
                    from ..parallel import pad_rows_for

                    self._pad_rows = pad_rows_for(n, self._mesh)
        pad = self._pad_rows
        n_dev = n + pad  # LOCAL device rows (== global when single-process)

        # the objective is initialized on the UNPADDED data so its host-side
        # statistics (class priors, is_unbalance weights, percentiles) are
        # exact; only its per-row DEVICE arrays get padded + mesh-placed below
        if self.objective is not None:
            with get_tracer().span(
                "setup/objective_init", "setup", args={"objective": cfg.objective}
            ):
                if self._multiproc and not self.objective.need_query:
                    # global host statistics (reference: Network::Allreduce inside
                    # ObtainAutomaticInitialScore / label-count sync): gather the
                    # label/weight COLUMNS across processes — O(8 bytes/row),
                    # negligible next to the bin matrix which stays local.  The
                    # per-row device arrays are re-sliced to local rows below.
                    # Ranking objectives skip this: their init statistics are
                    # per-query and queries never straddle processes.
                    from ..parallel import allgather_host_varlen

                    glabel = allgather_host_varlen(np.asarray(md.label))
                    gweight = (
                        allgather_host_varlen(np.asarray(md.weight))
                        if md.weight is not None
                        else None
                    )
                    self._gathered_label = glabel  # reused by pos/neg bagging
                    self.objective.init(glabel, gweight, None, None)
                else:
                    self.objective.init(
                        md.label, md.weight, md.query_boundaries, md.position
                    )
            self.num_class = self.objective.num_class
        else:
            self.num_class = max(1, cfg.num_class)
        self.num_tree_per_iteration = (
            self.objective.num_tree_per_iteration if self.objective else self.num_class
        )
        self.feature_names = list(train_set.feature_names)
        self.feature_infos = [m.feature_info_str() for m in train_set.bin_mappers]
        self.max_feature_idx = train_set.num_total_features - 1
        # recorded category orders (pandas categoricals / Arrow dictionary
        # columns) so predict on a fresh frame remaps codes identically
        self.pandas_categorical = (
            getattr(train_set, "pandas_categorical", None)
            or getattr(train_set, "arrow_categories", None)
        )
        self.average_output = cfg.boosting == "rf"

        k = self.num_tree_per_iteration
        init = np.zeros((k, n_dev), dtype=np.float32)
        if md.init_score is not None:
            isc = np.asarray(md.init_score, dtype=np.float32)
            init[:, :n] += isc.reshape(k, n) if isc.size == k * n else isc.reshape(1, n)
            self._has_init_score = True
        else:
            self._has_init_score = False

        # device data: ONE placement path for every mesh layout, driven by
        # the logical-axis-rule table (parallel/mesh.py AXIS_RULES).  Rows
        # shard over the 'data' axis and replicate over 'feature'; on a
        # pure-feature (1, F) mesh the data axis has size 1, so the same
        # specs degenerate to full replication (pad_rows is 0 there).
        if self._mesh is not None:
            from ..parallel import pad_rows_np, shard_cols, shard_rows

            self._score = traced_transfer("score", lambda: shard_cols(
                init, self._mesh, process_local=self._multiproc
            ))
            self._bins = traced_transfer("bins", lambda: shard_rows(
                pad_rows_np(train_set.bins, pad), self._mesh,
                process_local=self._multiproc,
            ))
            # the objective's per-row device arrays ride the same sharding as
            # the score (zero-padded; padded rows' gradients are zeroed
            # explicitly in _sample — NOT via synthetic weights, which would
            # change semantics for objectives with non-multiplicative weights
            # like cross_entropy_lambda, xentropy_objective.hpp:184)
            if self.objective is not None:
                for holder, name, axis in self.objective.per_row_device_arrays():
                    arr = getattr(holder, name, None)
                    if arr is None:
                        continue
                    a = np.asarray(arr, dtype=np.float32)
                    if self._multiproc and a.shape[axis] == self._n_global:
                        # global-statistics init left global-length arrays on
                        # the objective: keep only this process's rows
                        off = self._proc_row_offset
                        a = np.take(a, np.arange(off, off + n), axis=axis)
                    if pad:
                        widths = [(0, 0)] * a.ndim
                        widths[axis] = (0, pad)
                        a = np.pad(a, widths)
                    place = shard_rows if axis == 0 else shard_cols
                    setattr(holder, name, traced_transfer(
                        "objective." + name,
                        lambda: place(a, self._mesh, process_local=self._multiproc),
                    ))
        else:
            self._score = traced_transfer("score", lambda: jnp.asarray(init))
            self._bins = train_set.device_bins()
        # per-COLUMN operand arrays: with EFB a bin-matrix column is a
        # bundle plane, without it a used feature (dataset plane accessors
        # return the right thing either way)
        self._bundle = getattr(train_set, "bundle_layout", None)
        self._has_bundle = bool(
            self._bundle is not None and self._bundle.has_bundles
        )
        nb = train_set.plane_num_bins()
        self._num_bins = jnp.asarray(nb, dtype=jnp.int32)
        nan_bins = train_set.plane_nan_bins()
        if len(nan_bins) == 0:
            nan_bins = np.array([-1], dtype=np.int32)  # pairs with the dummy column
        self._nan_bins = jnp.asarray(nan_bins)
        isc = train_set.plane_is_cat()
        if len(isc) == 0:
            isc = np.array([False])
        self._has_cat = bool(isc.any())
        self._is_cat = jnp.asarray(isc) if self._has_cat else None
        self._max_bin_padded = _ceil_pow2(int(nb.max()) if len(nb) else 2)
        self._bundle_end = (
            jnp.asarray(self._bundle.bundle_end_array(self._max_bin_padded))
            if self._has_bundle
            else None
        )
        # the Pallas kernels' modules (jax.experimental.pallas and Mosaic
        # behind them) load on a process's first booster: about a second
        # that _make_grower_params' own import would otherwise hide
        with get_tracer().span("setup/kernel_import", "setup"):
            from ..ops.pallas import seg as _seg  # noqa: F401
        self._check_bundle_compat()
        self._setup_constraints()
        self._forced = self._build_forced_splits()
        self._setup_cegb()
        self._grower_params = self._make_grower_params()
        f_used = self._bins.shape[1]
        if self._mesh is not None:
            from ..parallel import shard_rows

            base = np.ones(n_dev, np.float32)
            base[n:] = 0.0
            # rows role: sharded over 'data', replicated over 'feature' —
            # on a pure-feature mesh the data axis is 1, so this IS the
            # old replicate placement
            self._ones_mask = traced_transfer("ones_mask", lambda: shard_rows(
                base, self._mesh, process_local=self._multiproc
            ))
            self._setup_sharded_grower()
        else:
            self._ones_mask = jnp.ones((n,), jnp.float32)
        self._full_feature_mask = jnp.ones((f_used,), bool)
        self._rng = jax.random.PRNGKey(cfg.seed if cfg.seed is not None else 0)
        self._shrinkage_rate = cfg.learning_rate

        from .sampling import create_sample_strategy

        # the sampler draws GLOBAL-width masks (every process runs the same
        # rng program, so the bagging subset is consistent across shards)
        n_sampler = self._n_dev_global if self._multiproc else n_dev
        is_pos = None
        if cfg.pos_bagging_fraction < 1.0 or cfg.neg_bagging_fraction < 1.0:
            if self._multiproc:
                from ..parallel import allgather_host_varlen

                lpad = n_dev
                gl = getattr(self, "_gathered_label", None)
                if gl is None:
                    gl = allgather_host_varlen(np.asarray(md.label))
                gl = gl > 0
                blocks, o = [], 0
                for c in self._proc_row_counts:
                    blocks.append(gl[o : o + int(c)])
                    blocks.append(np.zeros(lpad - int(c), bool))
                    o += int(c)
                ip = np.concatenate(blocks)
            else:
                ip = np.asarray(md.label) > 0
                if pad:
                    ip = np.concatenate([ip, np.zeros(pad, bool)])
            is_pos = traced_transfer("is_pos", lambda: jnp.asarray(ip))
        from .sampling import bagging_is_active

        query_sizes = None
        pad_query_mask = None
        if cfg.bagging_by_query and bagging_is_active(cfg):
            qb = md.query_boundaries
            if qb is not None and self._multiproc:
                # global query-size list in PROCESS-BLOCK order: every
                # process's local queries followed by its block's padding
                # rows as a never-in-bag pseudo-query — all processes build
                # the identical list (allgather), so the shared rng stream
                # yields the same per-query mask everywhere (SPMD)
                from ..parallel import allgather_host_varlen

                local_sizes = np.diff(np.asarray(qb, np.int64))
                gsizes, gcounts = allgather_host_varlen(
                    local_sizes, return_counts=True
                )
                lpad = n_dev  # the per-process padded block width
                sizes, padm, off = [], [], 0
                for p, cq in enumerate(gcounts):
                    block = gsizes[off : off + int(cq)]
                    off += int(cq)
                    sizes.extend(int(s) for s in block)
                    padm.extend([False] * int(cq))
                    blk_pad = lpad - int(block.sum())
                    if blk_pad:
                        sizes.append(blk_pad)
                        padm.append(True)
                query_sizes = np.asarray(sizes, np.int64)
                pad_query_mask = np.asarray(padm, bool)
            elif qb is not None:
                query_sizes = np.diff(np.asarray(qb, np.int64))
        self._sampler = create_sample_strategy(
            cfg, n_sampler, is_pos, query_sizes=query_sizes,
            pad_query_mask=pad_query_mask,
        )
        self._sampler.set_live_count(self._rows_to_sample())
        self._gathered_label = None  # free the init-time global label copy

        # metrics for the training set.  Multi-process pre_partition: metric
        # aggregation across processes is not wired yet — train with
        # metric='none' and evaluate on a loaded model instead (the reference
        # evaluates rank-locally too, metric.cpp is per-machine).
        train_metrics = self._create_metrics()
        if self._multiproc and train_metrics:
            from ..utils.log import log_warning

            log_warning(
                "training metrics are disabled under multi-process "
                "pre_partition training (per-process rows only)"
            )
            train_metrics = []
        self._train_entry = _EvalEntry("training", train_set, train_metrics)
        for m in self._train_entry.metrics:
            m.init(md.label, md.weight, md.query_boundaries)
        self._class_need_train = [
            self.objective.class_need_train(kk) if self.objective else True
            for kk in range(k)
        ]

    def _check_bundle_compat(self) -> None:
        """EFB-bundled datasets reuse the numeric gain path + mask partition;
        modes that reinterpret the column axis per-feature (or per-candidate)
        are not wired through bundle planes — fail with the fix spelled out
        (the grower re-checks statically as a backstop)."""
        if not self._has_bundle:
            return
        cfg = self.config
        conflicts = [
            (
                cfg.monotone_constraints
                and any(v != 0 for v in cfg.monotone_constraints),
                "monotone_constraints",
            ),
            (
                isinstance(cfg.interaction_constraints, str)
                and cfg.interaction_constraints.strip() != ""
                or isinstance(cfg.interaction_constraints, (list, tuple))
                and len(cfg.interaction_constraints) > 0,
                "interaction_constraints",
            ),
            (bool(cfg.forcedsplits_filename), "forcedsplits_filename"),
            (cfg.extra_trees, "extra_trees"),
            (
                cfg.cegb_tradeoff < 1.0
                or cfg.cegb_penalty_split > 0.0
                or bool(cfg.cegb_penalty_feature_coupled),
                "CEGB penalties",
            ),
            (
                cfg.tree_learner in ("feature", "voting"),
                f"tree_learner='{cfg.tree_learner}'",
            ),
        ]
        for bad, what in conflicts:
            if bad:
                raise ValueError(
                    f"{what} is not supported together with EFB feature "
                    "bundling; pass enable_bundle=false in the Dataset "
                    "params to train this configuration"
                )

    def _setup_constraints(self) -> None:
        """Map per-original-feature constraints onto used columns."""
        cfg = self.config
        ds = self.train_set
        used = ds.used_features
        self._monotone = None
        if cfg.monotone_constraints and any(v != 0 for v in cfg.monotone_constraints):
            mc = np.zeros(len(used), dtype=np.int8)
            for ci, j in enumerate(used):
                if j < len(cfg.monotone_constraints):
                    mc[ci] = cfg.monotone_constraints[j]
            self._monotone = jnp.asarray(mc)
        # per-feature gain multipliers (reference feature_contri,
        # feature_histogram.hpp:1445) mapped onto used columns; all-ones is
        # the identity, so only materialize when some entry differs
        self._feature_contri = None
        if cfg.feature_contri and any(v != 1.0 for v in cfg.feature_contri):
            fc = np.ones(len(used), dtype=np.float32)
            for ci, j in enumerate(used):
                if j < len(cfg.feature_contri):
                    fc[ci] = cfg.feature_contri[j]
            self._feature_contri = jnp.asarray(fc)
        self._interaction_sets = None
        ic = cfg.interaction_constraints
        sets: List[List[int]] = []
        if isinstance(ic, str) and ic.strip():
            import re

            for grp in re.findall(r"\[([^\]]*)\]", ic):
                sets.append([int(x) for x in grp.split(",") if x.strip() != ""])
        elif isinstance(ic, (list, tuple)) and ic:
            sets = [list(map(int, g)) for g in ic]
        if sets:
            mat = np.zeros((len(sets), len(used)), dtype=bool)
            orig_to_used = {j: ci for ci, j in enumerate(used)}
            for si, grp in enumerate(sets):
                for j in grp:
                    if j in orig_to_used:
                        mat[si, orig_to_used[j]] = True
            self._interaction_sets = jnp.asarray(mat)

    def _setup_sharded_grower(self) -> None:
        """(Re)build the shard_map'd grower for the current GrowerParams.
        shard_map needs concrete arrays for every operand: dummies stand in
        for the optional ones (statically gated off inside grow_tree)."""
        from ..parallel.mesh import MeshSpec, make_mesh_grow

        f_used = self._bins.shape[1]
        spec = getattr(self, "_mesh_spec", None)
        if spec is None and self._mesh is not None:
            # meshes restored outside the constructor path (tests building
            # boosters by hand) default to the pure-data layout
            spec = MeshSpec("data", data=self._mesh.size)
        self._sharded_grow = make_mesh_grow(
            self._mesh, self._grower_params, spec
        )
        self._mono_arg = (
            self._monotone
            if self._monotone is not None
            else jnp.zeros((f_used,), jnp.int8)
        )
        self._inter_arg = (
            self._interaction_sets
            if self._interaction_sets is not None
            else jnp.ones((1, f_used), bool)
        )
        self._iscat_arg = (
            self._is_cat
            if self._is_cat is not None
            else jnp.zeros((f_used,), bool)
        )
        self._bundle_end_arg = (
            self._bundle_end
            if self._bundle_end is not None
            else jnp.full((1, 1), -1, jnp.int32)  # static no-op dummy
        )
        self._contri_arg = (
            self._feature_contri
            if self._feature_contri is not None
            else jnp.ones((f_used,), jnp.float32)
        )

    def _quant_seed(self) -> np.uint32:
        """The seed word of the rounding draws (ops.quantize.rounding_uniforms)."""
        return np.uint32((self.config.seed or 0) & 0xFFFFFFFF)

    def _quant_grow_inputs(self, grad_k, hess_k, kk: int):
        """Quantized-gradient training (GradientDiscretizer): tree growth
        sees grid-quantized gradients; leaf values are renewed from the true
        ones afterwards when quant_train_renew_leaf.  The launch scan calls
        the same two functions inside its body (boosting/launch.py)."""
        cfg = self.config
        if not cfg.use_quantized_grad:
            return grad_k, hess_k
        from ..ops.quantize import quantize_gradients

        qg, qh, g_scale, h_scale = quantize_gradients(
            grad_k,
            hess_k,
            self._quant_seed(),
            np.int32(self._iter * self.num_tree_per_iteration + kk),
            **self._quant_static(),
        )
        self._quant_scales = (g_scale, h_scale)  # for the integer kernels
        return qg, qh

    def _quant_static(self) -> Dict[str, Any]:
        cfg = self.config
        return dict(
            num_bins=cfg.num_grad_quant_bins,
            stochastic=cfg.stochastic_rounding,
            constant_hessian=bool(
                self.objective is not None and self.objective.is_constant_hessian
            ),
        )

    def _quant_renew(self, ta, leaf_id, grad_k, hess_k, mask):
        """RenewIntGradTreeOutput (gradient_discretizer.cpp:209) on device."""
        cfg = self.config
        if not (cfg.use_quantized_grad and cfg.quant_train_renew_leaf):
            return ta
        from ..ops.quantize import renew_leaf_values

        lv = renew_leaf_values(
            leaf_id,
            grad_k,
            hess_k,
            mask,
            ta.num_leaves,
            self._grower_params.num_leaves,
            cfg.lambda_l1,
            cfg.lambda_l2,
            cfg.max_delta_step,
            measure=self._grower_params.measure_collectives,
        )
        return ta._replace(leaf_value=lv)

    def _quant_scales_arg(self):
        """The scales operand of the sharded grower: this tree's, or None
        for a booster whose gradients are not on the grid (shard_map takes
        the empty subtree; grow_tree engages the integer kernels on any
        scales it is given)."""
        return getattr(self, "_quant_scales", None)

    def _grow_one(self, grad_k, hess_k, mask, feature_mask, rng):
        """Grow one tree: serial grow_tree or the mesh-sharded shard_map path
        (reference: SerialTreeLearner vs DataParallelTreeLearner dispatch,
        src/boosting/gbdt.cpp:59 tree_learner selection)."""
        ses = get_session()
        with get_tracer().span("train/grow", phase="grow", timer="tree/grow"):
            fused = self._mesh is None and bool(self._grower_params.grow_fused)
            try:
                if fused:
                    # fault-injection consult: stands in for a Mosaic
                    # compile/launch failure surfacing at dispatch
                    chaos.maybe_raise_pallas("fused_grow_step", self._iter)
                res = self._grow_one_inner(grad_k, hess_k, mask, feature_mask, rng)
                ses.sync(res)
            except Exception as exc:
                if not fused:
                    raise
                self._degrade_fused(exc)
                res = self._grow_one_inner(grad_k, hess_k, mask, feature_mask, rng)
                ses.sync(res)
            sample_device_memory("grow")
            return res

    def _degrade_fused(self, exc: Exception) -> None:
        """Permanently fall back from the fused Pallas grow step to the
        two-launch XLA composition (the byte-identical correctness oracle)
        after a kernel compile/launch failure.  The latch flips grow_fused
        off in GrowerParams, so the cost is ONE bounded retrace — not a
        retrace storm — and the run completes instead of dying."""
        from ..utils.log import log_warning

        self._grow_fused_disabled = True
        self._grower_params = self._make_grower_params()
        ses = get_session()
        ses.inc("degradations")
        event = {
            "event": "degradation",
            "component": "fused_grow_step",
            "action": "fallback_to_xla_oracle",
            "iter": int(self._iter),
            "error": f"{type(exc).__name__}: {exc}"[:300],
        }
        ses.record(event)
        # the latch is a survivable fault, but the triggering context is
        # exactly what a postmortem needs — dump the flight ring now
        flight = get_flight()
        flight.note_event(event)
        get_tracer().instant(
            "lifecycle/degradation",
            "lifecycle",
            args={
                "component": "fused_grow_step",
                "iter": int(self._iter),
                "error": event["error"],
            },
        )
        flight.dump("degradation")
        log_warning(
            "[resilience] fused Pallas grow step failed "
            f"({type(exc).__name__}); permanently falling back to the "
            "two-launch XLA path for the rest of the run"
        )

    @property
    def degraded(self) -> bool:
        """True once a run-time kernel failure latched this booster onto a
        fallback path (``_degrade_fused``: fused grow step -> two-launch).
        Readable with telemetry off, so a measurement or smoke run can
        refuse a result the fast path did not produce; the flight ring's
        ``degradation`` event carries the cause."""
        return bool(getattr(self, "_grow_fused_disabled", False))

    def _grow_one_inner(self, grad_k, hess_k, mask, feature_mask, rng):
        fn, args, kwargs = self._grow_call(
            grad_k, hess_k, mask, feature_mask, rng
        )
        return fn(*args, **kwargs)

    def _grow_call(self, grad_k, hess_k, mask, feature_mask, rng):
        """The jitted grow entry for this booster and its full operand
        list, as ``(fn, args, kwargs)`` — one place builds it, so what is
        dispatched and what a caller lowers for inspection
        (``fn.lower(*args, **kwargs)``, chip_smoke.py) cannot drift."""
        if self._mesh is not None:
            return self._sharded_grow, (
                self._bins,
                grad_k,
                hess_k,
                mask,
                self._num_bins,
                self._nan_bins,
                feature_mask,
                self._mono_arg,
                self._inter_arg,
                rng if rng is not None else jax.random.PRNGKey(0),
                self._iscat_arg,
                self._forced,
                *self._cegb_args(),
                self._quant_scales_arg(),
                self._bundle_end_arg,
                self._contri_arg,
            ), {}
        kwargs = dict(
            monotone=self._monotone,
            interaction_sets=self._interaction_sets,
            rng=rng,
            is_cat=self._is_cat,
            forced=self._forced,
            quant_scales=self._quant_scales_arg(),
            bundle_end=self._bundle_end,
            feature_contri=self._feature_contri,
        )
        if self._cegb_coupled is not None:
            kwargs.update(
                zip(("cegb_penalty", "cegb_used"), self._cegb_args())
            )
        return grow_tree, (
            self._bins,
            grad_k,
            hess_k,
            mask,
            self._num_bins,
            self._nan_bins,
            feature_mask,
            self._grower_params,
        ), kwargs

    def _setup_cegb(self) -> None:
        """Cost-Effective Gradient Boosting state (reference:
        cost_effective_gradient_boosting.hpp). The coupled per-feature
        penalty applies until a feature is first used ANYWHERE in the model
        (is_feature_used_in_split_ persists across trees); the lazy per-row
        penalty is not supported and warns."""
        cfg = self.config
        used = self.train_set.used_features
        self._cegb_coupled = None
        self._cegb_used = None
        coupled = cfg.cegb_penalty_feature_coupled
        enabled = (
            cfg.cegb_tradeoff < 1.0
            or cfg.cegb_penalty_split > 0.0
            or bool(coupled)
        )
        if cfg.cegb_penalty_feature_lazy:
            from ..utils.log import log_warning

            log_warning(
                "cegb_penalty_feature_lazy is not supported; ignoring"
            )
        if not enabled:
            return
        f_used = max(1, len(used))
        arr = np.zeros(f_used, np.float64)
        if coupled:
            for ci, j in enumerate(used):
                if j < len(coupled):
                    arr[ci] = coupled[j]
        self._cegb_coupled = arr * cfg.cegb_tradeoff
        self._cegb_used = np.zeros(f_used, bool)

    def _cegb_mark_used(self, split_features) -> None:
        if self._cegb_used is not None and len(split_features):
            self._cegb_used[np.asarray(split_features)] = True

    def _cegb_args(self):
        """(penalty, used) operands; concrete dummies when CEGB is off so the
        shard_map operand structure stays fixed (statically gated inside
        grow_tree by use_cegb)."""
        f = self._bins.shape[1]
        if self._cegb_coupled is None:
            return jnp.zeros((f,), jnp.float32), jnp.zeros((f,), bool)
        return (
            jnp.asarray(self._cegb_coupled, jnp.float32),
            jnp.asarray(self._cegb_used),
        )

    def _build_forced_splits(self):
        """forcedsplits_filename JSON -> BFS step arrays in the grower's
        leaf-id convention (step t splits `leaf`; left keeps the id, right
        becomes t+1).  Reference: SerialTreeLearner::ForceSplits
        (serial_tree_learner.cpp:627) — queue-ordered, thresholds quantized
        through the BinMapper like BinThreshold."""
        fn = self.config.forcedsplits_filename
        if not fn:
            return None
        import json as _json
        from collections import deque

        with open(fn) as fp:
            root = _json.load(fp)
        ds = self.train_set
        orig_to_used = {j: ci for ci, j in enumerate(ds.used_features)}
        steps = []  # (leaf, used_feat, bin, is_cat)
        q = deque([(root, 0)])
        max_steps = self.config.num_leaves - 1
        while q and len(steps) < max_steps:
            node, leaf = q.popleft()
            if (
                not isinstance(node, dict)
                or "feature" not in node
                or "threshold" not in node
            ):
                continue
            orig = int(node["feature"])
            if orig not in orig_to_used:
                break  # unused feature: abort remaining (reference warns)
            ci = orig_to_used[orig]
            mapper = ds.bin_mappers[orig]
            if mapper.is_categorical:
                bn = (mapper.cat_to_bin or {}).get(int(node["threshold"]))
                if bn is None:
                    break
                steps.append((leaf, ci, int(bn), True))
            else:
                ub = np.asarray(mapper.bin_upper_bound)
                bn = int(np.searchsorted(ub, float(node["threshold"]), side="left"))
                steps.append((leaf, ci, min(bn, mapper.num_bins - 1), False))
            t = len(steps) - 1
            if "left" in node:
                q.append((node["left"], leaf))
            if "right" in node:
                q.append((node["right"], t + 1))
        if not steps:
            return None
        arr = np.asarray(steps, dtype=np.int64)
        return (
            jnp.asarray(arr[:, 0].astype(np.int32)),
            jnp.asarray(arr[:, 1].astype(np.int32)),
            jnp.asarray(arr[:, 2].astype(np.int32)),
            jnp.asarray(arr[:, 3].astype(bool)),
        )

    def _score_valid_tree(self, entry_fn, ta, shrunk, kk: int) -> None:
        """Add the new tree's (shrunk) outputs to row ``kk`` of every
        validation score through ``entry_fn`` (the donating entry, or the
        pipelined loop's keeping one)."""
        for entry in self._valid:
            count_valid_tree(shrunk, ta.cat_mask)
            entry.score = entry_fn(
                entry.score,
                entry.bins,
                self._nan_bins,
                ta.split_feature,
                ta.split_bin,
                ta.default_left,
                ta.left_child,
                ta.right_child,
                shrunk,
                ta.split_is_cat,
                ta.cat_mask,
                jnp.int32(kk),
                row_mesh=row_mesh_of(entry.bins),
            )

    def _score_span_args(self) -> Dict[str, str]:
        """Which forms the score update takes, for the ``train/iteration``
        and ``train/launch`` spans: ``score_lookup`` (``leaf_value[leaf_id]``
        over the training rows: "onehot" | "gather") and ``valid_walk`` (a
        new tree over a validation set: "contract" | "walk", "none" where no
        device walk runs: no validation set, or linear trees, whose scores
        advance on the host), and ``leaf_ids`` (how a tree grown on the
        segment path gives every row its leaf: "walk" | "segment", "none"
        off that path).  All follow static shapes alone."""
        p = getattr(self, "_grower_params", None)
        if p is None:
            return {}
        # the width the grower gives its trees' cat_mask (the Booster hands
        # it ``is_cat`` / ``bundle_end`` exactly when these flags are set),
        # which is what ``count_valid_tree`` reads off the tree
        width = cat_mask_width(p.use_cat, p.use_bundle, int(p.max_bin))
        if not self._valid or self.config.linear_tree:
            walk = "none"
        else:
            walk = valid_walk_form(int(p.num_leaves), width)
        if p.hist_mode != "seg" or self.train_set is None:
            leaf_ids = "none"
        elif p.bag_window and bag_window_ok(p, width):
            leaf_ids = "walk"
        else:
            leaf_ids = leaf_ids_form(
                int(p.num_leaves), int(self._bins.shape[1]), width,
                int(p.feature_shard),
            )
        return {
            "score_lookup": lookup_form(int(p.num_leaves)),
            "valid_walk": walk, "leaf_ids": leaf_ids,
        }

    def _first_step_args(self) -> Dict[str, bool]:
        """``{"first": True}`` on the first ``train/iteration`` or
        ``train/launch`` of a booster: the span whose ``compile/*`` children
        say why it is long."""
        if self.__dict__.pop("_first_step", False):
            return {"first": True}
        return {}

    def _hist_step(self) -> int:
        """STEP of the histogram kernel at this booster's shapes
        (``seg.hist_step``, the function the kernel's scratch is sized by);
        0 off the segment path."""
        p = getattr(self, "_grower_params", None)
        if p is None or p.hist_mode != "seg" or self.train_set is None:
            return 0
        from ..ops.pallas.seg import (
            hist_bpad, hist_step, hist_sub, padded_rows, plane_groups,
        )

        f = int(self._bins.shape[1]) // max(self._featpar or 1, 1)
        if f <= 0:
            return 0
        wide = p.max_bin > 256
        # the packed matrix a kernel call sees: a shard's rows under
        # tree_learner=data
        shards = (
            self._mesh.size
            if self._mesh is not None and not self._featpar else 1
        )
        return hist_step(
            f, hist_bpad(p.max_bin),
            hist_sub(f, wide, plane_groups(f, wide) > 1),
            padded_rows(-(-int(self._bins.shape[0]) // shards)),
        )

    def _long_step_row_share(self) -> Optional[float]:
        """Of the rows this booster's trees histogrammed — the root's and
        the smaller child's of every split, by the counts of the model it
        holds — the share that lay in whole STEP-row steps of the histogram
        kernel, ``sum (cnt // STEP) * STEP / sum cnt``.  Computed when
        asked, from the trees the host has materialized; None off the
        segment path or before the first tree.  (Under ``tree_learner=data``
        the counts are global where the windows are a shard's.)"""
        step = self._hist_step()
        if not step:
            return None
        rows = long_rows = 0
        for tree in list(self._models_store):
            n = int(tree.num_leaves)
            if n < 2:
                cnts = np.asarray(tree.leaf_count[:1], dtype=np.int64)
            else:
                counts = np.concatenate([
                    np.asarray(tree.internal_count[: n - 1], np.int64),
                    np.asarray(tree.leaf_count[:n], np.int64),
                ])
                # child c >= 0 is node c; c < 0 is leaf ~c, at n - 1 + ~c
                lc = np.asarray(tree.left_child[: n - 1], np.int64)
                rc = np.asarray(tree.right_child[: n - 1], np.int64)
                left = counts[np.where(lc >= 0, lc, n - 1 + ~lc)]
                right = counts[np.where(rc >= 0, rc, n - 1 + ~rc)]
                cnts = np.concatenate(
                    [counts[:1], np.minimum(left, right)])
            rows += int(cnts.sum())
            long_rows += int((cnts // step * step).sum())
        return long_rows / rows if rows else None

    def _seg_span_args(self) -> Dict[str, int]:
        """Plane groups of the packed row and the planes a group, and the
        histogram kernel's two-digit one-hot ("HxL", "1x<bpad>" where it
        resolves to the full one-hot) with the features a matmul takes,
        whether it is the kernel's integer form, and the gradient levels of
        quantized training (0 when off), for the ``train/iteration`` and
        ``train/launch`` spans (none off the segment path)."""
        step = self._hist_step()
        if not step:  # off the segment path
            return {}
        from ..ops.pallas.seg import (
            TILE, group_shape, hist_bpad, hist_digits, hist_feature_block,
            seg_int8_dispatch,
        )

        p = self._grower_params
        f = int(self._bins.shape[1]) // max(self._featpar or 1, 1)
        g, sub = group_shape(f, p.max_bin > 256)
        bpad = hist_bpad(p.max_bin)
        high, low = hist_digits(bpad)
        cfg = self.config
        return {
            "seg_groups": g, "seg_group_planes": sub,
            "hist_digits": f"{high}x{low}",
            "hist_feature_block": hist_feature_block(f, bpad),
            # the rows a step of the histogram kernel contracts: long steps
            # where the window allows, the TILE-row tile for its ragged end
            "hist_step": f"{step}+{TILE}" if step > TILE else str(TILE),
            # which form of the histogram kernel: int8 operands and int32
            # sums (quantized gradients, or hist_acc's int8 grid) or the
            # three-term bf16 split
            "hist_int8": bool(
                (cfg.use_quantized_grad and seg_int8_dispatch())
                or self._int8_engaged()
            ),
            "grad_quant_bins": (
                int(cfg.num_grad_quant_bins) if cfg.use_quantized_grad else 0
            ),
        }

    def _make_grower_params(self) -> GrowerParams:
        from ..ops.split import CatParams
        from .sampling import sampling_is_active

        cfg = self.config
        hist_method = str(self.params.get("hist_method", "auto"))
        # segment-resident mode (streaming partition + histogram kernels,
        # ops/pallas/) is the fast path on TPU: eligible whenever bins fit
        # a byte and the packed row fits 128 i16 lanes; hist_method
        # 'pallas_int8' rides the seg path's own int8 grid kernel (r3).
        # The budget counts bin-matrix COLUMNS — with EFB that is bundle
        # planes, which is exactly how 50k one-hot columns fit the seg path.
        n_used = int(self._bins.shape[1]) if self.train_set else 0
        import jax as _jax

        # the ONE config-time validation for the explicit int8 kernel choice
        # (the ordered path's; the seg path takes the integer kernels for
        # any quantized booster, whatever hist_method says)
        if hist_method.startswith("pallas_int8") and not cfg.use_quantized_grad:
            raise ValueError(
                "hist_method='pallas_int8' needs quantized gradients "
                "(use_quantized_grad=True provides the scales)"
            )

        # bins byte-pack two per i16 plane up to max_bin 256, one u16 plane
        # per feature beyond (the reference's DenseBin<uint16_t> analog,
        # dense_bin.hpp:18).  Width is no limit: a row of more than 128
        # planes is packed as plane groups (ops/pallas/seg.py).  What bounds
        # a table on the seg path is the kernels' VMEM scratch at very wide
        # bins and the device's memory: the packed matrix (2 B a plane a
        # row), the partition's one-group spill and hist_buf [L + 1, 3, F, B].
        from ..ops.pallas.seg import (
            group_shape,
            padded_rows,
            plane_groups,
            seg_vmem_ok,
        )

        # feature-parallel seg: each shard packs only its feature slice, so
        # the VMEM and memory budgets apply to the PER-SHARD feature count
        n_eff = n_used // self._featpar if self._featpar else n_used
        bins_ok = self._max_bin_padded <= 65536
        seg_fits = bins_ok and seg_vmem_ok(
            max(n_eff, 1), self._max_bin_padded, getattr(self, "_has_cat", False)
        )
        seg_bytes = 0
        if bins_ok and n_eff > 0:
            g, sub = group_shape(n_eff, self._max_bin_padded > 256)
            shards = (
                self._mesh.size
                if self._mesh is not None and not self._featpar else 1
            )
            rows = padded_rows(-(-int(self._bins.shape[0]) // shards))
            seg_bytes = (
                (g + 1) * sub * rows * 2
                + (cfg.num_leaves + 1) * 3 * n_eff * self._max_bin_padded * 4
            )
        mem_limit = (_jax.devices()[0].memory_stats() or {}).get("bytes_limit")
        mem_fits = mem_limit is None or seg_bytes <= mem_limit
        seg_ok = (
            seg_fits
            and mem_fits
            and n_eff > 0
            # the seg path has its own kernels: the default bf16 three-term
            # one and (r3) an int8 grid variant for quantized training;
            # other explicit kernel choices keep the ordered path
            # (pallas_int8_interpret stays on the ordered path: the seg
            # dispatcher has no interpret plumbing)
            and hist_method in ("auto", "pallas_int8")
            # off-TPU the seg histogram falls back to a masked full-N pass
            # per split — ordered mode's O(parent segment) wins there
            and _jax.default_backend() == "tpu"
        )
        if (
            not seg_ok
            and not self._featpar
            and _jax.default_backend() == "tpu"
            and hist_method == "auto"
            and n_used > 0
        ):
            # loud fence: no benchmark cell runs the ordered fallback, so
            # its speed on the chip is not measured (PERF.md section 7);
            # benchmark/run.py counts this warning by its opening words
            from ..utils.log import log_warning

            if not bins_ok:
                why = f"max_bin padded to {self._max_bin_padded} > 65536"
                cure = "Consider a smaller max_bin."
            elif not seg_fits:
                why = (
                    f"histogram VMEM scratch at {n_used} features x "
                    f"max_bin {self._max_bin_padded} exceeds the budget"
                )
                cure = "Consider a smaller max_bin."
            else:
                why = (
                    f"the packed rows and hist_buf [num_leaves + 1, 3, "
                    f"features, max_bin] need {seg_bytes / 2**30:.1f} GiB of the "
                    f"device's {mem_limit / 2**30:.1f} GiB"
                )
                cure = (
                    "Consider fewer num_leaves, a smaller max_bin, or "
                    "tree_learner='data' over more chips."
                )
            log_warning(
                "segment-resident training is unavailable: " + why +
                "; falling back to hist_mode='ordered'. " + cure
            )
        hist_mode = str(
            self.params.get(
                "hist_mode",
                "seg" if seg_ok
                else ("gather" if self._featpar else "ordered"),
            )
        )
        # frontier batching scope: modes whose per-split state is not
        # member-local keep the serial loop (grow_tree raises on these at
        # K > 1; downgrade here with a warning instead)
        leaf_k = max(1, int(cfg.leaf_batch))
        if leaf_k > 1:
            inter_mono = (
                self._monotone is not None
                and cfg.monotone_constraints_method
                in ("intermediate", "advanced")
            )
            blockers = [
                (cfg.tree_learner == "voting" and self._mesh is not None,
                 "tree_learner='voting'"),
                (bool(self._featpar), "feature-parallel training"),
                (self._cegb_coupled is not None, "CEGB feature penalties"),
                (inter_mono,
                 "monotone_constraints_method='intermediate'/'advanced'"),
                (self._interaction_sets is not None,
                 "interaction_constraints"),
                (hist_mode == "seg" and bins_ok and n_eff > 0 and plane_groups(
                    n_eff, self._max_bin_padded > 256) > 1,
                 "a packed row of more than one plane group "
                 f"({n_eff} columns)"),
            ]
            why = [what for bad, what in blockers if bad]
            if why:
                from ..utils.log import log_warning

                log_warning(
                    "leaf_batch > 1 does not support "
                    + ", ".join(why)
                    + "; falling back to serial (leaf_batch=1) growth"
                )
                leaf_k = 1
        # remaining-leaf budget: a tree can never commit more than
        # num_leaves - 1 splits, so offering more slots only speculates
        leaf_k = min(leaf_k, max(1, cfg.num_leaves - 1))
        # adaptive commit-rate clamp: a prior tree's low commit rate halved
        # the cap (see _note_commit_rate); sticky for the rest of the run
        cap = getattr(self, "_leaf_batch_cap", None)
        if cap is not None:
            leaf_k = min(leaf_k, cap)
        if cfg.grow_fused == "on":
            grow_fused = True
        elif cfg.grow_fused == "off":
            grow_fused = False
        else:  # 'auto' — on when the seg fast path is active
            grow_fused = hist_mode == "seg"
        if getattr(self, "_grow_fused_disabled", False):
            # a runtime kernel failure latched the XLA fallback
            # (_degrade_fused); the latch survives checkpoint/restore
            grow_fused = False
        # double-buffered histogram collectives: 'auto' engages whenever
        # the frontier batch exists and a mesh is up (the grower further
        # gates on an actual histogram psum axis — see use_overlap); kept
        # False for serial/leaf_batch=1 configs so their trace keys are
        # unchanged
        overlap = (
            cfg.overlap_collectives != "off"
            and leaf_k > 1
            and self._mesh is not None
        )
        return GrowerParams(
            num_leaves=cfg.num_leaves,
            max_bin=self._max_bin_padded,
            hist_mode=hist_mode,
            hist_method=hist_method,
            max_depth=cfg.max_depth,
            min_data_in_leaf=cfg.min_data_in_leaf,
            min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
            lambda_l1=cfg.lambda_l1,
            lambda_l2=cfg.lambda_l2,
            min_gain_to_split=cfg.min_gain_to_split,
            max_delta_step=cfg.max_delta_step,
            path_smooth=cfg.path_smooth,
            use_monotone=self._monotone is not None,
            monotone_method=cfg.monotone_constraints_method,
            # PV-Tree election (ops/grower.voting_active gates on F > 2k —
            # below that the dense psum is exact and cheaper, the documented
            # alias onto tree_learner=data)
            voting_top_k=(
                cfg.top_k
                if (cfg.tree_learner == "voting" and self._mesh is not None)
                else 0
            ),
            feature_shard=self._featpar,
            use_interaction=self._interaction_sets is not None,
            feature_fraction_bynode=cfg.feature_fraction_bynode,
            extra_trees=cfg.extra_trees,
            use_cat=self._has_cat,
            cat_params=CatParams(
                max_cat_to_onehot=cfg.max_cat_to_onehot,
                max_cat_threshold=cfg.max_cat_threshold,
                cat_l2=cfg.cat_l2,
                cat_smooth=cfg.cat_smooth,
                min_data_per_group=cfg.min_data_per_group,
            )
            if self._has_cat
            else None,
            n_forced=0 if self._forced is None else len(self._forced[0]),
            use_cegb=self._cegb_coupled is not None,
            cegb_split_penalty=cfg.cegb_tradeoff * cfg.cegb_penalty_split,
            fused_split_scan=cfg.fused_split_scan,
            use_bundle=self._has_bundle,
            leaf_batch=leaf_k,
            grow_fused=grow_fused,
            overlap_collectives=overlap,
            monotone_penalty=cfg.monotone_penalty,
            use_feature_contri=self._feature_contri is not None,
            # measured collectives only make sense with a mesh; static so the
            # toggle retraces (obs/collectives module docstring)
            measure_collectives=bool(
                cfg.telemetry and cfg.obs_collectives and self._mesh is not None
            ),
            # histogram engine v2: int8-by-default accumulation on the seg
            # TPU path ('auto'/'int8'), near-tie f32 re-accumulate tolerance
            # no say under use_quantized_grad: gradients on the grid take
            # the integer kernels (grow_tree, quant_scales)
            hist_acc=cfg.hist_acc,
            near_tie_tol=cfg.hist_near_tie_tol,
            # trees grown on a subset of the rows (GOSS, bagging, rf, a
            # fixed row mask) get the in-bag window on the segment path;
            # grow_tree gates it further (grower.bag_window_ok).  A booster
            # that samples nothing keeps the program it always traced
            bag_window=(
                hist_mode == "seg"
                and (
                    sampling_is_active(cfg)
                    or getattr(self, "_fixed_row_mask", None) is not None
                )
            ),
        )

    def _fit_linear_leaves(
        self,
        tree: Tree,
        leaf_id: np.ndarray,
        grad: np.ndarray,
        hess: np.ndarray,
        mask: Optional[np.ndarray],
    ) -> None:
        """Fit a linear model per leaf on the path's numerical features
        (reference: LinearTreeLearner::CalculateLinear,
        src/treelearner/linear_tree_learner.cpp:182 — weighted normal
        equations XᵀHX w = -Xᵀg with linear_lambda ridge, Eigen solve;
        NumPy lstsq here — this is per-tree host work like the reference)."""
        ds = self.train_set
        raw = ds.raw if ds.raw is not None else self._raw_for_replay(ds)
        lam = self.config.linear_lambda
        n_leaves = tree.num_leaves
        # path features per leaf from the tree structure
        paths: List[List[int]] = [[] for _ in range(n_leaves)]

        def walk(node: int, feats: List[int]):
            if node < 0:
                paths[~node] = feats
                return
            fsplit = int(tree.split_feature[node])
            is_cat = bool(tree.decision_type[node] & 1)
            nxt = feats if is_cat else feats + [fsplit]
            walk(int(tree.left_child[node]), nxt)
            walk(int(tree.right_child[node]), nxt)

        if n_leaves > 1:
            walk(0, [])
        tree.is_linear = True
        tree.leaf_const = np.array(tree.leaf_value, dtype=np.float64)
        tree.leaf_features = []
        tree.leaf_coeff = []
        sel_all = np.ones(len(leaf_id), bool) if mask is None else mask > 0
        for leaf in range(n_leaves):
            feats = sorted(set(paths[leaf]))
            rows = np.nonzero((leaf_id == leaf) & sel_all)[0]
            if not feats or len(rows) < len(feats) + 1:
                tree.leaf_features.append(np.zeros(0, dtype=np.int32))
                tree.leaf_coeff.append(np.zeros(0))
                continue
            Xl = raw[np.ix_(rows, feats)]
            ok = ~np.isnan(Xl).any(axis=1)
            if ok.sum() < len(feats) + 1:
                tree.leaf_features.append(np.zeros(0, dtype=np.int32))
                tree.leaf_coeff.append(np.zeros(0))
                continue
            Xl = Xl[ok]
            g = grad[rows][ok]
            h = hess[rows][ok]
            design = np.concatenate([Xl, np.ones((len(Xl), 1))], axis=1)
            A = design.T @ (design * h[:, None])
            A[np.arange(len(feats)), np.arange(len(feats))] += lam
            b = -design.T @ g
            try:
                w = np.linalg.solve(A + 1e-10 * np.eye(len(A)), b)
            except np.linalg.LinAlgError:
                tree.leaf_features.append(np.zeros(0, dtype=np.int32))
                tree.leaf_coeff.append(np.zeros(0))
                continue
            if not np.isfinite(w).all():
                tree.leaf_features.append(np.zeros(0, dtype=np.int32))
                tree.leaf_coeff.append(np.zeros(0))
                continue
            tree.leaf_features.append(np.asarray(feats, dtype=np.int32))
            tree.leaf_coeff.append(w[:-1])
            tree.leaf_const[leaf] = w[-1]

    def _create_metrics(self) -> List[Metric]:
        cfg = self.config
        names = cfg.metric if cfg.metric else cfg.default_metric()
        out = []
        for name in names:
            m = create_metric(name, cfg)
            if m is not None:
                out.append(m)
        return out

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if getattr(self, "_multiproc", False):
            raise ValueError(
                "validation sets are not supported under multi-process "
                "pre_partition training; evaluate the saved model per process"
            )
        with get_tracer().span("setup/add_valid", "setup", args={"name": name}):
            return self._add_valid(data, name)

    def _add_valid(self, data: Dataset, name: str) -> "Booster":
        data.construct()
        entry = _EvalEntry(name, data, self._create_metrics())
        md = data.metadata
        for m in entry.metrics:
            m.init(md.label, md.weight, md.query_boundaries)
        k = self.num_tree_per_iteration
        nv = data.num_data
        if self._mesh is not None:
            entry.pad = (-nv) % self._mesh.size
        init = np.zeros((k, nv + entry.pad), dtype=np.float32)
        if md.init_score is not None:
            isc = np.asarray(md.init_score, dtype=np.float32)
            init[:, :nv] += (
                isc.reshape(k, nv) if isc.size == k * nv else isc.reshape(1, nv)
            )
        if self._mesh is not None:
            from ..parallel import pad_rows_np, shard_cols, shard_rows

            entry.score = traced_transfer(
                "valid.score", lambda: shard_cols(init, self._mesh)
            )
            entry.dev_bins = traced_transfer("valid.bins", lambda: shard_rows(
                pad_rows_np(data.bins, entry.pad), self._mesh
            ))
        else:
            entry.score = traced_transfer("valid.score", lambda: jnp.asarray(init))
        # replay existing trees onto the valid score
        vbins = entry.bins
        vraw = None
        for idx, rec in enumerate(self._bin_records):
            k_id = idx % k
            if rec is not None and rec.get("no_bin_form"):
                if vraw is None:
                    vraw = self._raw_for_replay(data)
                entry.score = entry.score.at[k_id].add(
                    self._pad_delta(self.models_[idx].predict(vraw), entry.pad)
                )
                continue
            if rec is None or len(rec["split_feature"]) == 0:
                tree = self.models_[idx]
                entry.score = entry.score.at[k_id].add(float(tree.leaf_value[0]))
                continue
            entry.score = entry.score.at[k_id].set(
                add_tree_to_score(
                    entry.score[k_id],
                    vbins,
                    self._nan_bins,
                    jnp.asarray(rec["split_feature"]),
                    jnp.asarray(rec["split_bin"]),
                    jnp.asarray(rec["default_left"]),
                    jnp.asarray(rec["left_child"]),
                    jnp.asarray(rec["right_child"]),
                    jnp.asarray(np.asarray(self.models_[idx].leaf_value, dtype=np.float32)),
                    *self._rec_cat_args(rec),
                )
            )
        self._valid.append(entry)
        return self

    def _next_rng(self) -> jax.Array:
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _tree_rng(self):
        """Per-tree RNG for feature_fraction_bynode / extra_trees draws.

        An EXPLICIT extra_seed (present in the raw params, reference
        config.h extra_seed) folds into the stream so changing it changes
        the extra-trees thresholds; unset, the stream is untouched and
        training stays byte-identical to the pre-wiring behavior."""
        cfg = self.config
        if not (cfg.feature_fraction_bynode < 1.0 or cfg.extra_trees):
            return None
        rng = self._next_rng()
        if cfg.extra_trees and "extra_seed" in cfg.raw:
            rng = jax.random.fold_in(rng, cfg.extra_seed)
        return rng

    def _bagging_rng(self) -> jax.Array:
        """Row-sampling RNG; an EXPLICIT bagging_seed folds in (reference
        config.h bagging_seed — a distinct deterministic bagging stream),
        unset keeps the historical stream byte-identical."""
        rng = self._next_rng()
        cfg = self.config
        if "bagging_seed" in cfg.raw:
            rng = jax.random.fold_in(rng, cfg.bagging_seed)
        return rng

    @staticmethod
    def _rec_cat_args(rec):
        """(split_is_cat, cat_mask) device args for a bin record; records
        from older model loads may lack them (numeric-only trees)."""
        sic = rec.get("split_is_cat")
        cm = rec.get("cat_mask")
        nn = len(rec["split_feature"])
        if sic is None or cm is None or np.size(cm) == 0:
            return jnp.zeros((nn,), bool), jnp.zeros((nn, 1), bool)
        return jnp.asarray(sic), jnp.asarray(cm)

    @staticmethod
    def _pad_delta(delta, pad: int) -> jnp.ndarray:
        """Pad a real-space [N] per-row score delta to the mesh row width."""
        from ..parallel import pad_rows_np

        return jnp.asarray(pad_rows_np(np.asarray(delta, dtype=np.float32), pad))

    def _get_gradients(self):
        """Objective gradients in the GLOBAL score sharding.

        Elementwise objectives run straight on the sharded score.  Ranking
        objectives under multi-process feeding are per-query and queries
        never straddle processes (the init contract at _init_train), so
        each process computes gradients on its LOCAL score columns and the
        results are reassembled into the global sharded array from local
        device buffers — no host round trip of the global matrix
        (reference: rank_objective gradients are rank-local too; the
        Allreduce happens later on histograms)."""
        if not (self._multiproc and self.objective.need_query):
            return self.objective.get_gradients(self._score, self._next_rng())
        import jax as _jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        shards = sorted(
            self._score.addressable_shards,
            key=lambda s: s.index[1].start or 0,
        )
        # per-device shards -> one host-local [K, lpad] block (small: the
        # score column slice of this process only)
        local = np.concatenate([np.asarray(s.data) for s in shards], axis=1)
        n = self.train_set.num_data  # local unpadded rows
        g, h = self.objective.get_gradients(
            jnp.asarray(local[:, :n]), self._next_rng()
        )
        lpad = local.shape[1]
        if lpad > n:
            z = jnp.zeros((g.shape[0], lpad - n), g.dtype)
            g = jnp.concatenate([g, z], axis=1)
            h = jnp.concatenate([h, z], axis=1)
        pidx = _jax.process_index()
        # mesh devices along the data axis, this process's block (process
        # blocks are contiguous: the mesh is built from jax.devices())
        mine = [
            d for d in self._mesh.devices.flat if d.process_index == pidx
        ]
        chunk = lpad // len(mine)
        sh = NamedSharding(self._mesh, P(None, "data"))
        gshape = (g.shape[0], self._n_dev_global)

        def _assemble(a):
            pieces = [
                _jax.device_put(a[:, i * chunk : (i + 1) * chunk], d)
                for i, d in enumerate(mine)
            ]
            return _jax.make_array_from_single_device_arrays(
                gshape, sh, pieces
            )

        return _assemble(g), _assemble(h)

    def _objective_name(self) -> str:
        if self.objective is not None:
            return type(self.objective).__name__
        return str(self.params.get("objective", "custom"))

    def _fault_dump(self, reason: str) -> str:
        """Black-box the run before a numerics abort: register a critical
        alert (so the dump carries it and ``health()`` reflects it), then
        atomically write the flight ring next to the checkpoint dir.
        Returns the dump path ("" when no fault_dir is configured)."""
        ses = get_session()
        ses.inc("numerics/guard_trips")
        flight = get_flight()
        it = int(self._iter)
        wd = getattr(self, "_watchdog", None)
        if wd is not None:
            alert = wd.note_fault("numerics", it, reason, ses=ses)
        else:
            alert = {
                "event": "alert", "rule": "numerics",
                "severity": "critical", "iter": it, "message": reason,
                "value": 1.0, "threshold": 0.0,
            }
        ses.record_alert(alert)
        flight.note_alert(alert)
        get_tracer().instant(
            "lifecycle/fault",
            "lifecycle",
            args={"reason": reason, "iter": it},
        )
        return flight.dump(reason)

    def _guard_gradients(self, grad, hess) -> None:
        """check_numerics guard: ONE device-side finiteness reduce over
        gradients+hessians per iteration, pulled as a single host bool.
        Catches poisoned labels/init_score/learning-rate blowups at the
        iteration that produced them instead of training NaN into the
        model silently."""
        with get_tracer().span("wait/check_numerics"):
            ok = bool(jnp.isfinite(grad).all() & jnp.isfinite(hess).all())
        if not ok:
            self._fault_dump("numerics_gradients")
            raise NumericsError(
                f"non-finite gradients/hessians at iteration {self._iter} "
                f"(objective={self._objective_name()}); model state is "
                "intact up to the previous iteration — inspect labels, "
                "init_score, and learning_rate"
            )

    def _guard_tree(self, ta_host, iteration: int) -> None:
        """check_numerics guard: split gains and leaf values of a
        materialized tree must be finite (host-side; arrays already
        fetched, so this costs two np reductions)."""
        nn = max(0, int(ta_host.num_leaves) - 1)
        gains = np.asarray(ta_host.split_gain)[:nn]
        leaves = np.asarray(ta_host.leaf_value)[: int(ta_host.num_leaves)]
        if not (np.isfinite(gains).all() and np.isfinite(leaves).all()):
            self._fault_dump("numerics_tree")
            raise NumericsError(
                f"non-finite split gain or leaf value in the tree grown at "
                f"iteration {iteration} (objective={self._objective_name()})"
            )

    def set_row_mask(self, row_mask) -> None:
        """Restrict training to a fixed row subset (CV folds, holdouts).

        The mask rides the same live-row machinery as mesh padding: excluded
        rows get exact-zero gradients BEFORE sampling (so GOSS never selects
        them) and a zero sample mask after. Shape must be [num_data] (unpadded
        length); pass None to clear. Scores for excluded rows still advance —
        that is what makes out-of-fold prediction on the train-set scores
        possible."""
        sampler = getattr(self, "_sampler", None)
        if row_mask is None:
            self._fixed_row_mask = None
            if sampler is not None:
                sampler.set_live_count(self._rows_to_sample())
            self._refresh_grower_params()
            return
        m = np.asarray(row_mask, dtype=np.float32).reshape(-1)
        if m.shape[0] != self.train_set.num_data:
            raise ValueError(
                f"row_mask length {m.shape[0]} != num_data "
                f"{self.train_set.num_data}"
            )
        live = int((m > 0).sum())
        if live == 0:
            raise ValueError("row_mask excludes every row")
        if self._pad_rows:
            m = np.concatenate([m, np.zeros(self._pad_rows, np.float32)])
        self._fixed_row_mask = jnp.asarray(m)
        if sampler is not None:
            sampler.set_live_count(live)
        self._refresh_grower_params()

    def _rows_to_sample(self) -> Optional[int]:
        """The sampler's live count without a fixed row mask: the table's
        own rows where a mesh padded them (GOSS sizes its top and rest sets
        against real rows, so a mesh and one device draw the same bag), else
        None (the sampler's width is the table)."""
        if self._pad_rows and not self._multiproc:
            return int(self.train_set.num_data)
        return None

    def _refresh_grower_params(self) -> None:
        """A fixed row mask set or cleared moves ``bag_window``: rebuild the
        grower's parameters (and the sharded grower made from them) when
        they changed, and only then."""
        params = self._make_grower_params()
        if params != self._grower_params:
            self._grower_params = params
            if self._mesh is not None:
                self._setup_sharded_grower()

    def _sample(self, grad, hess):
        """Bagging/GOSS row sampling; padded (mesh-fill) rows never count.

        Padded rows' gradients are forced to exact zeros FIRST — objectives
        compute unspecified (finite or NaN) values on the zero-filled padding
        labels, and a NaN would poison the masked histogram (nan*0=nan)."""
        # the gate must be PROCESS-INVARIANT: under multi-process feeding a
        # per-process `_pad_rows` test would make processes issue different
        # op sequences on the same global arrays (SPMD violation — only some
        # processes reaching the next collective deadlocks the cluster)
        any_pad = bool(self._pad_rows) or getattr(self, "_multiproc", False)
        fixed = getattr(self, "_fixed_row_mask", None)
        if any_pad or fixed is not None:
            live = self._ones_mask[None] > 0
            if fixed is not None:
                live = jnp.logical_and(live, fixed[None] > 0)
            grad = jnp.where(live, grad, 0.0)
            hess = jnp.where(live, hess, 0.0)
        mask, grad, hess = self._sampler.sample(
            self._iter, grad, hess, self._bagging_rng()
        )
        if any_pad:
            mask = mask * self._ones_mask
        if fixed is not None:
            mask = mask * fixed
        return mask, grad, hess

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration (reference GBDT::TrainOneIter gbdt.cpp:352).

        Returns True when training cannot continue (no positive-gain split),
        mirroring the reference's is_finished flag.
        """
        chaos.on_iteration(self._iter)  # no-op unless a test armed a fault
        ses = get_session()
        flight = get_flight()
        wd = getattr(self, "_watchdog", None)
        if not ses.enabled:
            # telemetry off: the always-on flight ring still gets a minimal
            # iteration event (one dict per iteration) and the watchdog
            # still sees walls; gauges/counters stay empty so gauge-based
            # rules simply never fire
            it = self._iter
            tracer = get_tracer()
            t0 = time.perf_counter()
            finished = False
            with tracer.span(
                "train/iteration", timer="boosting/update",
                args={"iter": it, **self._first_step_args(),
                      **self._seg_span_args(), **self._score_span_args()},
                ambient=True,
            ) as sp:
                try:
                    finished = self._update_impl(train_set, fobj)
                finally:
                    if sp is not None:
                        sp.args["finished"] = bool(finished)
            if flight.active or wd is not None:
                event = {
                    "event": "iteration",
                    "iter": it,
                    "wall_ms": (time.perf_counter() - t0) * 1e3,
                    "finished": bool(finished),
                }
                flight.note_event(event)
                if wd is not None:
                    wd.observe(event, ses)
            return finished
        it = self._iter
        trees_before = len(self._bin_records_store)
        compiles_before = _obs_compile_count()
        tracer = get_tracer()
        t0 = time.perf_counter()
        finished = False
        # ambient parents the collective io_callback spans fired off-thread
        with tracer.span(
            "train/iteration", timer="boosting/update",
            args={"iter": it, **self._first_step_args(),
                  **self._seg_span_args(), **self._score_span_args()},
            ambient=True,
        ) as sp:
            ses.begin_iteration()
            try:
                try:
                    finished = self._update_impl(train_set, fobj)
                finally:
                    phases = ses.end_iteration()
                # under obs_sync_timing wall_ms is the fully synchronized
                # iteration time; otherwise it is dispatch time (async
                # runtime)
                ses.sync(self._score)
            finally:
                if sp is not None:
                    sp.args["finished"] = bool(finished)
        wall_ms = (time.perf_counter() - t0) * 1e3
        # host bookkeeping (and hence these records) lags one iteration on
        # the pipelined path — splits here count trees MATERIALIZED this call
        new_recs = [r for r in self._bin_records_store[trees_before:] if r]
        compiles_now = _obs_compile_count()
        event = {
            "event": "iteration",
            "iter": it,
            "wall_ms": wall_ms,
            "phases": {k2: v * 1e3 for k2, v in phases.items()},
            "compile_count": compiles_now,
            "compiles_delta": compiles_now - compiles_before,
            "trees_materialized": len(new_recs),
            "splits": int(sum(len(r["split_feature"]) for r in new_recs)),
            "leaf_batch": int(self.config.leaf_batch),
            "finished": bool(finished),
        }
        if (
            self._mesh is not None
            # voting's elected-slice psums are data-dependent (top-k per
            # shard), so the analytic shape model covers every layout BUT it
            and self.config.tree_learner != "voting"
        ):
            from ..parallel.mesh import MeshSpec, mesh_psum_bytes_per_iteration

            spec = getattr(self, "_mesh_spec", None) or MeshSpec(
                "data", data=int(self._mesh.devices.size)
            )
            k = max(1, self.num_tree_per_iteration)
            per_tree = (
                event["splits"] // max(1, len(new_recs))
                if new_recs
                else max(1, self.config.num_leaves - 1)
            )
            coll = mesh_psum_bytes_per_iteration(
                per_tree,
                int(self._bins.shape[1]),
                # PADDED bin-axis size: the psum moves the [3, F, B] padded
                # histogram, so the measured cross-check only matches with
                # the same B the trace actually uses
                int(self._grower_params.max_bin),
                leaf_batch=int(self.config.leaf_batch),
                spec=spec,
            )
            coll = {k2: v * k for k2, v in coll.items()}
            event["collective"] = coll
            ses.set_gauge("collective_hist_bytes", coll["hist_bytes"])
            ses.set_gauge("collective_count_bytes", coll["count_bytes"])
            ses.set_gauge(
                "collective_ring_bytes_per_device",
                coll["ring_bytes_per_device"],
            )
        if self._mesh is not None and self._grower_params.measure_collectives:
            snap = collectives_snapshot(reset=True)
            if snap:
                meas = measured_summary(snap, int(self._mesh.devices.size))
                event["collective_measured"] = meas
                ses.set_gauge("collective_measured_bytes", meas["bytes"])
                ses.set_gauge(
                    "collective_measured_psum_bytes", meas["psum_bytes"]
                )
                ses.set_gauge("collective_measured_wall_ms", meas["wall_ms"])
                ses.inc("collective_measured_bytes_total", int(meas["bytes"]))
        sample_device_memory("iteration")
        ses.inc("iterations")
        ses.set_gauge("hist/int8_engaged", float(self._int8_engaged()))
        # deferred: the engine annotates eval metrics into this event before
        # the JSONL line is flushed (next record / flush_pending)
        ses.record(event, defer=True)
        flight.note_event(event)
        if wd is not None:
            # alerts are recorded via record_alert, which leaves the
            # deferred iteration event pending (late eval annotations
            # still land on its JSONL line)
            wd.observe(event, ses)
        return finished

    def _launch_runner_for(self, n: int):
        """Cached compiled N-iteration launch runner (boosting/launch.py).
        Rebuilt when the static snapshot went stale (set_row_mask /
        reset_parameter between trains swap the sampler or grower
        params)."""
        from .launch import LaunchRunner

        cache = getattr(self, "_launch_runners", None)
        if cache is None:
            cache = self._launch_runners = {}
        runner = cache.get(int(n))
        if runner is None or runner.stale(self):
            with get_tracer().span("setup/launch_build", "setup", args={"steps": int(n)}):
                runner = cache[int(n)] = LaunchRunner(self, int(n))
        return runner

    def update_launch(self, n: int) -> Tuple[int, bool]:
        """Advance up to ``n`` boosting iterations in ONE compiled device
        launch (lax.scan over the iteration loop — boosting/launch.py).
        Model dumps are byte-identical to ``n`` serial ``update()`` calls
        for every eligible config; the caller (engine.train) handles
        eligibility and period clamping via ``resolve_launch_steps``.
        Returns ``(steps_consumed, is_finished)`` — the finishing
        all-constant iteration counts as consumed, like ``update()``
        returning True."""
        if int(n) <= 1:
            return 1, self.update()
        return self._launch_runner_for(int(n)).run()

    def _update_impl(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        if train_set is not None and train_set is not self.train_set:
            self._init_train(train_set)
        ses = get_session()
        tracer = get_tracer()
        cfg = self.config
        k = self.num_tree_per_iteration
        n = self.train_set.num_data

        if self._finished:
            return True
        # pipeline gate BEFORE any drain: reading models_ would block on the
        # in-flight fetch and serialize host bookkeeping with device compute
        eff_len = len(self._models_store) + (
            k if getattr(self, "_pending", None) is not None else 0
        )
        if (
            fobj is None
            and self.objective is not None
            and not self.objective.is_renew_tree_output
            and not cfg.linear_tree
            and type(self) is Booster
            and eff_len >= k  # init/boost-from-avg settled
        ):
            with tracer.span("train/gradients", phase="gradients"):
                grad, hess = self._get_gradients()
                ses.sync(grad)
            grad, hess = chaos.maybe_poison_gradients(grad, hess, self._iter)
            if cfg.check_numerics:
                self._guard_gradients(grad, hess)
            with tracer.span("train/sample", phase="sample"):
                mask, grad, hess = self._sample(grad, hess)
                ses.sync(mask)
            feature_mask = self._feature_mask_for_iter()
            return self._update_pipelined(grad, hess, mask, feature_mask, k)

        self._drain_pending()
        if self._finished:
            return True

        init_scores = [0.0] * k
        if fobj is None:
            if (
                not self.models_
                and not self._has_init_score
                and self.objective is not None
                and cfg.boost_from_average
            ):
                for kk in range(k):
                    s = self.objective.boost_from_score(kk)
                    if abs(s) > _EPS:
                        init_scores[kk] = s
                        self._score = self._score.at[kk].add(s)
                        for entry in self._valid:
                            entry.score = entry.score.at[kk].add(s)
            with tracer.span("train/gradients", phase="gradients"):
                grad, hess = self._get_gradients()
                ses.sync(grad)
        else:
            if self._multiproc:
                raise ValueError(
                    "custom fobj is not supported under multi-process "
                    "pre_partition training (scores are process-sharded)"
                )
            g, h = fobj(
                np.asarray(self._score)[:, :n].reshape(-1)
                if k > 1
                else np.asarray(self._score[0])[:n],
                self.train_set,
            )
            g = np.asarray(g, dtype=np.float32).reshape(k, n)
            h = np.asarray(h, dtype=np.float32).reshape(k, n)
            if self._pad_rows:
                zeros = np.zeros((k, self._pad_rows), np.float32)
                g = np.concatenate([g, zeros], axis=1)
                h = np.concatenate([h, zeros], axis=1)
            grad = jnp.asarray(g)
            hess = jnp.asarray(h)

        grad, hess = chaos.maybe_poison_gradients(grad, hess, self._iter)
        if cfg.check_numerics:
            self._guard_gradients(grad, hess)

        # bagging / GOSS (reference: SampleStrategy::Bagging gbdt.cpp:384)
        with tracer.span("train/sample", phase="sample"):
            mask, grad, hess = self._sample(grad, hess)
            ses.sync(mask)
        feature_mask = self._feature_mask_for_iter()

        should_continue = False
        for kk in range(k):
            grown = None
            if self._class_need_train[kk] and self._bins.shape[1] > 0:
                grown = self._grow_class(
                    kk, grad, hess, mask, feature_mask, self._tree_rng()
                )
            if self._commit_class_tree(kk, grown, grad, hess, mask, init_scores):
                should_continue = True

        return self._finish_iteration(should_continue)

    def _grow_class(self, kk, grad, hess, mask, feature_mask, rng):
        """Grow + host-materialize one class's tree.

        Returns (ta, ta_host, leaf_id); the commit step is separate so a
        fleet trainer can substitute one batched grow for M solo grows and
        still reuse the per-member commit path unchanged."""
        cfg = self.config
        qg, qh = self._quant_grow_inputs(grad[kk], hess[kk], kk)
        ta, leaf_id = self._grow_one(qg, qh, mask, feature_mask, rng)
        ta = self._quant_renew(ta, leaf_id, grad[kk], hess[kk], mask)
        # two bulk transfers instead of ~14 small ones (remote TPU
        # round-trips dominate otherwise); wait/fetch_tree opens inside
        ta_host = fetch_tree_arrays(ta)
        if cfg.check_numerics:
            self._guard_tree(ta_host, self._iter)
        self._note_refine_rate(ta_host)
        return ta, ta_host, leaf_id

    def _commit_class_tree(self, kk, grown, grad, hess, mask, init_scores,
                           skip_train_score=False):
        """Commit one class's grown tree into the model: score updates,
        Tree materialization, bin records. `grown` is `_grow_class`'s
        result or None for a skipped class. Returns True when the tree
        has at least one split (the iteration should continue).

        ``skip_train_score`` is the device-resident launch path
        (boosting/launch.py): the scan already applied this tree's train
        score delta inside the compiled program, so only the valid-score
        walk and host materialization run here."""
        cfg = self.config
        k = self.num_tree_per_iteration
        n = self.train_set.num_data
        n_leaves = int(grown[1].num_leaves) if grown is not None else 1

        if n_leaves > 1:
            ta, ta_host, leaf_id = grown
            leaf_value = ta.leaf_value
            if self.objective is not None and self.objective.is_renew_tree_output:
                lv = self.objective.renew_tree_output(
                    np.asarray(self._score[kk], dtype=np.float64)[:n],
                    np.asarray(leaf_id)[:n],
                    np.asarray(ta_host.leaf_value, dtype=np.float64),
                    np.asarray(mask)[:n],
                )
                leaf_value = jnp.asarray(lv, dtype=jnp.float32)
                ta = ta._replace(leaf_value=leaf_value)
                ta_host = ta_host._replace(leaf_value=lv)
            with get_tracer().span("train/host_tree"):
                tree = Tree.from_device_arrays(
                    ta_host,
                    self.train_set.bin_mappers,
                    self.train_set.used_features,
                    bundle_layout=self._bundle,
                )
            if cfg.verbosity >= 2:
                tree.validate()  # debug CHECK paths (tree.py)
            is_linear = bool(cfg.linear_tree)
            if is_linear:
                self._fit_linear_leaves(
                    tree,
                    np.asarray(leaf_id)[:n],
                    np.asarray(grad[kk], dtype=np.float64)[:n],
                    np.asarray(hess[kk], dtype=np.float64)[:n],
                    np.asarray(mask)[:n],
                )
            tree.apply_shrinkage(self._shrinkage_rate)

            if is_linear:
                # linear leaves: per-row output depends on raw features;
                # scores advance by a host tree walk (the reference's
                # LinearTreeLearner AddPredictionToScore equivalent)
                delta = tree.predict(self._raw_for_replay(self.train_set))
                self._score = self._score.at[kk].add(
                    self._pad_delta(delta, self._pad_rows)
                )
                for entry in self._valid:
                    vdelta = tree.predict(self._raw_for_replay(entry.dataset))
                    entry.score = entry.score.at[kk].add(
                        self._pad_delta(vdelta, entry.pad)
                    )
            else:
                with get_tracer().span("train/score_update"):
                    shrunk = leaf_value * self._shrinkage_rate
                    # train score update: one gather (reference UpdateScore
                    # :501); the donated entry retires the old score cache
                    if not skip_train_score:
                        self._score = _apply_tree_score(
                            self._score, shrunk, leaf_id, jnp.int32(kk)
                        )
                    # valid score updates: the new tree scored in bin space
                    self._score_valid_tree(
                        _apply_tree_valid_score, ta, shrunk, kk
                    )
            if abs(init_scores[kk]) > _EPS:
                tree.add_bias(init_scores[kk])
            nn = n_leaves - 1
            rec = {
                "split_feature": np.asarray(ta_host.split_feature)[:nn],
                "split_bin": np.asarray(ta_host.split_bin)[:nn],
                "default_left": np.asarray(ta_host.default_left)[:nn],
                "left_child": np.asarray(ta_host.left_child)[:nn],
                "right_child": np.asarray(ta_host.right_child)[:nn],
                "leaf_value": np.asarray(tree.leaf_value, dtype=np.float32),
                "split_is_cat": np.asarray(ta_host.split_is_cat)[:nn],
                "cat_mask": np.asarray(ta_host.cat_mask)[:nn],
            }
            self._cegb_mark_used(rec["split_feature"])
            if is_linear:
                rec["no_bin_form"] = True  # device walker can't see coeffs
            self._bin_records.append(rec)
            self.models_.append(tree)
            self._bump_model_version()
        else:
            # constant tree (reference gbdt.cpp:428-441)
            if len(self.models_) < k:
                if (
                    self.objective is not None
                    and not cfg.boost_from_average
                    and not self._has_init_score
                ):
                    init_scores[kk] = self.objective.boost_from_score(kk)
                    self._score = self._score.at[kk].add(init_scores[kk])
                    for entry in self._valid:
                        entry.score = entry.score.at[kk].add(init_scores[kk])
                tree = Tree.constant_tree(init_scores[kk])
            else:
                tree = Tree.constant_tree(0.0)
            self._bin_records.append(
                {
                    "split_feature": np.zeros(0, np.int32),
                    "split_bin": np.zeros(0, np.int32),
                    "default_left": np.zeros(0, bool),
                    "left_child": np.zeros(0, np.int32),
                    "right_child": np.zeros(0, np.int32),
                    "leaf_value": np.asarray(tree.leaf_value, dtype=np.float32),
                }
            )
            self.models_.append(tree)
            self._bump_model_version()

        return n_leaves > 1

    def _finish_iteration(self, should_continue: bool) -> bool:
        """Iteration epilogue shared by solo and fleet-lockstep paths:
        roll back the all-constant round or advance the iteration
        counter. Returns the is_finished flag."""
        k = self.num_tree_per_iteration
        if not should_continue:
            if len(self.models_) > k:
                for _ in range(k):
                    self.models_.pop()
                    self._bin_records.pop()
                self._bump_model_version()
            return True
        self._iter += 1
        return False

    def _fleet_begin_iter(self):
        """Per-iteration preamble for lockstep fleet training.

        Mirrors the non-pipelined `_update_impl` preamble EXACTLY —
        including RNG consumption order, which is what makes a fleet
        member's model dump byte-identical to its solo run: gradients
        consume one key, bagging one key, then one per-class tree key
        drawn only for classes that actually train and only when the
        grower needs device RNG (`_tree_rng` returns None otherwise).
        Returns the iteration operands the fleet trainer stacks across
        members before the single batched grow."""
        ses = get_session()
        tracer = get_tracer()
        cfg = self.config
        k = self.num_tree_per_iteration
        init_scores = [0.0] * k
        if (
            not self.models_
            and not self._has_init_score
            and self.objective is not None
            and cfg.boost_from_average
        ):
            for kk in range(k):
                s = self.objective.boost_from_score(kk)
                if abs(s) > _EPS:
                    init_scores[kk] = s
                    self._score = self._score.at[kk].add(s)
                    for entry in self._valid:
                        entry.score = entry.score.at[kk].add(s)
        with tracer.span("train/gradients", phase="gradients"):
            grad, hess = self._get_gradients()
            ses.sync(grad)
        grad, hess = chaos.maybe_poison_gradients(grad, hess, self._iter)
        if cfg.check_numerics:
            self._guard_gradients(grad, hess)
        with tracer.span("train/sample", phase="sample"):
            mask, grad, hess = self._sample(grad, hess)
            ses.sync(mask)
        feature_mask = self._feature_mask_for_iter()
        tree_rngs = [
            self._tree_rng()
            if (self._class_need_train[kk] and self._bins.shape[1] > 0)
            else None
            for kk in range(k)
        ]
        return {
            "init_scores": init_scores,
            "grad": grad,
            "hess": hess,
            "mask": mask,
            "feature_mask": feature_mask,
            "tree_rngs": tree_rngs,
        }

    def _fleet_end_iter(self, should_continue: bool) -> bool:
        """Fleet-lockstep epilogue: `_finish_iteration` plus latching the
        finished flag so this member becomes a value-preserving no-op slot
        (zero gradients, discarded outputs) while the rest of the fleet
        keeps training."""
        finished = self._finish_iteration(should_continue)
        if finished:
            self._finished = True
        return finished

    def _feature_mask_np_for(self, iteration: int) -> np.ndarray:
        """Host-side feature mask for an arbitrary iteration — the pure
        part of ``_feature_mask_for_iter``, reusable by the launch path
        (boosting/launch.py), which precomputes the masks for a whole
        N-iteration window before dispatching the scan."""
        cfg = self.config
        f = self._bins.shape[1]
        if cfg.feature_fraction >= 1.0 or f == 0:
            return np.ones(f, dtype=bool)
        rng = np.random.default_rng(cfg.feature_fraction_seed + iteration)
        used = max(1, int(round(f * cfg.feature_fraction)))
        chosen = rng.choice(f, size=used, replace=False)
        m = np.zeros(f, dtype=bool)
        m[chosen] = True
        return m

    def _feature_mask_for_iter(self) -> jnp.ndarray:
        f = self._bins.shape[1]
        if self.config.feature_fraction >= 1.0 or f == 0:
            self._note_live_plane(None, f)
            return self._full_feature_mask
        m = self._feature_mask_np_for(self._iter)
        self._note_live_plane(m, f)
        return jnp.asarray(m)

    def rollback_one_iter(self) -> "Booster":
        """Reference GBDT::RollbackOneIter (gbdt.cpp:462)."""
        if self._iter <= 0:
            return self
        k = self.num_tree_per_iteration
        for kk in range(k):
            idx = len(self.models_) - k + kk
            tree = self.models_[idx]
            rec = self._bin_records[idx]
            neg = jnp.asarray(-np.asarray(tree.leaf_value, dtype=np.float32))
            if rec.get("no_bin_form"):
                # linear trees / re-expressed init-model trees: the bin-space
                # walk with plain leaf_value would ignore per-leaf linear
                # coefficients — un-apply with the same real-valued predict
                # the forward path used
                self._score = self._score.at[kk].add(
                    -self._pad_delta(
                        tree.predict(self._train_raw_for_replay()), self._pad_rows
                    )
                )
                for entry in self._valid:
                    entry.score = entry.score.at[kk].add(
                        -self._pad_delta(
                            tree.predict(self._raw_for_replay(entry.dataset)),
                            entry.pad,
                        )
                    )
            elif len(rec["split_feature"]):
                self._score = self._score.at[kk].set(
                    add_tree_to_score(
                        self._score[kk],
                        self._bins,
                        self._nan_bins,
                        jnp.asarray(rec["split_feature"]),
                        jnp.asarray(rec["split_bin"]),
                        jnp.asarray(rec["default_left"]),
                        jnp.asarray(rec["left_child"]),
                        jnp.asarray(rec["right_child"]),
                        neg,
                        *self._rec_cat_args(rec),
                    )
                )
                for entry in self._valid:
                    entry.score = entry.score.at[kk].set(
                        add_tree_to_score(
                            entry.score[kk],
                            entry.bins,
                            self._nan_bins,
                            jnp.asarray(rec["split_feature"]),
                            jnp.asarray(rec["split_bin"]),
                            jnp.asarray(rec["default_left"]),
                            jnp.asarray(rec["left_child"]),
                            jnp.asarray(rec["right_child"]),
                            neg,
                            *self._rec_cat_args(rec),
                        )
                    )
            else:
                self._score = self._score.at[kk].add(-float(tree.leaf_value[0]))
                for entry in self._valid:
                    entry.score = entry.score.at[kk].add(-float(tree.leaf_value[0]))
        for _ in range(k):
            self.models_.pop()
            self._bin_records.pop()
        self._bump_model_version()
        self._iter -= 1
        self._finished = False
        return self

    # ================================================================== eval
    def _eval_entry(self, entry: _EvalEntry, feval=None) -> List[Tuple[str, str, float, bool]]:
        dev_score = self._score if entry is self._train_entry else entry.score
        n_real = entry.dataset.num_data
        tracer = get_tracer()
        out = []
        score = None  # host copy, pulled only if some metric needs it
        dev_sliced = None
        for m in entry.metrics:
            res = None
            if feval is None and hasattr(m, "eval_device"):
                # device-side metric: only the result scalar crosses to host
                # (the [K, N] score pull dominates eval at 10M+ rows)
                # the metric's dispatches; its one blocking read of the
                # result is wait/eval_metric inside (metrics.host_scalar)
                with tracer.span("train/eval_score"):
                    if dev_sliced is None:
                        dev_sliced = dev_score[:, :n_real]
                    res = m.eval_device(dev_sliced, self.objective)
            if res is None:
                if score is None:
                    with tracer.span("wait/eval_metric"):
                        score = np.asarray(dev_score, dtype=np.float64)[:, :n_real]
                res = m.eval(score, self.objective)
            for name, val in res:
                out.append((entry.name, name, val, m.is_higher_better))
        if score is None and feval is not None:
            with tracer.span("wait/eval_metric"):
                score = np.asarray(dev_score, dtype=np.float64)[:, :n_real]
        if feval is not None:
            fevals = feval if isinstance(feval, (list, tuple)) else [feval]
            # feval receives transformed predictions, matching the reference
            # (GBDT::GetPredictAt applies ConvertOutput before handing the
            # score to python feval)
            if self.objective is not None:
                pred_for_feval = np.asarray(
                    self.objective.convert_output(
                        jnp.asarray(score.T if self.num_class > 1 else score[0])
                    )
                )
            else:
                pred_for_feval = score.T if self.num_class > 1 else score[0]
            for f in fevals:
                res = f(pred_for_feval, entry.dataset)
                results = res if isinstance(res, list) else [res]
                for name, val, hib in results:
                    out.append((entry.name, name, val, hib))
        return out

    def eval_train(self, feval=None):
        return self._eval_entry(self._train_entry, feval)

    def eval_valid(self, feval=None):
        out = []
        for entry in self._valid:
            out.extend(self._eval_entry(entry, feval))
        return out

    def eval(self, data: Dataset, name: str, feval=None):
        for entry in self._valid:
            if entry.dataset is data:
                return self._eval_entry(entry, feval)
        if data is self.train_set:
            return self.eval_train(feval)
        raise ValueError("dataset was not added with add_valid")

    # =============================================================== predict
    def telemetry(self) -> Dict[str, Any]:
        """Snapshot of the process-global telemetry session: per-iteration
        events, counters/gauges (including the ``cost/*`` / ``memory/*`` /
        ``collective_measured*`` families — see README "Deep profiling"),
        and jit retrace counts (global and by label)."""
        from ..obs.jit import compile_counts_by_label

        ses = get_session()
        ses.flush_pending()
        return {
            "enabled": ses.enabled,
            "events": list(ses.events),
            "counters": dict(ses.counters),
            # the booster's own readings are computed here, when asked
            "gauges": {**ses.gauges, **self._hist_gauges()},
            "compile_count": _obs_compile_count(),
            "compile_counts_by_label": compile_counts_by_label(),
        }

    def health(self) -> Dict[str, Any]:
        """Live health snapshot: watchdog status (``ok``/``warn``/
        ``critical``), active alerts, the counter/gauge tables and flight-
        recorder state.  Same document as the exporter's ``GET /healthz``
        (see README "Live observability")."""
        from ..obs.export import health_snapshot

        # every ``GET /metrics`` scrape of ``obs_export_port`` asks here
        # first, then renders the session's gauges
        get_session().update_gauges(self._hist_gauges())
        return health_snapshot(getattr(self, "_watchdog", None))

    def _hist_gauges(self) -> Dict[str, float]:
        """``hist/long_step_row_share``, computed when ``telemetry()`` or
        ``health()`` asks; nothing in the training loop sets it."""
        share = self._long_step_row_share()
        return {} if share is None else {"hist/long_step_row_share": share}

    def dump_trace(self, path: str) -> str:
        """Write the span recorder's ring as a Chrome trace-event JSON file
        (atomic tmp+rename).  Load the file in Perfetto
        (https://ui.perfetto.dev) or ``chrome://tracing`` to see the
        train-launch / iteration / wait / collective span timeline.  The
        same document is served live at ``GET /trace`` when
        ``obs_export_port`` is set, and dumped automatically next to every
        flight-recorder fault dump.  Returns the path written."""
        return get_tracer().dump(path)

    def current_iteration(self) -> int:
        return self._iter

    def num_trees(self) -> int:
        return len(self.models_)

    @property
    def num_model_per_iteration(self) -> int:
        return self.num_tree_per_iteration

    def num_feature(self) -> int:
        return self.max_feature_idx + 1

    def _tree_range(self, start_iteration: int, num_iteration: Optional[int]):
        k = self.num_tree_per_iteration
        total_iters = len(self.models_) // k
        start = max(0, start_iteration)
        if num_iteration is None:
            # LightGBM contract: default to best_iteration when early
            # stopping recorded one (basic.py predict docs)
            end = self.best_iteration if self.best_iteration > 0 else total_iters
            end = min(end, total_iters)
        elif num_iteration <= 0:
            end = total_iters
        else:
            end = min(total_iters, start + num_iteration)
        return start * k, max(end, start) * k

    def predict(
        self,
        data: Union[np.ndarray, "Any"],
        start_iteration: int = 0,
        num_iteration: Optional[int] = None,
        raw_score: bool = False,
        pred_leaf: bool = False,
        pred_contrib: bool = False,
        validate_features: bool = False,
        **kwargs,
    ) -> np.ndarray:
        """Batch prediction (reference: LGBM_BoosterPredictForMat ->
        PredictBatchDirect, src/c_api.cpp:2531/:528; per-tree walk
        tree_avx512.hpp:41 -> predict.py level-sync walker).

        Unlike the fork's quirk (PredictRawBatch skipping ConvertOutput,
        SURVEY §2.9), the sigmoid/softmax transform IS applied unless
        raw_score is requested.
        """
        X = self._coerce_predict_input(data)
        t0, t1 = self._tree_range(start_iteration, num_iteration)
        if pred_contrib:
            if hasattr(X, "toarray"):
                X = np.asarray(X.toarray(), dtype=np.float64)
            return self._predict_contrib(X, t0, t1)
        k = self.num_tree_per_iteration
        if t1 <= t0 or not self.models_:
            n = X.shape[0]
            if pred_leaf:
                return np.zeros((n, 0), dtype=np.int32)
            base = np.zeros((n, k) if k > 1 else n)
            return base

        use_bins = (
            self.train_set is not None
            and self.train_set.bin_mappers
            # merged init-model trees may have no exact bin-space form
            # (e.g. categorical splits); fall back to the host walker then
            and not any(
                r.get("no_bin_form") for r in self._bin_records[t0:t1]
            )
        )
        es_requested = bool(
            kwargs.get("pred_early_stop", self.config.pred_early_stop)
        ) and self._early_stop_type(k) != "none"
        knobs = self._predict_knobs(kwargs)
        if use_bins:
            # resolve the prediction engine up front: a matmul/auto request
            # that resolves to the tensor engine skips the Pallas walk fast
            # path (the contractions ARE the MXU path); a walker resolution
            # keeps the existing routing byte-for-byte
            resolved_engine, _ = self._stream_engine().resolve_engine(
                knobs["engine"], "bin", t0, t1
            )
            if (
                resolved_engine == "walk"
                and not pred_leaf
                and not es_requested
            ):
                # fast path: Pallas forest-walk kernel (the fork's
                # tree_avx512 batch predictor, TPU-shaped) with device-side
                # binning — falls back to the streaming XLA engine off-TPU
                # or for categorical/wide trees
                raw_fw = self._forest_walk_raw(
                    X, t0, t1, k,
                    exact_binning=bool(kwargs.get("pred_exact_binning", False)),
                )
                if raw_fw is not None:
                    return self._finish_predict(raw_fw, t0, t1, k, raw_score)
            space = "bin"
        else:
            if hasattr(X, "toarray"):  # real-space walkers need dense values
                X = np.asarray(X.toarray(), dtype=np.float64)
            # linear trees carry per-leaf coefficients the device walker
            # doesn't model — host walk (Tree.predict applies them)
            has_linear = any(t.is_linear for t in self.models_[t0:t1])
            if has_linear and not pred_leaf:
                per_tree = np.stack(
                    [t.predict(X) for t in self.models_[t0:t1]], axis=1
                )
                n = X.shape[0]
                if es_requested:
                    raw = self._apply_pred_early_stop(per_tree, k, kwargs)
                else:
                    raw = per_tree.reshape(n, (t1 - t0) // k, k).sum(axis=1)
                return self._finish_predict(raw, t0, t1, k, raw_score)
            space = "real"

        # streaming engine: chunked, bucket-padded, double-buffered walks
        # (real-space chunks carry the f64 suspect re-walk patch inside)
        eng = self._stream_engine()
        if pred_leaf:
            return eng.run(X, t0, t1, space=space, kind="leaf", **knobs)
        n = X.shape[0]
        iters = (t1 - t0) // k
        if es_requested:
            per_tree = eng.run(X, t0, t1, space=space, kind="value", **knobs)
            raw = self._apply_pred_early_stop(per_tree, k, kwargs)
        else:
            raw = eng.run(
                X,
                t0,
                t1,
                space=space,
                kind="value",
                reduce_fn=lambda blk, rows: blk.reshape(rows, iters, k).sum(
                    axis=1
                ),
                **knobs,
            )
        return self._finish_predict(raw, t0, t1, k, raw_score)

    def _predict_knobs(self, kwargs: Dict[str, Any]) -> Dict[str, Any]:
        """Streaming-engine tuning knobs: per-call kwargs win over params."""
        cfg = self.config
        return {
            "chunk": int(kwargs.get("pred_chunk_rows", cfg.pred_chunk_rows)),
            "num_buffers": int(
                kwargs.get("pred_num_buffers", cfg.pred_num_buffers)
            ),
            "shard_devices": int(
                kwargs.get("pred_shard_devices", cfg.pred_shard_devices)
            ),
            "engine": str(
                kwargs.get("pred_engine", getattr(cfg, "pred_engine", "walk"))
            ),
        }

    def _stream_engine(self) -> StreamingPredictor:
        eng = getattr(self, "_stream", None)
        if eng is None:
            eng = self._stream = StreamingPredictor(self)
        return eng

    @property
    def last_predict_stats(self) -> Dict[str, Any]:
        """Phase breakdown of the most recent predict() call (bin_ms,
        transfer_ms, walk_ms, host_ms, chunks, buckets, compiles)."""
        stats = getattr(self, "_fw_stats", None)
        eng = getattr(self, "_stream", None)
        if eng is not None and eng.last_stats:
            return eng.last_stats
        return stats or {}

    def _bin_matrix_width(self) -> int:
        """Column count of the host-binned prediction matrix: bundle planes
        under EFB, used features otherwise, 1 dummy when nothing is used."""
        ds = self.train_set
        layout = getattr(ds, "bundle_layout", None)
        if layout is not None and getattr(layout, "has_bundles", False):
            return max(1, ds.num_planes)
        return max(1, len(ds.used_features))

    def compile_predict(
        self,
        start_iteration: int = 0,
        num_iteration: Optional[int] = None,
        kinds=("value",),
        chunk: Optional[int] = None,
        pred_engine: Optional[str] = None,
    ) -> int:
        """AOT-lower and cache the streaming engine's bucket-ladder
        executables so the first predict() pays no compile (pred_aot_compile
        runs this at Booster load).  ``chunk`` overrides the config's
        ``pred_chunk_rows`` ladder top (the serving registry warms at its
        ``serve_max_batch``); ``pred_engine`` overrides the config's engine
        (the registry warms at the serve-level engine).  Returns the number
        of executables compiled."""
        t0, t1 = self._tree_range(start_iteration, num_iteration)
        if t1 <= t0 or not self.models_:
            return 0
        knobs = self._predict_knobs(
            {} if pred_engine is None else {"pred_engine": pred_engine}
        )
        if chunk is None:
            chunk = knobs["chunk"]
        return self._stream_engine().warmup(
            t0,
            t1,
            space=self._predict_space(t0, t1),
            chunk=max(256, int(chunk)),
            shard_devices=knobs["shard_devices"],
            kinds=kinds,
            engine=knobs["engine"],
        )

    def _predict_space(self, t0: int, t1: int) -> str:
        """Which walker space predict() will use for this tree range: exact
        bin-space when the training BinMappers are present and every tree
        has a bin-space form, else real-value space."""
        use_bins = (
            self.train_set is not None
            and self.train_set.bin_mappers
            and not any(
                r.get("no_bin_form") for r in self._bin_records[t0:t1]
            )
        )
        return "bin" if use_bins else "real"

    def _real_walk_suspects(self, X: np.ndarray, t0: int, t1: int) -> np.ndarray:
        """Row indices whose f32 walk could disagree with the reference's
        f64 NumericalDecision: some feature value lies within f32 rounding
        distance of some numeric threshold on that feature (categorical
        splits compare exact small integers and cannot flip)."""
        key = ("thr", t0, t1, self._model_version)
        if key not in self._stack_cache:
            # one live entry: staged-prediction loops would otherwise pin a
            # threshold map per (t0, t1) range forever
            self._stack_cache = {
                kk: v for kk, v in self._stack_cache.items()
                if kk[0] != "thr"
            }
            per_feat: Dict[int, list] = {}
            for tr in self.models_[t0:t1]:
                cat = (np.asarray(tr.decision_type) & 1) != 0
                for f_, th in zip(
                    np.asarray(tr.split_feature)[~cat],
                    np.asarray(tr.threshold, np.float64)[~cat],
                ):
                    per_feat.setdefault(int(f_), []).append(float(th))
            self._stack_cache[key] = {
                f_: np.unique(np.asarray(v, np.float64))
                for f_, v in per_feat.items()
            }
        sus = np.zeros(X.shape[0], bool)
        for f_, thr in self._stack_cache[key].items():
            if f_ >= X.shape[1] or thr.size == 0:
                continue
            x = X[:, f_]
            j = np.clip(np.searchsorted(thr, x), 0, thr.size - 1)
            jm = np.clip(j - 1, 0, thr.size - 1)
            near = np.minimum(np.abs(x - thr[j]), np.abs(x - thr[jm]))
            # a flip needs |x - thr| within the f32 rounding of either
            # operand; 8 ulps is comfortably conservative and still keeps
            # the suspect rate ~1e-5
            eps = 8.0 * np.float64(
                np.spacing(
                    np.maximum(np.abs(x), np.abs(thr[j])).astype(np.float32)
                )
            )
            sus |= near <= eps
        return np.flatnonzero(sus)

    def _finish_predict(self, raw: np.ndarray, t0, t1, k, raw_score):
        if self.average_output:
            raw = raw / ((t1 - t0) // k)
        if k == 1:
            raw = raw[:, 0]
        if raw_score or self.objective is None:
            return raw
        n = raw.shape[0]
        if n == 0:
            return raw
        # pad rows to a power of two before the (row-local) output transform
        # so convert_output compiles per bucket, not per distinct row count
        n_pad = _ceil_pow2(n)
        if n_pad != n:
            widths = [(0, n_pad - n)] + [(0, 0)] * (raw.ndim - 1)
            padded = np.pad(raw, widths)
        else:
            padded = raw
        return np.asarray(
            self.objective.convert_output(jnp.asarray(padded))
        )[:n]

    def _forest_walk_raw(self, X, t0, t1, k, exact_binning: bool = False):
        """Raw class scores via the Pallas forest-walk kernel
        (ops/pallas/forest_walk.py — the fork's tree_avx512 batch path,
        TPU-shaped), or None when ineligible.  Binning runs on device
        when every used feature is numeric (the f32 compare-reduce form of
        BinMapper::ValueToBin) with boundary-adjacent rows re-binned on
        host for f64 exactness; ``predict(..., pred_exact_binning=True)``
        forces the host path entirely."""
        import jax as _jax

        from ..ops.pallas.forest_walk import (
            _pack_bins_device,
            bin_numeric_device,
            bucket_pad_rows,
            build_devbin_tables,
            build_tables,
            forest_walk,
            pad_bins_for_walk,
            unpack_walk_scores,
            walk_reject_reason,
        )

        if _jax.default_backend() != "tpu" and not _WALK_INTERPRET:
            return None
        if getattr(self, "_has_bundle", False):
            # EFB models carry plane-membership nodes the walk kernel's
            # threshold tables don't model; the XLA bin walker handles them
            return None
        n = X.shape[0]
        n_used = self.train_set.num_planes
        recs = self._bin_records[t0:t1]
        nanb = np.asarray(self._nan_bins)
        reason = walk_reject_reason(recs, nanb, n_used, self._max_bin_padded)
        if reason is not None:
            # loud fence (VERDICT r3 weak #6): the XLA walker is an order of
            # magnitude slower — tell the user WHY the fast path was lost
            if not getattr(self, "_warned_walk_fallback", False):
                self._warned_walk_fallback = True
                from ..utils.log import log_warning

                log_warning(
                    "prediction fast path (forest-walk kernel) unavailable: "
                    + reason + "; using the slower XLA walker"
                )
            return None
        key = ("fw", t0, t1, self._model_version)
        if key not in self._stack_cache:
            self._stack_cache = {
                kk: v for kk, v in self._stack_cache.items() if kk[0] != "fw"
            }
            self._stack_cache[key] = build_tables(recs, nanb)
        tables = self._stack_cache[key]

        dense_np = isinstance(X, np.ndarray) and X.ndim == 2
        dbt = None
        if dense_np and not exact_binning:
            if ("devbin",) not in self._stack_cache:
                self._stack_cache[("devbin",)] = build_devbin_tables(
                    self.train_set.bin_mappers, self.train_set.used_features
                )
            dbt = self._stack_cache[("devbin",)]

        def _walk(packed):
            return forest_walk(
                packed,
                tables,
                n_trees=tables.n_trees,
                max_depth=tables.max_depth,
                k=k,
                interpret=_WALK_INTERPRET,
            )

        import time as _time

        t_start = _time.perf_counter()

        def _fw_stats(bin_ms=0.0, walk_ms=0.0, chunks=1):
            self._fw_stats = {
                "path": "forest_walk",
                "rows": n,
                "chunks": chunks,
                "bin_ms": round(bin_ms, 3),
                "transfer_ms": 0.0,
                "walk_ms": round(walk_ms, 3),
                "host_ms": 0.0,
            }
            # engine stats would shadow these (last_predict_stats prefers
            # the engine when it ran last) — clear its record
            if getattr(self, "_stream", None) is not None:
                self._stream.last_stats = {}

        if dbt is None:
            t_b = _time.perf_counter()
            host_bins = self._bin_input_host(X)
            bin_ms = (_time.perf_counter() - t_b) * 1e3
            out = _walk(pad_bins_for_walk(host_bins, bucket_pad_rows(n)))
            res = unpack_walk_scores(np.asarray(out), n, k).astype(np.float64)
            _fw_stats(bin_ms, (_time.perf_counter() - t_start) * 1e3 - bin_ms)
            return res

        # device binning + chunked feed: fixed-size chunks keep ONE compiled
        # (bin, pack, walk) pipeline, and dispatching chunk i+1's host slice
        # prep while chunk i computes overlaps transfer with the walk
        # (double buffering; jax's async dispatch is the buffer)
        CHUNK = _PREDICT_CHUNK
        used = self.train_set.used_features

        def _bin_chunk(xs_np, x_orig, rows):
            """[CHUNK, F] f32 used-feature slice -> exact device bins.

            ``x_orig`` is the ORIGINAL full-width f64 rows of this chunk —
            the suspect re-bin must run the exact host path on the
            unrounded values (and _bin_input_host indexes by global
            feature id)."""
            mat_dev, suspect = bin_numeric_device(jnp.asarray(xs_np), *dbt)
            # device binning compares in f32; rows with a value within a
            # few ulps of a bin boundary are re-binned with the exact f64
            # host path so predictions match it bit-for-bit (ADVICE r2; the
            # boundary test is conservative, suspects are typically none)
            sidx = np.flatnonzero(np.asarray(suspect[:rows]))
            if len(sidx):
                patch = self._bin_input_host(x_orig[sidx])
                mat_dev = mat_dev.at[jnp.asarray(sidx)].set(
                    jnp.asarray(patch.astype(np.int32))
                )
            return mat_dev

        if n <= CHUNK:
            xs = np.ascontiguousarray(X[:, used], dtype=np.float32)
            # bucketed tile count: varying batch sizes reuse a small ladder
            # of compiled walk programs instead of one per distinct size
            out = _walk(_pack_bins_device(_bin_chunk(xs, X, n), bucket_pad_rows(n)))
            res = unpack_walk_scores(np.asarray(out), n, k).astype(np.float64)
            _fw_stats(0.0, (_time.perf_counter() - t_start) * 1e3)
            return res

        # one-chunk lookahead drain: chunk i dispatches asynchronously, then
        # chunk i-1 transfers to host — compute/transfer overlap without
        # letting every chunk's device output accumulate in HBM (~32+ MB per
        # 1M-row chunk; an unbounded predict would OOM the accelerator)
        parts = []
        pending = None  # (device_out, rows)
        for lo in range(0, n, CHUNK):
            rows = min(CHUNK, n - lo)
            xo = X[lo : lo + rows]
            xs = np.zeros((CHUNK, len(used)), np.float32)
            xs[:rows] = xo[:, used]
            out = _walk(_pack_bins_device(_bin_chunk(xs, xo, rows), CHUNK))
            if pending is not None:
                parts.append(unpack_walk_scores(np.asarray(pending[0]), pending[1], k))
            pending = (out, rows)
        parts.append(unpack_walk_scores(np.asarray(pending[0]), pending[1], k))
        res = np.concatenate(parts, axis=0).astype(np.float64)
        _fw_stats(0.0, (_time.perf_counter() - t_start) * 1e3, chunks=-(-n // CHUNK))
        return res

    def _early_stop_type(self, k: int) -> str:
        """Reference c_api chooses the margin rule from the objective
        (src/c_api.cpp: binary/multiclassova objectives -> 'binary'/'multiclass')."""
        if k > 1:
            return "multiclass"
        name = self.objective.name if self.objective is not None else ""
        if name in ("binary", "cross_entropy", "cross_entropy_lambda"):
            return "binary"
        return "none"

    def _apply_pred_early_stop(
        self, per_tree: np.ndarray, k: int, kwargs: Dict[str, Any]
    ) -> np.ndarray:
        """Margin-based prediction early stopping, vectorized over rows
        (reference: prediction_early_stop.cpp:26-75 + the per-iteration
        counter loop in gbdt_prediction.cpp:18-36).  Each row's accumulation
        freezes at the FIRST checkpoint (every pred_early_stop_freq
        iterations) whose margin exceeds pred_early_stop_margin — identical
        outputs to the reference's sequential loop, computed as one cumsum."""
        freq = max(1, int(kwargs.get("pred_early_stop_freq",
                                     self.config.pred_early_stop_freq)))
        margin_thr = float(kwargs.get("pred_early_stop_margin",
                                      self.config.pred_early_stop_margin))
        n, total = per_tree.shape
        iters = total // k
        cum = np.cumsum(per_tree.reshape(n, iters, k), axis=1)  # [N, I, K]
        if k == 1:
            margin = 2.0 * np.abs(cum[:, :, 0])
        else:
            s = np.sort(cum, axis=2)
            margin = s[:, :, -1] - s[:, :, -2]
        checkpoint = (np.arange(1, iters + 1) % freq) == 0
        stop = (margin > margin_thr) & checkpoint[None, :]
        any_stop = stop.any(axis=1)
        first = np.where(any_stop, stop.argmax(axis=1), iters - 1)
        return cum[np.arange(n), first]

    def _predict_category_maps(self, cat_names):
        """Recorded train-time category orders as a {name: values} dict.

        ``pandas_categorical`` loaded from a reference-produced model file is
        a list-of-lists ordered like the frame's categorical columns
        (reference: basic.py ``_data_from_pandas`` zips them in column
        order); ours is already a dict keyed by column name."""
        maps = self.pandas_categorical or getattr(
            self.train_set, "arrow_categories", None
        ) or getattr(self.train_set, "pandas_categorical", None)
        if isinstance(maps, list):
            maps = dict(zip(cat_names, maps))
        if not maps and cat_names:
            from ..utils.log import log_warning

            log_warning(
                "predict input has categorical columns but the Booster has "
                "no recorded category order (model trained on pre-coded "
                "data?); raw dictionary codes will be used and may not "
                "match training"
            )
        return maps or {}

    def _coerce_predict_input(self, data):
        from ..dataset import (
            _arrow_to_numpy,
            _is_arrow,
            _is_cat_dtype,
            _pandas_to_numpy,
        )

        if _is_arrow(data):
            import pyarrow as pa  # _is_arrow guaranteed pyarrow is loaded

            dict_cols = [
                str(f.name)
                for f in data.schema
                if pa.types.is_dictionary(f.type)
            ]
            data = _arrow_to_numpy(data, self._predict_category_maps(dict_cols))[0]
        try:
            import pandas as pd  # type: ignore
        except Exception:
            pd = None
        if pd is not None and isinstance(data, pd.DataFrame):
            cat_cols = [
                str(c) for c in data.columns if _is_cat_dtype(data[c].dtype)
            ]
            data = _pandas_to_numpy(
                data, self._predict_category_maps(cat_cols)
            )[0]
        if hasattr(data, "tocsc") and hasattr(data, "nnz"):
            # scipy sparse stays sparse: the bin path bins per-column from
            # CSC; paths that need dense values densify themselves
            return data
        X = np.asarray(data, dtype=np.float64)
        if X.ndim == 1:
            X = X[None, :]
        return X

    def _bin_input_host(self, X) -> np.ndarray:
        ds = self.train_set
        csc = X.tocsc() if hasattr(X, "tocsc") else None
        if csc is not None and csc.shape[1] < ds.num_total_features:
            # copy before resize: tocsc() aliases csc_matrix inputs and
            # resize() would mutate the caller's matrix
            csc = csc.copy()
            csc.resize(csc.shape[0], ds.num_total_features)

        def _feature_bins(j):
            mapper = ds.bin_mappers[j]
            if csc is not None:
                sl = slice(csc.indptr[j], csc.indptr[j + 1])
                col = np.zeros(csc.shape[0], np.float64)
                col[csc.indices[sl]] = csc.data[sl]
            else:
                col = X[:, j]
            b = mapper.values_to_bins(col)
            if mapper.is_categorical:
                # unseen categories must fall through to the right child
                # (reference CategoricalDecision, tree.h:382): bin 0 would
                # wrongly send them left, so route them to a sentinel bin
                vals = np.asarray(col)
                nan_mask = np.isnan(vals)
                iv = np.where(nan_mask, -1, vals).astype(np.int64)
                known = np.isin(iv, mapper.bin_to_cat) & (iv >= 0)
                sentinel = np.int32(1 << 20)
                b = np.where(known | (nan_mask & (mapper.nan_bin >= 0)), b, sentinel)
            return b

        layout = getattr(ds, "bundle_layout", None)
        if layout is not None:
            # EFB: predict input packs into the SAME plane columns training
            # used, so bin-space walks see identical decisions
            return layout.pack_columns(X.shape[0], _feature_bins).astype(
                np.int32
            )
        cols = [_feature_bins(j) for j in ds.used_features]
        mat = (
            np.stack(cols, axis=1)
            if cols
            # no used features (all trivial): keep one dummy column so the
            # walker's gathers stay in range; constant trees never read it
            else np.zeros((X.shape[0], 1), dtype=np.int32)
        )
        return mat.astype(np.int32)

    def _bump_model_version(self) -> None:
        self._model_version = getattr(self, "_model_version", 0) + 1

    def _stacked_real(self, t0: int, t1: int):
        """Cached real-space tree batch (same invalidation discipline as
        _stacked_bins: any models_ mutation bumps _model_version)."""
        key = ("real", t0, t1, self._model_version)
        if key not in self._stack_cache:
            self._stack_cache = {
                k: v for k, v in self._stack_cache.items() if k[0] != "real"
            }
            self._stack_cache[key] = stack_real_trees(self.models_[t0:t1])
        return self._stack_cache[key]

    def _stacked_bins(self, t0: int, t1: int) -> BinTreeBatch:
        key = (t0, t1, self._model_version)
        if key not in self._stack_cache:
            # evict older BIN stacks only; real-space batches, forest-walk
            # tables and the model-independent devbin tables stay valid
            self._stack_cache = {
                k: v
                for k, v in self._stack_cache.items()
                if k[0] in ("real", "fw", "devbin")
            }
            self._stack_cache[key] = stack_bin_trees(
                self._bin_records[t0:t1], self.config.num_leaves
            )
        return self._stack_cache[key]

    def _predict_contrib(self, X: np.ndarray, t0: int, t1: int) -> np.ndarray:
        """SHAP values via TreeSHAP (reference: GBDT::PredictContrib ->
        Tree::PredictContrib, src/io/tree.cpp TreeSHAP path)."""
        from ..shap import predict_contrib

        return predict_contrib(self, X, t0, t1)

    # ============================================================== model IO
    def model_to_string(
        self,
        num_iteration: Optional[int] = None,
        start_iteration: int = 0,
        importance_type: Optional[str] = None,
    ) -> str:
        """Reference: GBDT::SaveModelToString (gbdt_model_text.cpp:314).

        ``importance_type`` defaults to the ``saved_feature_importance_type``
        param (reference config.h:616 / gbdt.h:169): 0 -> "split", 1 ->
        "gain"."""
        if importance_type is None:
            importance_type = (
                "gain"
                if getattr(self.config, "saved_feature_importance_type", 0)
                else "split"
            )
        t0, t1 = self._tree_range(start_iteration, num_iteration)
        lines = ["tree"]
        lines.append(f"version={_MODEL_VERSION}")
        lines.append(f"num_class={self.num_class}")
        lines.append(f"num_tree_per_iteration={self.num_tree_per_iteration}")
        lines.append(f"label_index={self.label_idx}")
        lines.append(f"max_feature_idx={self.max_feature_idx}")
        if self.objective is not None:
            lines.append(f"objective={self.objective.to_string()}")
        elif self.config.objective:
            lines.append(f"objective={self.config.objective}")
        if self.average_output:
            lines.append("average_output")
        lines.append("feature_names=" + " ".join(self.feature_names))
        lines.append("feature_infos=" + " ".join(self.feature_infos))

        tree_strs = [
            self.models_[i].to_string(i - t0) for i in range(t0, t1)
        ]
        sizes = [len(s) + 1 for s in tree_strs]  # +1: joining newline
        lines.append("tree_sizes=" + " ".join(str(s) for s in sizes))
        lines.append("")
        body = "\n".join(tree_strs)
        out = "\n".join(lines) + "\n" + body + ("\n" if body else "") + "end of trees\n"

        imp = self.feature_importance(importance_type=importance_type)
        pairs = sorted(
            [
                (imp[i], self.feature_names[i])
                for i in range(len(imp))
                if imp[i] > 0
            ],
            key=lambda p: -p[0],
        )
        out += "\nfeature_importances:\n"
        for v, name in pairs:
            # split counts print as integers (reference
            # gbdt_model_text.cpp:435 writes size_t; gain writes doubles)
            out += f"{name}={int(v) if importance_type == 'split' else v}\n"
        out += "\nparameters:\n"
        for key, val in (self.params or {}).items():
            out += f"[{key}: {val}]\n"
        out += "end of parameters\n"
        # trailing category-order record, same slot AND shape as the
        # reference model file (python-package/lightgbm/basic.py save_model
        # appends ``pandas_categorical:<json>`` after the parameters block):
        # a list-of-lists zipped positionally with the frame's categorical
        # columns — a {name: cats} dict would pass the reference loader's
        # len check and then silently NaN every category.  Internally the
        # dict is insertion-ordered by frame column, so values() IS the
        # positional order; loading accepts both forms.
        import json as _json

        cats = self.pandas_categorical
        if isinstance(cats, dict):
            cats = list(cats.values())
        out += "\npandas_categorical:%s\n" % _json.dumps(cats, default=str)
        return out

    def save_model(
        self,
        filename: str,
        num_iteration: Optional[int] = None,
        start_iteration: int = 0,
        importance_type: Optional[str] = None,
    ) -> "Booster":
        # None defers to saved_feature_importance_type (model_to_string)
        # tmp+fsync+rename: a kill mid-save leaves the previous file intact,
        # never a truncated model (resilience/checkpoint.py idiom)
        from ..resilience.checkpoint import atomic_write_text

        atomic_write_text(
            str(filename),
            self.model_to_string(num_iteration, start_iteration, importance_type),
        )
        return self

    def _load_model_string(self, s: str) -> None:
        """Reference: GBDT::LoadModelFromString (gbdt_model_text.cpp:468)."""
        # trailing category-order record; ours is a {name: values} dict, the
        # reference python package writes a list-of-lists (kept as-is and
        # zipped with the frame's categorical columns at predict time).
        # Reset first: a model string without the trailer (e.g. produced by
        # the reference CLI) must not inherit a previous model's maps.
        self.pandas_categorical = None
        for line in s.rsplit("\n", 8)[1:]:
            if line.startswith("pandas_categorical:"):
                import json as _json

                try:
                    self.pandas_categorical = _json.loads(
                        line[len("pandas_categorical:"):]
                    )
                except ValueError:
                    pass
        # parameters block round-trips (reference GBDT::LoadModelFromString
        # restores loaded_parameter_); explicitly passed ctor params win,
        # alias-aware (shrinkage_rate passed + learning_rate in the file
        # must not override each other)
        head, marker, rest = s.rpartition("\nparameters:\n")
        file_params = {}
        if marker:
            from ..config import _PARAM_ALIASES as PARAM_ALIASES

            # a RELOAD must not keep the previous file's params: only the
            # user's own (non-file) params shield against the new file
            for k in getattr(self, "_file_param_keys", ()):
                self.params.pop(k, None)
            have = {
                PARAM_ALIASES.get(str(k), str(k)) for k in self.params
            }
            for line in rest.partition("end of parameters")[0].splitlines():
                line = line.strip()
                if line.startswith("[") and line.endswith("]") and ":" in line:
                    pk, pv = line[1:-1].split(":", 1)
                    pk = pk.strip()
                    if PARAM_ALIASES.get(pk, pk) not in have:
                        file_params[pk] = pv.strip()
        self._file_param_keys = tuple(file_params)
        if marker:
            self.params.update(file_params)
            self.config = Config.from_params(self.params)
        header, _, rest = s.partition("Tree=")
        kv = {}
        for line in header.splitlines():
            line = line.strip()
            if "=" in line:
                key, v = line.split("=", 1)
                kv[key] = v
            elif line == "average_output":
                self.average_output = True
        self.num_class = int(kv.get("num_class", 1))
        self.num_tree_per_iteration = int(kv.get("num_tree_per_iteration", 1))
        self.label_idx = int(kv.get("label_index", 0))
        self.max_feature_idx = int(kv.get("max_feature_idx", 0))
        self.feature_names = kv.get("feature_names", "").split()
        self.feature_infos = kv.get("feature_infos", "").split()
        obj_str = kv.get("objective", "")
        if obj_str:
            parts = obj_str.split()
            obj_params = dict(self.params)
            obj_params["objective"] = parts[0]
            for tok in parts[1:]:
                if ":" in tok:
                    pk, pv = tok.split(":", 1)
                    obj_params[pk] = pv
                elif tok == "sqrt":
                    obj_params["reg_sqrt"] = True
            self.config = Config.from_params(obj_params)
            try:
                self.objective = create_objective(self.config)
            except ValueError:
                self.objective = None
        trees_part, _, _tail = ("Tree=" + rest).partition("end of trees")
        blocks = trees_part.split("Tree=")
        self.models_ = []
        self._bin_records = []
        for block in blocks:
            if not block.strip():
                continue
            self.models_.append(Tree.from_string(block))
        self._bump_model_version()
        self._iter = len(self.models_) // max(1, self.num_tree_per_iteration)
        # objective needs label stats for convert_output only for a few
        # objectives; predict-time convert uses config scalars, so a light
        # init with dummy labels is enough when we have no dataset.
        if self.objective is not None:
            try:
                self.objective.init(np.zeros(1), None)
            except Exception:
                pass
            self.objective.num_data = 0

    def dump_model(
        self, num_iteration: Optional[int] = None, start_iteration: int = 0
    ) -> dict:
        t0, t1 = self._tree_range(start_iteration, num_iteration)
        return {
            "name": "tree",
            "version": _MODEL_VERSION,
            "num_class": self.num_class,
            "num_tree_per_iteration": self.num_tree_per_iteration,
            "label_index": self.label_idx,
            "max_feature_idx": self.max_feature_idx,
            "objective": self.objective.to_string() if self.objective else "",
            "average_output": self.average_output,
            "feature_names": self.feature_names,
            "feature_infos": self.feature_infos,
            "tree_info": [
                {"tree_index": i - t0, **self.models_[i].to_json()}
                for i in range(t0, t1)
            ],
            "feature_importances": {
                self.feature_names[i]: float(v)
                for i, v in enumerate(self.feature_importance("split"))
                if v > 0
            },
        }

    # ============================================================ inspection
    def feature_importance(
        self, importance_type: str = "split", iteration: Optional[int] = None
    ) -> np.ndarray:
        """Reference: GBDT::FeatureImportance (gbdt_model_text.cpp:654)."""
        num_f = self.max_feature_idx + 1
        k = self.num_tree_per_iteration
        end = len(self.models_) if iteration is None or iteration <= 0 else iteration * k
        out = np.zeros(num_f)
        for tree in self.models_[:end]:
            if importance_type == "split":
                out += tree.split_counts(num_f)
            else:
                out += tree.gain_sums(num_f)
        return out

    def feature_name(self) -> List[str]:
        return list(self.feature_names)

    def model_from_string(self, model_str: str) -> "Booster":
        """Load a model from text IN PLACE (reference basic.py model_from_string)."""
        self._load_model_string(model_str)
        return self

    def shuffle_models(
        self, start_iteration: int = 0, end_iteration: int = -1
    ) -> "Booster":
        """Permute ITERATION blocks in [start, end) (reference
        GBDT::ShuffleModels, gbdt.h:89 — whole iterations move together so a
        multiclass model's per-class tree slots stay aligned; deterministic
        seed like the reference's Random(17))."""
        k = self.num_tree_per_iteration
        total_iter = len(self.models_) // k
        i0 = max(0, start_iteration)
        i1 = total_iter if end_iteration <= 0 else min(total_iter, end_iteration)
        block_perm = np.arange(i0, i1)
        np.random.default_rng(17).shuffle(block_perm)
        perm = list(range(len(self.models_)))
        for pos, src_it in enumerate(block_perm):
            for kk in range(k):
                perm[(i0 + pos) * k + kk] = src_it * k + kk
        models = self.models_
        recs = self._bin_records
        self.models_ = [models[i] for i in perm]
        if len(recs) == len(models):
            self._bin_records = [recs[i] for i in perm]
        self._bump_model_version()
        return self

    def set_train_data_name(self, name: str) -> "Booster":
        """Reference basic.py set_train_data_name."""
        self._train_data_name = name
        return self

    def set_network(
        self, machines=None, local_listen_port: int = 12400,
        listen_time_out: int = 120, num_machines: int = 1,
    ) -> "Booster":
        """Compatibility shim: the reference wires its TCP machine list here;
        the TPU-native path forms clusters via jax.distributed
        (parallel.init_distributed / parallel.launcher) instead."""
        from ..utils.log import log_warning

        if num_machines > 1:
            log_warning(
                "set_network is a no-op: use lightgbm_tpu.parallel."
                "init_distributed / the launcher for multi-host training"
            )
        return self

    def get_split_value_histogram(
        self, feature, bins=None, xgboost_style: bool = False
    ):
        """Histogram of a feature's split thresholds across the model
        (reference basic.py get_split_value_histogram)."""
        if isinstance(feature, str):
            feature = self.feature_names.index(feature)
        values = []
        for t in self.models_:
            nn = t.num_leaves - 1
            for node in range(nn):
                if int(t.split_feature[node]) == feature:
                    if t.decision_type[node] & 1:
                        raise ValueError(
                            "Cannot compute split value histogram for the "
                            "categorical feature"
                        )
                    values.append(float(t.threshold[node]))
        values = np.asarray(values)
        n_unique = len(np.unique(values))
        # reference default: one bin per unique split value; an explicit int
        # is clamped to n_unique under xgboost_style (basic.py:5123)
        if bins is None or (
            xgboost_style and isinstance(bins, int) and bins > n_unique
        ):
            bins = max(n_unique, 1)
        hist, edges = np.histogram(values, bins=bins)
        if xgboost_style:
            # reference drops zero-count bins and falls back to a numpy
            # array when pandas is unavailable (basic.py)
            ret = np.column_stack((edges[1:], hist))
            ret = ret[ret[:, 1] > 0]
            try:
                import pandas as pd  # type: ignore

                return pd.DataFrame(ret, columns=["SplitValue", "Count"])
            except ImportError:
                return ret
        return hist, edges

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        """Reference: Booster.get_leaf_output (basic.py:4913)."""
        return float(self.models_[tree_id].leaf_value[leaf_id])

    def set_leaf_output(self, tree_id: int, leaf_id: int, value: float) -> "Booster":
        """Reference: Booster.set_leaf_output (LGBM_BoosterSetLeafValue)."""
        self.models_[tree_id].leaf_value[leaf_id] = value
        if tree_id < len(self._bin_records):  # loaded models keep no records
            rec = self._bin_records[tree_id]
            if rec is not None and len(rec.get("leaf_value", ())) > leaf_id:
                rec["leaf_value"][leaf_id] = value
        self._bump_model_version()
        return self

    def lower_bound(self) -> float:
        """Minimum possible model output (reference: Booster.lower_bound ->
        GBDT::GetLowerBoundValue, sum of per-tree minimum leaves)."""
        return float(
            sum(float(np.min(t.leaf_value[: t.num_leaves])) for t in self.models_)
        )

    def upper_bound(self) -> float:
        """Maximum possible model output (GBDT::GetUpperBoundValue)."""
        return float(
            sum(float(np.max(t.leaf_value[: t.num_leaves])) for t in self.models_)
        )

    def trees_to_dataframe(self):
        """Per-node model table (reference: Booster.trees_to_dataframe,
        basic.py:4060 — same column set and node naming S/L scheme)."""
        import pandas as pd  # type: ignore

        rows = []
        for ti, tree in enumerate(self.models_):
            n = tree.num_leaves
            feat_names = self.feature_names

            def node_name(idx, is_leaf):
                return f"{ti}-L{idx}" if is_leaf else f"{ti}-S{idx}"

            def emit(node, depth, parent):
                if node < 0:
                    leaf = ~node
                    rows.append(
                        {
                            "tree_index": ti,
                            "node_depth": depth,
                            "node_index": node_name(leaf, True),
                            "left_child": None,
                            "right_child": None,
                            "parent_index": parent,
                            "split_feature": None,
                            "split_gain": None,
                            "threshold": None,
                            "decision_type": None,
                            "value": float(tree.leaf_value[leaf]),
                            "weight": float(tree.leaf_weight[leaf])
                            if len(tree.leaf_weight) > leaf
                            else None,
                            "count": int(tree.leaf_count[leaf])
                            if len(tree.leaf_count) > leaf
                            else None,
                        }
                    )
                    return ()
                fidx = int(tree.split_feature[node])
                is_cat = bool(tree.decision_type[node] & 1)
                rows.append(
                    {
                        "tree_index": ti,
                        "node_depth": depth,
                        "node_index": node_name(node, False),
                        "left_child": node_name(
                            ~int(tree.left_child[node])
                            if tree.left_child[node] < 0
                            else int(tree.left_child[node]),
                            tree.left_child[node] < 0,
                        ),
                        "right_child": node_name(
                            ~int(tree.right_child[node])
                            if tree.right_child[node] < 0
                            else int(tree.right_child[node]),
                            tree.right_child[node] < 0,
                        ),
                        "parent_index": parent,
                        "split_feature": feat_names[fidx]
                        if fidx < len(feat_names)
                        else str(fidx),
                        "split_gain": float(tree.split_gain[node]),
                        "threshold": float(tree.threshold[node]),
                        "decision_type": "==" if is_cat else "<=",
                        "value": float(tree.internal_value[node])
                        if len(tree.internal_value) > node
                        else None,
                        "weight": float(tree.internal_weight[node])
                        if len(tree.internal_weight) > node
                        else None,
                        "count": int(tree.internal_count[node])
                        if len(tree.internal_count) > node
                        else None,
                    }
                )
                me = node_name(node, False)
                # children pushed right-first so the left subtree emits first
                return (
                    (int(tree.right_child[node]), depth + 1, me),
                    (int(tree.left_child[node]), depth + 1, me),
                )

            # explicit stack: leaf-wise trees can be ~num_leaves deep, past
            # Python's recursion limit
            stack = [(0 if n > 1 else ~0, 1, None)]
            while stack:
                node, depth, parent = stack.pop()
                stack.extend(emit(node, depth, parent))
        return pd.DataFrame(rows)

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """Reference: Booster::ResetConfig via LGBM_BoosterResetParameter."""
        self.params.update(params)
        self.config = Config.from_params(self.params)
        self._shrinkage_rate = self.config.learning_rate
        self._finished = False
        if self.train_set is not None:
            self._setup_constraints()
            self._forced = self._build_forced_splits()
            self._setup_cegb()
            self._grower_params = self._make_grower_params()
            if self._mesh is not None:
                # the shard_map'd grower closed over the OLD params
                self._setup_sharded_grower()
        return self

    def refit(
        self,
        data,
        label,
        decay_rate: float = 0.9,
        reference: Optional[Dataset] = None,
        weight=None,
        group=None,
        init_score=None,
        feature_name="auto",
        categorical_feature="auto",
        dataset_params: Optional[Dict[str, Any]] = None,
        free_raw_data: bool = True,
        validate_features: bool = False,
        **kwargs,
    ) -> "Booster":
        """Refit leaf values on new data, keeping every tree's structure
        (reference: GBDT::RefitTree src/boosting/gbdt.cpp:266 +
        SerialTreeLearner::FitByExistingTree serial_tree_learner.cpp:250 +
        python Booster.refit basic.py:4746).

        leaf_output = decay_rate * old + (1 - decay_rate) * new, where new is
        the regularized optimal output of the leaf's gradient/hessian sums on
        the new data, times shrinkage."""
        if self.objective is None:
            raise ValueError("Cannot refit: no objective (custom-objective model)")
        from ..ops.split import leaf_output as _leaf_out

        leaf_preds = np.asarray(
            self.predict(data, pred_leaf=True, **kwargs), dtype=np.int64
        )  # [N, T]
        new_params = dict(self.params)
        new_params.update(dataset_params or {})
        new_params["refit_decay_rate"] = decay_rate
        train_set = Dataset(
            data,
            label,
            reference=reference,
            weight=weight,
            group=group,
            init_score=init_score,
            feature_name=feature_name,
            categorical_feature=categorical_feature,
            params=new_params,
            free_raw_data=free_raw_data,
        )
        nb = Booster(new_params, train_set)
        import copy as _copy

        nb.models_ = [_copy.deepcopy(t) for t in self.models_]
        k = nb.num_tree_per_iteration
        n = train_set.num_data
        cfg = nb.config
        n_iters = len(nb.models_) // k
        for it in range(n_iters):
            grad, hess = nb.objective.get_gradients(nb._score, nb._next_rng())
            g = np.asarray(grad, dtype=np.float64)[:, :n]
            h = np.asarray(hess, dtype=np.float64)[:, :n]
            for kk in range(k):
                mi = it * k + kk
                tree = nb.models_[mi]
                lp = leaf_preds[:, mi]
                nl = tree.num_leaves
                sum_g = np.bincount(lp, weights=g[kk], minlength=nl)[:nl]
                sum_h = np.bincount(lp, weights=h[kk], minlength=nl)[:nl] + 1e-15
                out = np.asarray(
                    _leaf_out(
                        jnp.asarray(sum_g),
                        jnp.asarray(sum_h),
                        cfg.lambda_l1,
                        cfg.lambda_l2,
                        cfg.max_delta_step,
                    )
                )
                new_out = out * (tree.shrinkage if tree.shrinkage else 1.0)
                tree.leaf_value = (
                    decay_rate * np.asarray(tree.leaf_value, dtype=np.float64)
                    + (1.0 - decay_rate) * new_out
                )
                # advance the new-data score with the refitted outputs
                delta = tree.leaf_value[np.minimum(lp, nl - 1)]
                nb._score = nb._score.at[kk].add(
                    self._pad_delta(delta, nb._pad_rows)
                )
        # bin-space mirrors against the NEW dataset's binning
        nb._bin_records = [nb._bin_record_from_tree(t) for t in nb.models_]
        nb._bump_model_version()
        nb._iter = n_iters
        return nb

    # ============================================================== resilience
    def _checkpoint_state(self) -> Dict[str, Any]:
        """Full trainer-state snapshot for resilience/checkpoint.py.

        Everything the update loop reads that evolves across iterations:
        host model + bin records, device score caches (train and valid),
        the RNG key, the bagging-mask cache, the adaptive leaf_batch
        EMA/cap, the fused-fallback latch, the CEGB feature-usage set, and
        telemetry counters.  Restoring this dict into a freshly constructed
        Booster over the same Dataset+params reproduces the uninterrupted
        run byte-for-byte (the kill/resume parity tests assert it).
        """
        if self.train_set is None:
            raise ValueError("checkpointing requires a training Booster")
        if getattr(self, "_multiproc", False):
            raise NotImplementedError(
                "checkpointing under multi-process feeding is not supported "
                "(scores are process-sharded); checkpoint from a "
                "single-process run"
            )
        from .sampling import BaggingStrategy

        models = self.models_  # property: drains the in-flight fetch first
        sampler_state = None
        if isinstance(self._sampler, BaggingStrategy):
            sampler_state = {"mask": np.asarray(self._sampler._mask)}
        ses = get_session()
        return {
            "format_version": 1,
            "iter": int(self._iter),
            "finished": bool(self._finished),
            "models": list(models),
            "bin_records": [dict(r) if r else r for r in self._bin_records_store],
            "score": np.asarray(self._score),
            "valid_scores": {
                e.name: np.asarray(e.score)
                for e in self._valid
                if e.score is not None
            },
            "rng": np.asarray(self._rng),
            "sampler": sampler_state,
            "commit_rate_ema": getattr(self, "_commit_rate_ema", None),
            "leaf_batch_cap": getattr(self, "_leaf_batch_cap", None),
            "grow_fused_disabled": bool(
                getattr(self, "_grow_fused_disabled", False)
            ),
            "cegb_used": (
                None if self._cegb_used is None else np.asarray(self._cegb_used)
            ),
            "shrinkage_rate": float(self._shrinkage_rate),
            "best_iteration": int(self.best_iteration),
            "num_tree_per_iteration": int(self.num_tree_per_iteration),
            "num_features": int(self._bins.shape[1]),
            "seed": self.config.seed,
            "telemetry_counters": dict(ses.counters) if ses.enabled else None,
        }

    def _restore_checkpoint_state(self, state: Dict[str, Any]) -> None:
        """Rehydrate a training Booster from a _checkpoint_state dict.

        The Booster must already be constructed over the SAME Dataset and
        params as the checkpointed run (engine.train does this before
        calling restore); cheap invariants guard against mixups."""
        if self.train_set is None:
            raise ValueError("restore requires a training Booster")
        if getattr(self, "_multiproc", False):
            raise NotImplementedError(
                "checkpoint restore under multi-process feeding is not "
                "supported"
            )
        if int(state["num_tree_per_iteration"]) != self.num_tree_per_iteration:
            raise ValueError(
                "checkpoint num_tree_per_iteration mismatch: "
                f"{state['num_tree_per_iteration']} vs "
                f"{self.num_tree_per_iteration}"
            )
        if int(state["num_features"]) != int(self._bins.shape[1]):
            raise ValueError(
                "checkpoint was taken on a different dataset "
                f"({state['num_features']} features vs {self._bins.shape[1]})"
            )
        if state.get("seed") != self.config.seed:
            raise ValueError(
                f"checkpoint seed {state.get('seed')} differs from params "
                f"seed {self.config.seed}; the RNG streams would diverge"
            )
        from .sampling import BaggingStrategy

        self._pending = None
        self._models_store = list(state["models"])
        self._bin_records_store = list(state["bin_records"])
        self._bump_model_version()
        self._iter = int(state["iter"])
        self._finished = bool(state["finished"])
        self._shrinkage_rate = float(state["shrinkage_rate"])
        self.best_iteration = int(state.get("best_iteration", -1))
        # re-place scores with the sharding _init_train chose (device_put
        # handles replicated / col-sharded / single-device alike)
        self._score = jax.device_put(
            jnp.asarray(np.asarray(state["score"], np.float32)),
            self._score.sharding,
        )
        valid_scores = state.get("valid_scores") or {}
        for e in self._valid:
            sc = valid_scores.get(e.name)
            if sc is not None and e.score is not None:
                e.score = jax.device_put(
                    jnp.asarray(np.asarray(sc, np.float32)), e.score.sharding
                )
        self._rng = jnp.asarray(np.asarray(state["rng"]))
        sampler_state = state.get("sampler")
        if sampler_state is not None:
            if not isinstance(self._sampler, BaggingStrategy):
                raise ValueError(
                    "checkpoint carries a bagging mask but bagging is not "
                    "active under the current params"
                )
            self._sampler._mask = jnp.asarray(
                np.asarray(sampler_state["mask"])
            )
        self._commit_rate_ema = state.get("commit_rate_ema")
        cap = state.get("leaf_batch_cap")
        if cap is not None:
            self._leaf_batch_cap = int(cap)
        if state.get("grow_fused_disabled"):
            self._grow_fused_disabled = True
        cegb_used = state.get("cegb_used")
        if cegb_used is not None and self._cegb_used is not None:
            self._cegb_used[:] = np.asarray(cegb_used, bool)
        # grower params depend on the restored leaf_batch cap + fused latch
        self._grower_params = self._make_grower_params()
        if self._mesh is not None:
            self._setup_sharded_grower()
        counters = state.get("telemetry_counters")
        if counters:
            get_session().restore_counters(counters)

    def merge_from(self, other: "Booster") -> "Booster":
        """Continued training from an init model (reference: GBDT
        MergeFrom/continued-training via num_init_iteration_, gbdt.h:614)."""
        if other.num_tree_per_iteration != self.num_tree_per_iteration:
            raise ValueError("init model has different num_tree_per_iteration")
        k = self.num_tree_per_iteration
        for idx, tree in enumerate(other.models_):
            self.models_.append(tree)
            rec = self._bin_record_from_tree(tree)
            self._bin_records.append(rec)
            self._bump_model_version()
            kk = idx % k
            # replay onto the train score
            self._score = self._score.at[kk].add(
                self._pad_delta(
                    tree.predict(self._train_raw_for_replay()), self._pad_rows
                )
            )
        n_init = len(other.models_) // k
        self._iter += n_init
        self._replay_rng_stream(self._iter - n_init, n_init)
        return self

    def _replay_rng_stream(self, start_iter: int, n_iters: int) -> None:
        """Advance the per-iteration RNG stream (and the bagging-mask cache)
        as if iterations [start_iter, start_iter + n_iters) had been trained.

        Continued training via init_model used to restart the key stream at
        the fold-0 position, so a 10+10 run drew different bagging masks and
        extra-trees thresholds than the uninterrupted 20-iteration run.
        Replaying the exact draw order of _update_impl — one gradient split,
        one bagging split (plus the BaggingStrategy mask refresh), then per
        trained class one tree split when bynode sampling or extra trees
        are active — makes the continuation byte-identical (quantized
        gradients draw no key: ops.quantize.rounding_uniforms).
        (Custom-fobj runs draw no gradient split and are not replayable.)
        """
        if not hasattr(self, "_rng"):
            return  # model-only booster: no live training state to sync
        from .sampling import BaggingStrategy

        cfg = self.config
        trained = (
            sum(1 for need in self._class_need_train if need)
            if self._bins.shape[1] > 0
            else 0
        )
        per_class = 0
        if cfg.feature_fraction_bynode < 1.0 or cfg.extra_trees:
            per_class += 1  # _tree_rng
        for it in range(start_iter, start_iter + n_iters):
            self._next_rng()  # objective gradients (_get_gradients)
            rng_bag = self._bagging_rng()  # row sampling (_sample)
            if isinstance(self._sampler, BaggingStrategy):
                # refresh the cached mask exactly as training would (the
                # strategy ignores grad/hess); iterations between refreshes
                # reuse it, so the resumed run starts from the right mask
                self._sampler.sample(it, None, None, rng_bag)
            for _ in range(trained * per_class):
                self._next_rng()

    def _train_raw_for_replay(self) -> np.ndarray:
        return self._raw_for_replay(self.train_set)

    def _raw_for_replay(self, ds: Dataset) -> np.ndarray:
        if ds.raw is not None:
            if hasattr(ds.raw, "toarray"):  # sparse kept via free_raw_data=False
                return np.asarray(ds.raw.toarray(), dtype=np.float64)
            return ds.raw
        # reconstruct representative values from bins (inverse binning):
        # exact for the tree decisions because thresholds are bin bounds
        cols = np.zeros((ds.num_data, ds.num_total_features))
        layout = getattr(ds, "bundle_layout", None)
        for ci, j in enumerate(ds.used_features):
            mapper = ds.bin_mappers[j]
            if layout is None:
                b = ds.bins[:, ci].astype(np.int64)
            else:
                # unpack the feature's local bins from its EFB plane column
                p, k = layout.feature_position(j)
                pb = ds.bins[:, p].astype(np.int64)
                if layout.is_bundle(p):
                    s = layout.starts[p][k]
                    w = layout.widths[p][k]
                    b = np.where((pb >= s) & (pb < s + w), pb - s + 1, 0)
                else:
                    b = pb
            if mapper.is_categorical:
                table = np.asarray(mapper.bin_to_cat, dtype=np.float64)
                table = np.concatenate([table, [np.nan]])
                cols[:, j] = table[np.minimum(b, len(table) - 1)]
            else:
                ub = np.asarray(mapper.bin_upper_bound)
                reps = np.concatenate([ub[:-1], [mapper.max_value], [np.nan]])
                cols[:, j] = reps[np.minimum(b, len(reps) - 1)]
        return cols

    def _bin_record_from_tree(self, tree: Tree) -> dict:
        """Re-express a real-valued tree in bin space for the device predictor."""
        ds = self.train_set
        layout = getattr(ds, "bundle_layout", None)
        nn = tree.num_leaves - 1
        sf_used = np.zeros(nn, dtype=np.int32)
        sbin = np.zeros(nn, dtype=np.int32)
        sic = np.zeros(nn, dtype=bool)
        cmask = np.zeros((nn, self._max_bin_padded), dtype=bool)
        orig_to_used = {j: ci for ci, j in enumerate(ds.used_features)}
        ok = True
        has_cat = False
        for t in range(nn):
            orig = int(tree.split_feature[t])
            if orig not in orig_to_used:
                ok = False
                break
            mapper = ds.bin_mappers[orig]
            if layout is not None:
                p, k = layout.feature_position(orig)
                sf_used[t] = p
                if layout.is_bundle(p) and not (tree.decision_type[t] & 1):
                    # numeric split on a bundled member -> plane-bin
                    # membership mask (left = everything except the member's
                    # bins above the threshold), mirroring training's form
                    ub = np.asarray(mapper.bin_upper_bound)
                    thr = float(tree.threshold[t])
                    tl = int(np.searchsorted(ub, thr, side="left"))
                    bval = ub[tl] if tl < len(ub) else np.inf
                    if not (
                        bval == thr
                        or abs(bval - thr) <= 1e-10 * max(1.0, abs(thr))
                    ):
                        ok = False
                        break
                    s = layout.starts[p][k]
                    w = layout.widths[p][k]
                    has_cat = True
                    sic[t] = True
                    bids = np.arange(self._max_bin_padded)
                    cmask[t] = ~((bids >= s + tl) & (bids < s + w))
                    continue
            else:
                sf_used[t] = orig_to_used[orig]
            if tree.decision_type[t] & 1:
                # categorical: map the cat_threshold value-bitset back onto
                # this dataset's bins (cat value -> bin via cat_to_bin)
                if tree.cat_boundaries is None or mapper.cat_to_bin is None:
                    ok = False
                    break
                has_cat = True
                sic[t] = True
                ci = int(tree.threshold[t])
                b0, b1 = int(tree.cat_boundaries[ci]), int(tree.cat_boundaries[ci + 1])
                for w in range(b0, b1):
                    word = int(tree.cat_threshold[w])
                    base = (w - b0) * 32
                    for bit in range(32):
                        if word >> bit & 1:
                            bn = mapper.cat_to_bin.get(base + bit)
                            if bn is None or bn >= cmask.shape[1]:
                                # category in the bitset but absent from this
                                # dataset's bins: bin space would send it
                                # right while real space sends it left
                                ok = False
                                break
                            cmask[t, bn] = True
                    if not ok:
                        break
                if not ok:
                    break
            else:
                ub = np.asarray(mapper.bin_upper_bound)
                thr = float(tree.threshold[t])
                sbin[t] = int(np.searchsorted(ub, thr, side="left"))
                # bin space is exact only when the threshold coincides with a
                # bin boundary of THIS dataset's mapper — foreign thresholds
                # (refit / continued training on re-binned data) would be
                # silently requantized otherwise
                bval = ub[sbin[t]] if sbin[t] < len(ub) else np.inf
                if not (bval == thr or abs(bval - thr) <= 1e-10 * max(1.0, abs(thr))):
                    ok = False
                    break
        if not ok:
            return {
                "split_feature": np.zeros(0, np.int32),
                "split_bin": np.zeros(0, np.int32),
                "default_left": np.zeros(0, bool),
                "left_child": np.zeros(0, np.int32),
                "right_child": np.zeros(0, np.int32),
                "leaf_value": np.asarray(tree.leaf_value, dtype=np.float32),
                "no_bin_form": True,
            }
        return {
            "split_feature": sf_used,
            "split_bin": sbin,
            "default_left": (np.asarray(tree.decision_type) & 2) != 0,
            "left_child": np.asarray(tree.left_child),
            "right_child": np.asarray(tree.right_child),
            "leaf_value": np.asarray(tree.leaf_value, dtype=np.float32),
            "split_is_cat": sic,
            "cat_mask": cmask if has_cat else np.zeros((nn, 1), bool),
        }

    def __copy__(self):
        return self

    def free_dataset(self) -> "Booster":
        return self

    def free_network(self) -> "Booster":
        return self
