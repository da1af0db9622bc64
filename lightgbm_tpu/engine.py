"""Training entry points: train() and cv().

Reference analogs: python-package/lightgbm/engine.py — ``train`` (:109, the
canonical loop: construct Booster, per-iteration callbacks + booster.update()
+ eval) and ``cv`` (:627, folds + aggregated eval).
"""

from __future__ import annotations

import copy
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from .boosting import create_booster
from .boosting.gbdt import Booster
from .callback import CallbackEnv, EarlyStopException, early_stopping, log_evaluation
from .config import Config
from .dataset import Dataset
from .obs.aggregate import global_rollup
from .obs.flight import (
    get_flight,
    install_sigterm_handler,
    uninstall_sigterm_handler,
)
from .obs.profiler import TraceWindow
from .obs.registry import get_session
from .obs.trace import get_tracer
from .utils.log import log_info
from .utils.timer import global_timer


def train(
    params: Dict[str, Any],
    train_set: Dataset,
    num_boost_round: int = 100,
    valid_sets: Optional[Union[Dataset, Sequence[Dataset]]] = None,
    valid_names: Optional[Sequence[str]] = None,
    feval: Optional[Callable] = None,
    init_model: Optional[Union[str, Booster]] = None,
    keep_training_booster: bool = False,
    callbacks: Optional[List[Callable]] = None,
    fobj: Optional[Callable] = None,
    resume_from: Optional[str] = None,
) -> Booster:
    """Train a GBDT model (reference: engine.py:109).

    ``resume_from`` (or the ``resume_from`` param) names a resilience
    checkpoint file or directory (latest checkpoint wins) written by a run
    with ``checkpoint_dir``/``checkpoint_interval`` set; the restored run
    continues the SAME RNG/score/model state, so with identical
    params+data it reproduces the uninterrupted run byte-for-byte.  Under
    resume, ``num_boost_round`` counts TOTAL iterations (the resumed run
    trains ``num_boost_round - restored_iteration`` more)."""
    # fresh per-run phase report (repeated fits would double-count otherwise)
    global_timer.reset()
    params = dict(params or {})
    cfg = Config.from_params(params)
    ses = get_session()
    if cfg.telemetry:
        ses.configure(
            enabled=True,
            sync_timing=cfg.obs_sync_timing,
            sink_path=cfg.telemetry_out,
            device_accounting=cfg.obs_device_accounting,
            measure_collectives=cfg.obs_collectives,
        )
    # distributed tracing: always-on span recorder (independent of the
    # telemetry session) — iteration/launch spans land under one train/run
    # root span, dumped via Booster.dump_trace / GET /trace / on fault
    tracer = get_tracer()
    tracer.configure(
        active=cfg.trace_spans,
        capacity=cfg.trace_capacity,
        default_rate=cfg.trace_sample,
    )
    trace = (
        TraceWindow(
            cfg.profile_trace_dir,
            start_iter=cfg.profile_iter_start,
            end_iter=cfg.profile_iter_end,
        )
        if cfg.profile_trace_dir
        else None
    )
    if "num_iterations" in cfg.raw:
        num_boost_round = cfg.num_iterations
    if cfg.objective in ("none", "custom", "na", "null", "") and fobj is None:
        fobj = params.pop("_fobj", None)

    if isinstance(valid_sets, Dataset):
        valid_sets = [valid_sets]
    valid_sets = list(valid_sets or [])
    valid_names = list(valid_names or [])

    # create_booster -> the first iteration's start; begun unattached, so a
    # raise on the way leaves no stale parent on the span stack, and made the
    # parent of the booster's and the validation sets' set-up (transfers,
    # objective, every compilation on the way) for as long as they run
    init_span = tracer.begin("setup/booster_init", "setup")
    is_valid_contain_train = False
    train_data_name = "training"
    with tracer.under(init_span):
        booster = create_booster(params, train_set)
        if init_model is not None:
            init_booster = (
                init_model if isinstance(init_model, Booster)
                else Booster(model_file=init_model)
            )
            booster.merge_from(init_booster)
        for i, vs in enumerate(valid_sets):
            name = valid_names[i] if i < len(valid_names) else f"valid_{i}"
            if vs is train_set:
                is_valid_contain_train = True
                train_data_name = name
                continue
            booster.add_valid(vs, name)

    callbacks = list(callbacks or [])
    if cfg.early_stopping_round and cfg.early_stopping_round > 0:
        callbacks.append(
            early_stopping(
                cfg.early_stopping_round, cfg.first_metric_only,
                verbose=cfg.verbosity > 0,
                min_delta=cfg.early_stopping_min_delta,
            )
        )
    if cfg.verbosity > 0 and cfg.metric_freq > 0 and not any(
        getattr(cb, "order", None) == 10 and not getattr(cb, "before_iteration", False)
        for cb in callbacks
    ):
        pass  # reference prints via Log; python API requires explicit log_evaluation
    callbacks_before = [cb for cb in callbacks if getattr(cb, "before_iteration", False)]
    callbacks_after = [cb for cb in callbacks if not getattr(cb, "before_iteration", False)]
    callbacks_before.sort(key=lambda cb: getattr(cb, "order", 0))
    callbacks_after.sort(key=lambda cb: getattr(cb, "order", 0))

    resume_path = resume_from if resume_from is not None else (cfg.resume_from or None)
    resumed = False
    if resume_path:
        from .resilience.checkpoint import restore_checkpoint

        with tracer.under(init_span):
            restore_checkpoint(booster, resume_path)
        resumed = True

    # live ops plane: opt-in Prometheus endpoint for the run's duration,
    # and a SIGTERM handler that black-boxes the flight ring (preemption
    # notice -> flight_<ts>.json next to the checkpoint dir) before dying
    exporter = None
    if cfg.obs_export_port > 0:
        from .obs.export import MetricsExporter

        exporter = MetricsExporter(
            cfg.obs_export_port, health_provider=booster.health
        )
        exporter.start()
        if cfg.verbosity >= 1:
            log_info(
                f"[obs] metrics exporter serving {exporter.url}/metrics "
                f"and {exporter.url}/healthz"
            )
    sigterm_installed = install_sigterm_handler()

    begin_iteration = booster.current_iteration()
    if resumed:
        # total-iteration semantics: the resumed run stops where the
        # uninterrupted run would have
        end_iteration = max(begin_iteration, num_boost_round)
    else:
        end_iteration = begin_iteration + num_boost_round
    evaluation_result_list: List = []
    # hoisted "no eval work" fast path: without valid sets the loop used to
    # re-derive the eval-period modulo every iteration just to call an
    # eval_valid() that returns [] — decide once, skip the block entirely
    has_eval_work = bool(is_valid_contain_train or booster._valid)
    # device-resident boosting: one compiled launch advances launch_n
    # iterations; host-boundary work below buckets to launch boundaries
    launch_n = 1
    if fobj is None:
        from .boosting.launch import resolve_launch_steps

        launch_n = resolve_launch_steps(booster, has_eval_work=has_eval_work)
        if launch_n > 1 and callbacks_before:
            from .utils.log import log_warning

            log_warning(
                "[launch] train_steps_per_launch disabled: before-iteration "
                "callbacks (e.g. reset_parameter) mutate per-iteration state "
                "the compiled scan cannot observe"
            )
            launch_n = 1
    # per-launch host overhead (gauge train/host_overhead_ms): wall between
    # the end of one device dispatch and the start of the next (callbacks,
    # eval, telemetry, Python loop)
    prev_dispatch_end: Optional[float] = None
    # root span for the whole training run: iteration/launch spans created
    # by Booster.update / LaunchRunner.run attach as children (tls stack)
    tracer.end(init_span)
    run_span = tracer.begin(
        "train/run",
        "train",
        args={
            "begin_iteration": begin_iteration,
            "end_iteration": end_iteration,
            "steps_per_launch": launch_n,
        },
        attach=True,
        ambient=True,
    )
    try:
        it = begin_iteration
        while it < end_iteration:
            for cb in callbacks_before:
                cb(
                    CallbackEnv(
                        model=booster,
                        params=params,
                        iteration=it,
                        begin_iteration=begin_iteration,
                        end_iteration=end_iteration,
                        evaluation_result_list=None,
                    )
                )
            if trace is not None:
                trace.on_iteration_start(it)
            # serial tail: a partial window would compile a second scan
            # length — fall back to one-iteration dispatches instead.
            # Alignment: windows must START on a multiple of launch_n so the
            # (it_last + 1) % period checks below land on the iterations the
            # serial loop acts on (resolve_launch_steps only guarantees
            # launch_n divides each period, not that begin_iteration is
            # aligned — an init_model or a first-round serial fallback can
            # leave `it` unaligned); one-iteration dispatches re-align it
            use_launch = (
                launch_n > 1
                and it % launch_n == 0
                and it + launch_n <= end_iteration
            )
            if ses.enabled and prev_dispatch_end is not None:
                ses.set_gauge(
                    "train/host_overhead_ms",
                    (time.perf_counter() - prev_dispatch_end) * 1e3,
                )
            # train/iteration and train/launch open inside these calls and
            # feed global_timer's boosting/update
            if use_launch:
                steps, is_finished = booster.update_launch(launch_n)
            else:
                is_finished = booster.update(fobj=fobj)
                steps = 1
                if ses.enabled and launch_n > 1:
                    ses.set_gauge("train/steps_per_launch_effective", 1.0)
            prev_dispatch_end = time.perf_counter()
            it_last = it + max(1, steps) - 1
            if trace is not None:
                trace.on_iteration_end(it_last)

            # periodic model snapshot (reference GBDT::Train gbdt.cpp:258)
            sf = booster.config.snapshot_freq
            if sf > 0 and (it_last + 1) % sf == 0:
                booster.save_model(
                    f"{booster.config.output_model}.snapshot_iter_{it_last + 1}"
                )

            # resilience checkpoint: full trainer state, atomic (tmp+rename);
            # unlike the model snapshot above it captures RNG/score/sampler
            # state so the resumed run is byte-identical
            ck_dir = booster.config.checkpoint_dir
            ck_int = booster.config.checkpoint_interval
            if ck_dir and ck_int > 0 and (it_last + 1) % ck_int == 0:
                from .resilience.checkpoint import save_checkpoint

                with tracer.span("train/checkpoint", timer="boosting/checkpoint"):
                    save_checkpoint(booster, ck_dir)

            evaluation_result_list = []
            if has_eval_work and (
                (it_last + 1) % max(1, booster.config.metric_freq) == 0
                or it_last + 1 == end_iteration
            ):
                with tracer.span("train/eval", timer="boosting/eval"):
                    if is_valid_contain_train:
                        res = booster.eval_train(feval)
                        evaluation_result_list.extend(
                            [(train_data_name, n, v, hib) for (_, n, v, hib) in res]
                        )
                    evaluation_result_list.extend(booster.eval_valid(feval))
                if ses.enabled and evaluation_result_list:
                    # lands inside the deferred iteration JSONL line
                    ses.annotate_last({
                        "eval": {
                            f"{d}/{n}": v
                            for (d, n, v, _hib) in evaluation_result_list
                        }
                    })
            with tracer.span("train/callbacks"):
                for cb in callbacks_after:
                    cb(
                        CallbackEnv(
                            model=booster,
                            params=params,
                            iteration=it_last,
                            begin_iteration=begin_iteration,
                            end_iteration=end_iteration,
                            evaluation_result_list=evaluation_result_list,
                        )
                    )
            if is_finished:
                break
            it += max(1, steps)
    except EarlyStopException as e:
        booster.best_iteration = e.best_iteration + 1
        evaluation_result_list = e.best_score
    finally:
        if run_span is not None:
            tracer.end(run_span)
        if trace is not None:
            trace.close()
        if sigterm_installed:
            uninstall_sigterm_handler()
        if exporter is not None:
            exporter.stop()
        if ses.enabled:
            # multi-host rollup (GlobalSyncUp analog; identity on one
            # process) and one train_summary event carrying the final
            # counters/gauges for offline tools (telemetry_summary.py)
            global_rollup(ses)
            ses.record(
                {
                    "event": "train_summary",
                    "counters": dict(ses.counters),
                    "gauges": dict(ses.gauges),
                }
            )
        ses.flush_pending()
    booster.best_score = {}
    for item in evaluation_result_list or []:
        data_name, eval_name, val = item[0], item[1], item[2]
        booster.best_score.setdefault(data_name, {})[eval_name] = val
    if booster.config.verbosity >= 1:
        # per-phase wall summary (reference global_timer at shutdown,
        # utils/common.h:979)
        log_info(global_timer.summary())
        if ses.enabled:
            log_info(_deep_obs_summary(ses))
    return booster


def train_fleet(
    params_list: Union[Dict[str, Any], Sequence[Dict[str, Any]]],
    train_set: Dataset,
    num_boost_round: int = 100,
    valid_sets: Optional[Union[Dataset, Sequence[Dataset]]] = None,
    valid_names: Optional[Sequence[str]] = None,
    feval: Optional[Callable] = None,
    callbacks: Optional[List[Callable]] = None,
    row_masks: Optional[Sequence] = None,
    boosters: Optional[List[Booster]] = None,
) -> List[Booster]:
    """Train M same-shape models for far less than M runs.

    All members share the binned dataset and ONE compiled, vmapped grow
    step per tree class (boosting/fleet.py): histograms for every member
    accumulate in a single kernel launch, and under ``tree_learner=data``
    the per-member psums collapse into one stacked payload per step.
    Every member's model is byte-identical to the model its params would
    produce in a solo :func:`train` run.

    ``params_list`` is either an explicit list of per-member params dicts
    (same-shape sweeps: seeds, ``learning_rate``, bagging/GOSS fractions,
    ``extra_seed``) or ONE dict expanded to ``num_fleet`` members whose
    seeds are offset by the member index.  ``row_masks`` optionally
    restricts each member to a fixed row subset (CV folds) via
    :meth:`Booster.set_row_mask`.  ``callbacks`` are FACTORIES invoked
    once per member (stateful callbacks like ``early_stopping`` must not
    share state across members); per-member early stopping freezes that
    member while the rest of the fleet keeps training in the same warm
    executable.  ``boosters`` bypasses construction (used by ``cv``).

    Not supported in v1 (raises): custom fobj, init_model/resume,
    checkpointing, dart/rf boosting, linear trees, quantized gradients,
    CEGB, multi-process feeding.
    """
    from .boosting.fleet import FleetTrainer

    global_timer.reset()
    if boosters is None:
        if isinstance(params_list, dict):
            base = dict(params_list)
            cfg0 = Config.from_params(base)
            seed0 = cfg0.seed if cfg0.seed is not None else 0
            params_list = []
            for i in range(max(1, cfg0.num_fleet)):
                p = dict(base)
                p["seed"] = seed0 + i
                params_list.append(p)
        boosters = [create_booster(dict(p), train_set) for p in params_list]
    if row_masks is not None:
        if len(row_masks) != len(boosters):
            raise ValueError(
                f"row_masks has {len(row_masks)} entries for "
                f"{len(boosters)} fleet members"
            )
        for b, m in zip(boosters, row_masks):
            if m is not None:
                b.set_row_mask(m)

    cfg = boosters[0].config
    ses = get_session()
    if cfg.telemetry:
        ses.configure(
            enabled=True,
            sync_timing=cfg.obs_sync_timing,
            sink_path=cfg.telemetry_out,
            device_accounting=cfg.obs_device_accounting,
            measure_collectives=cfg.obs_collectives,
        )
    if "num_iterations" in cfg.raw:
        num_boost_round = cfg.num_iterations

    if isinstance(valid_sets, Dataset):
        valid_sets = [valid_sets]
    valid_sets = list(valid_sets or [])
    valid_names = list(valid_names or [])
    for b in boosters:
        for i, vs in enumerate(valid_sets):
            name = valid_names[i] if i < len(valid_names) else f"valid_{i}"
            b.add_valid(vs, name)

    # per-member callback instances: early_stopping keeps closure state,
    # so each member needs its own (factories, not shared instances)
    factories = list(callbacks or [])
    per_member_after: List[List[Callable]] = []
    for b in boosters:
        cbs = [f() for f in factories]
        bc = b.config
        if bc.early_stopping_round and bc.early_stopping_round > 0:
            cbs.append(
                early_stopping(
                    bc.early_stopping_round, bc.first_metric_only,
                    verbose=bc.verbosity > 0,
                    min_delta=bc.early_stopping_min_delta,
                )
            )
        cbs = [cb for cb in cbs if not getattr(cb, "before_iteration", False)]
        cbs.sort(key=lambda cb: getattr(cb, "order", 0))
        per_member_after.append(cbs)

    trainer = FleetTrainer(boosters)
    # device-resident boosting composed with the fleet: one compiled
    # launch advances launch_n lockstep rounds (scan-over-vmap); eval and
    # per-member early stopping bucket to launch boundaries
    from .boosting.launch import resolve_fleet_launch_steps

    launch_n = resolve_fleet_launch_steps(
        trainer, has_eval_work=any(b._valid for b in boosters)
    )
    last_eval: List[List] = [[] for _ in boosters]
    it = 0
    while it < num_boost_round:
        was_active = trainer.active_members()
        # same alignment rule as train(): a first-round serial fallback
        # (constant-tree hazard) consumes one round, so windows must wait
        # for `it` to re-align or the per-member metric_freq checks below
        # would stop landing on the serial loop's eval iterations
        use_launch = (
            launch_n > 1
            and it % launch_n == 0
            and it + launch_n <= num_boost_round
        )
        if use_launch:
            steps = trainer.update_launch(launch_n)
        else:
            trainer.update()
            steps = 1
        it_last = it + max(1, steps) - 1
        for i in was_active:
            b = boosters[i]
            evals: List = []
            if (it_last + 1) % max(1, b.config.metric_freq) == 0 or (
                it_last + 1 == num_boost_round
            ):
                with get_tracer().span("train/eval", timer="boosting/eval"):
                    evals = b.eval_valid(feval)
                if evals:
                    last_eval[i] = evals
            try:
                for cb in per_member_after[i]:
                    cb(
                        CallbackEnv(
                            model=b,
                            params=b.params,
                            iteration=it_last,
                            begin_iteration=0,
                            end_iteration=num_boost_round,
                            evaluation_result_list=evals,
                        )
                    )
            except EarlyStopException as e:
                b.best_iteration = e.best_iteration + 1
                last_eval[i] = e.best_score
                trainer.stop_member(i)
        if trainer.done():
            break
        it += max(1, steps)
    for b, evals in zip(boosters, last_eval):
        b.best_score = {}
        for item in evals or []:
            data_name, eval_name, val = item[0], item[1], item[2]
            b.best_score.setdefault(data_name, {})[eval_name] = val
    if cfg.verbosity >= 1:
        log_info(global_timer.summary())
        if ses.enabled:
            log_info(_deep_obs_summary(ses))
    return boosters


def _fmt_bytes(v: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(v) < 1024.0 or unit == "GiB":
            return f"{v:.1f} {unit}" if unit != "B" else f"{v:.0f} B"
        v /= 1024.0
    return f"{v:.1f} GiB"


def _deep_obs_summary(ses) -> str:
    """End-of-train deep-observability block next to the GlobalTimer one:
    peak HBM, analytic vs measured collective bytes, retraces by label."""
    from .obs.jit import compile_counts_by_label

    lines = ["deep observability:"]
    peak = ses.gauges.get("memory/hbm_peak_bytes")
    if peak is not None:
        lines.append(f"  peak HBM (all local devices): {_fmt_bytes(peak)}")
    else:
        lines.append(
            "  peak HBM: n/a (backend reports no memory_stats, or "
            "obs_device_accounting off)"
        )
    iters = max(1, ses.counters.get("iterations", 1))
    hist_b = ses.gauges.get("collective_hist_bytes")
    cnt_b = ses.gauges.get("collective_count_bytes")
    if hist_b is not None:
        analytic = (hist_b + (cnt_b or 0.0)) * iters
        lines.append(
            f"  collective bytes (analytic model): {_fmt_bytes(analytic)}"
        )
    measured = ses.counters.get("collective_measured_bytes_total")
    if measured is not None:
        lines.append(f"  collective bytes (measured): {_fmt_bytes(measured)}")
    by_label = compile_counts_by_label()
    if by_label:
        top = sorted(by_label.items(), key=lambda kv: -kv[1])
        lines.append(
            "  retraces by label: "
            + ", ".join(f"{k}={v}" for k, v in top)
        )
    return "\n".join(lines)


class CVBooster:
    """Container of per-fold boosters (reference: engine.py CVBooster)."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name: str):
        def handler(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]

        return handler


def _make_n_folds(
    full_data: Dataset,
    nfold: int,
    params: Dict[str, Any],
    seed: int,
    stratified: bool,
    shuffle: bool,
    group_aware: bool = False,
):
    """Yields (train_idx, test_idx, train_group, test_group); the group
    entries are None except for ranking data, where whole QUERIES are
    assigned to folds (reference engine.py:559 group_kfold split)."""
    full_data.construct()
    num_data = full_data.num_data
    rng = np.random.default_rng(seed)
    label = full_data.get_label()
    qb = full_data.metadata.query_boundaries
    if group_aware and qb is not None:
        nq = len(qb) - 1
        if nq < nfold:
            raise ValueError(
                f"ranking cv needs at least nfold queries: have {nq} "
                f"queries for nfold={nfold}"
            )
        order = np.arange(nq)
        if shuffle:
            rng.shuffle(order)
        fold_of_query = np.zeros(nq, dtype=np.int64)
        fold_of_query[order] = np.arange(nq) % nfold
        sizes = np.diff(qb)
        row_fold = np.repeat(fold_of_query, sizes)
        for k in range(nfold):
            test_q = fold_of_query == k
            yield (
                np.nonzero(row_fold != k)[0],
                np.nonzero(row_fold == k)[0],
                sizes[~test_q],
                sizes[test_q],
            )
        return
    if stratified:
        # per-class round-robin assignment after an optional shuffle
        fold_id = np.zeros(num_data, dtype=np.int64)
        for cls in np.unique(label):
            idx = np.nonzero(label == cls)[0]
            if shuffle:
                rng.shuffle(idx)
            fold_id[idx] = np.arange(len(idx)) % nfold
    else:
        idx = np.arange(num_data)
        if shuffle:
            rng.shuffle(idx)
        fold_id = np.zeros(num_data, dtype=np.int64)
        fold_id[idx] = np.arange(num_data) % nfold
    for k in range(nfold):
        test_mask = fold_id == k
        yield np.nonzero(~test_mask)[0], np.nonzero(test_mask)[0], None, None


def _fold_groups(train_set: Dataset, fold, need_query: bool):
    """(train_group, test_group) for a user-supplied (train_idx, test_idx)
    fold: for ranking data the indices must cover whole queries; their
    per-query sizes are derived from the dataset's boundaries."""
    if not need_query:
        return None, None
    qb = train_set.metadata.query_boundaries
    if qb is None:
        return None, None
    query_of_row = np.repeat(np.arange(len(qb) - 1), np.diff(qb))

    full_sizes = np.diff(qb)

    def sizes_for(idx):
        # respect the GIVEN row order: group sizes are emitted per run of
        # consecutive same-query rows, and each run must cover its query
        # exactly (any order inside the run is fine for listwise losses)
        idx = np.asarray(idx)
        qs = query_of_row[idx]
        change = np.nonzero(np.diff(qs))[0] + 1
        bounds = np.concatenate([[0], change, [len(qs)]])
        run_q = qs[bounds[:-1]]
        run_len = np.diff(bounds)
        bad = (
            len(np.unique(run_q)) != len(run_q)
            or not np.array_equal(run_len, full_sizes[run_q])
        )
        if not bad:
            for b0, b1, q in zip(bounds[:-1], bounds[1:], run_q):
                if not np.array_equal(
                    np.sort(idx[b0:b1]), np.arange(qb[q], qb[q + 1])
                ):
                    bad = True
                    break
        if bad:
            raise ValueError(
                "ranking cv folds must contain whole queries with each "
                "query's rows consecutive; a supplied fold splits or "
                "interleaves a query"
            )
        return run_len

    return sizes_for(fold[0]), sizes_for(fold[1])


def cv(
    params: Dict[str, Any],
    train_set: Dataset,
    num_boost_round: int = 100,
    folds=None,
    nfold: int = 5,
    stratified: bool = True,
    shuffle: bool = True,
    metrics: Optional[Union[str, Sequence[str]]] = None,
    feval: Optional[Callable] = None,
    init_model=None,
    seed: int = 0,
    callbacks: Optional[List[Callable]] = None,
    eval_train_metric: bool = False,
    return_cvbooster: bool = False,
    fobj: Optional[Callable] = None,
    fleet: bool = False,
) -> Dict[str, List[float]]:
    """K-fold cross-validation (reference: engine.py:627).

    ``fleet=True`` trains all folds in lockstep through ONE vmapped grow
    executable (boosting/fleet.py): folds become per-member row masks on
    the SHARED full-data binning instead of per-fold rebuilt Datasets, so
    one batched grow per iteration replaces nfold serial grows.  Metric
    values differ slightly from the legacy loop (shared bin boundaries
    and boost_from_average computed over the full data rather than per
    fold); each fold's trained model is byte-identical to a solo
    mask-based run of that fold.  Ranking objectives and custom ``fobj``
    fall back to the legacy per-fold loop with a warning."""
    params = dict(params or {})
    if metrics is not None:
        params["metric"] = metrics
    cfg = Config.from_params(params)
    if "num_iterations" in cfg.raw:
        num_boost_round = cfg.num_iterations
    if cfg.objective not in ("binary", "multiclass", "multiclassova"):
        stratified = False

    train_set.construct()
    data_np = train_set.bins  # binned copy exists; rebuild folds from raw-ish data
    label = train_set.get_label()
    weight = train_set.get_weight()

    # folds on raw arrays: reconstruct per-fold Datasets sharing bin mappers
    from .objectives import create_objective

    _obj = create_objective(cfg)
    need_query = bool(_obj is not None and _obj.need_query)
    if folds is None:
        folds = list(
            _make_n_folds(
                train_set, nfold, params, seed, stratified, shuffle,
                group_aware=need_query,
            )
        )
    else:
        folds = [
            f if len(f) == 4 else (*f, *_fold_groups(train_set, f, need_query))
            for f in folds
        ]

    if fleet:
        if need_query or fobj is not None or init_model is not None:
            from .utils.log import log_warning

            log_warning(
                "cv(fleet=True) supports non-ranking objectives without "
                "fobj/init_model; falling back to the legacy per-fold loop"
            )
        else:
            return _cv_fleet(
                params, cfg, train_set, num_boost_round, folds, feval,
                callbacks, eval_train_metric, return_cvbooster,
            )

    cvbooster = CVBooster()
    raw = train_set.raw
    if raw is None:
        raise ValueError(
            "cv requires the training Dataset to keep raw data; construct it "
            "with free_raw_data=False"
        )
    for train_idx, test_idx, train_group, test_group in folds:
        dtrain = Dataset(
            raw[train_idx],
            label[train_idx],
            weight=None if weight is None else weight[train_idx],
            group=train_group,
            params=params,
            free_raw_data=False,
        )
        dtest = dtrain.create_valid(
            raw[test_idx],
            label[test_idx],
            weight=None if weight is None else weight[test_idx],
            group=test_group,
        )
        booster = create_booster(params, dtrain)
        booster.add_valid(dtest, "valid")
        cvbooster.append(booster)

    results: Dict[str, List[float]] = {}
    callbacks = list(callbacks or [])
    if cfg.early_stopping_round and cfg.early_stopping_round > 0:
        callbacks.append(early_stopping(
            cfg.early_stopping_round, cfg.first_metric_only, verbose=False,
            min_delta=cfg.early_stopping_min_delta,
        ))
    callbacks_after = sorted(
        [cb for cb in callbacks if not getattr(cb, "before_iteration", False)],
        key=lambda cb: getattr(cb, "order", 0),
    )

    try:
        for it in range(num_boost_round):
            all_res: Dict[str, Any] = {}
            for booster in cvbooster.boosters:
                booster.update(fobj=fobj)
                res = booster.eval_valid(feval)
                if eval_train_metric:
                    res = booster.eval_train(feval) + res
                for data_name, name, val, hib in res:
                    entry = all_res.setdefault(f"{data_name} {name}", ([], hib))
                    entry[0].append(val)
            agg = []
            for key, (vals, hib) in all_res.items():
                mean = float(np.mean(vals))
                std = float(np.std(vals))
                results.setdefault(f"{key}-mean", []).append(mean)
                results.setdefault(f"{key}-stdv", []).append(std)
                agg.append(("cv_agg", key, mean, hib, std))
            for cb in callbacks_after:
                cb(
                    CallbackEnv(
                        model=cvbooster,
                        params=params,
                        iteration=it,
                        begin_iteration=0,
                        end_iteration=num_boost_round,
                        evaluation_result_list=agg,
                    )
                )
    except EarlyStopException as e:
        cvbooster.best_iteration = e.best_iteration + 1
        for key in list(results.keys()):
            results[key] = results[key][: cvbooster.best_iteration]
    if return_cvbooster:
        results["cvbooster"] = cvbooster  # type: ignore[assignment]
    return results


def _cv_fleet(
    params: Dict[str, Any],
    cfg: Config,
    train_set: Dataset,
    num_boost_round: int,
    folds,
    feval: Optional[Callable],
    callbacks: Optional[List[Callable]],
    eval_train_metric: bool,
    return_cvbooster: bool,
) -> Dict[str, List[float]]:
    """Fleet-mode cv: every fold is a row-masked member of ONE lockstep
    fleet over the shared full-data binning — one vmapped grow per
    iteration instead of nfold serial grows (see boosting/fleet.py).

    The oracle for this path is the sequential mask-based loop: training
    fold i alone with ``set_row_mask(fold_i)`` produces the byte-identical
    model (tests/test_fleet.py); the legacy rebuild-the-Dataset cv differs
    by bin boundaries, which is a documented fleet-mode trade."""
    from .boosting.fleet import FleetTrainer

    raw = train_set.raw
    if raw is None:
        raise ValueError(
            "cv requires the training Dataset to keep raw data; construct it "
            "with free_raw_data=False"
        )
    label = train_set.get_label()
    weight = train_set.get_weight()
    n = train_set.num_data
    cvbooster = CVBooster()
    for train_idx, test_idx, _tg, _ttg in folds:
        booster = create_booster(params, train_set)
        mask = np.zeros(n, np.float32)
        mask[np.asarray(train_idx)] = 1.0
        booster.set_row_mask(mask)
        dtest = train_set.create_valid(
            raw[test_idx],
            label[test_idx],
            weight=None if weight is None else weight[test_idx],
        )
        booster.add_valid(dtest, "valid")
        cvbooster.append(booster)

    results: Dict[str, List[float]] = {}
    callbacks = list(callbacks or [])
    if cfg.early_stopping_round and cfg.early_stopping_round > 0:
        callbacks.append(early_stopping(
            cfg.early_stopping_round, cfg.first_metric_only, verbose=False,
            min_delta=cfg.early_stopping_min_delta,
        ))
    callbacks_after = sorted(
        [cb for cb in callbacks if not getattr(cb, "before_iteration", False)],
        key=lambda cb: getattr(cb, "order", 0),
    )

    trainer = FleetTrainer(cvbooster.boosters)
    try:
        for it in range(num_boost_round):
            trainer.update()
            all_res: Dict[str, Any] = {}
            for booster in cvbooster.boosters:
                res = booster.eval_valid(feval)
                if eval_train_metric:
                    res = booster.eval_train(feval) + res
                for data_name, name, val, hib in res:
                    entry = all_res.setdefault(f"{data_name} {name}", ([], hib))
                    entry[0].append(val)
            agg = []
            for key, (vals, hib) in all_res.items():
                mean = float(np.mean(vals))
                std = float(np.std(vals))
                results.setdefault(f"{key}-mean", []).append(mean)
                results.setdefault(f"{key}-stdv", []).append(std)
                agg.append(("cv_agg", key, mean, hib, std))
            for cb in callbacks_after:
                cb(
                    CallbackEnv(
                        model=cvbooster,
                        params=params,
                        iteration=it,
                        begin_iteration=0,
                        end_iteration=num_boost_round,
                        evaluation_result_list=agg,
                    )
                )
            if trainer.done():
                break
    except EarlyStopException as e:
        cvbooster.best_iteration = e.best_iteration + 1
        for key in list(results.keys()):
            results[key] = results[key][: cvbooster.best_iteration]
    if return_cvbooster:
        results["cvbooster"] = cvbooster  # type: ignore[assignment]
    return results
