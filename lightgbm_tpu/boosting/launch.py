"""Device-resident boosting: scan N iterations per compiled launch.

``train_steps_per_launch=N`` fuses gradient/hessian computation, the full
tree-grow step and the score update for N consecutive boosting iterations
into ONE compiled ``lax.scan`` program, so the host loop advances N trees
per dispatch instead of returning to Python every iteration.  The scanned
carry is the already-device-resident trainer state: the [K, N] score
cache (donated), the RNG key, the persistent bagging mask, and the
finished/bad-step latches.  Per-iteration bagging/GOSS mask derivation is
folded inside the scan (``SampleStrategy.scan_sample``), and the N grown
trees ride out as packed (ints, floats) stacks — the same two-transfer
encoding ``fetch_tree_arrays`` uses — to be materialized, validated and
committed on the host after the launch returns.

Byte parity is the contract: every eligible config produces model dumps
byte-identical to the N=1 serial loop.  The load-bearing details:

* RNG stream: the serial loop consumes one ``split`` for gradients, one
  for bagging (ALWAYS, even on non-refresh iterations — the key is drawn
  and discarded), and one per trained class only when the grower needs
  device RNG.  The scan body replays exactly that order with the same
  ``fold_in`` gating on explicit ``bagging_seed``/``extra_seed``.
* Host branches become whole-array selects: bagging refresh and GOSS
  warmup are ``jnp.where`` selects of complete arrays (never
  ``x + where(p, delta, 0)``, which can flip ``-0.0`` to ``+0.0``), and
  a halted step's carry is select-protected so a mid-window finish
  freezes score/RNG/mask bit-exactly.
* The grow step always traces the two-launch XLA composition
  (``grow_fused=False``) — the same byte-identical oracle the fleet path
  uses — so the scan body is scan/vmap-safe everywhere, including under
  ``tree_learner=data`` mesh specs (the histogram psums scan cleanly
  inside shard_map).

Host-boundary semantics: eval, early stopping, callbacks, checkpoints,
snapshots and flight-recorder events bucket to launch boundaries; the
validator (:func:`resolve_launch_steps`) clamps N to divide every active
period and warns once.  ``check_numerics`` failures are detected on the
device carry (a ``bad`` latch records the first offending iteration; no
per-step host pull) and re-raised after the launch with the window named;
the trees grown BEFORE the bad step are committed first, so "model state
is intact up to the previous iteration" still holds.  Accepted
divergence: the serial loop raises after consuming only the gradient key
of the bad iteration, while the scan consumed that step's full key
budget — only the dead trainer's RNG differs, committed models and
scores are identical.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..obs.flight import get_flight
from ..obs.jit import compile_count as _compile_count
from ..obs.jit import closed_over_bytes, instrumented_jit
from ..obs.registry import get_session
from ..obs.device import sample_device_memory
from ..obs.trace import get_tracer
from ..ops.grower import _pack_tree_arrays_impl, grow_tree, unpack_tree_arrays
from ..ops.quantize import quantize_gradients, renew_leaf_values
from ..ops.score_lookup import leaf_lookup
from ..resilience import NumericsError, chaos
from .sampling import SampleStrategy
from ..utils.log import log_warning

_EPS = 1e-15


# --------------------------------------------------------------- validation


# what a sampling booster's launch reports an iteration (flight recorder's
# ``launch`` event, ``train/launch`` span args): rows in the bag, GOSS's rows
# at or above its threshold, and the rows equal to it
_BAG_COUNTERS = ("in_bag_rows", "top_rows", "threshold_ties")


def resolve_requested_steps(cfg) -> int:
    """The user-requested N: 'auto' resolves to 8 on TPU backends (where
    the per-dispatch fixed cost dominates the <100 ms/iteration budget)
    and 1 elsewhere."""
    req = cfg.train_steps_per_launch
    if req == "auto":
        return 8 if jax.default_backend() == "tpu" else 1
    return max(1, int(req))


def clamp_steps(n: int, periods) -> int:
    """Clamp a requested steps-per-launch so every host-boundary feature
    still fires on its configured period: N is reduced to
    ``gcd(N, period)`` for each ACTIVE period (eval via ``metric_freq``
    when eval work exists, ``checkpoint_interval`` when a checkpoint dir
    is set, ``snapshot_freq`` when > 0), so launch boundaries always land
    exactly on the iterations the serial loop would have acted on."""
    n = max(1, int(n))
    for p in periods:
        p = int(p)
        if p > 0:
            n = math.gcd(n, p)
    return max(1, n)


def launch_ineligible_reason(booster) -> Optional[str]:
    """Why this booster cannot scan iterations on device (None = eligible).

    The exclusions mirror the fleet trainer's: paths with per-iteration
    host work woven into the update (renew_tree_output's host leaf
    renewal, linear-tree least squares, CEGB's host-side used-feature
    latch), subclassed boosting schedules (dart's drop
    state, rf's bag-of-iterations), multi-process feeding, and armed
    chaos drills (their kill/poison hooks are host-gated per iteration).
    ``hist_mode='seg'`` stays ELIGIBLE: the scan traces the two-launch
    XLA composition, the seg path's byte-identical oracle.
    """
    from .gbdt import Booster

    cfg = booster.config
    if type(booster) is not Booster:
        return f"boosting type {type(booster).__name__} (dart/rf schedules)"
    if booster.objective is None:
        return "custom objective (host-side fobj)"
    if booster.objective.is_renew_tree_output:
        return (
            f"objective {type(booster.objective).__name__} renews leaf "
            "outputs on host each iteration"
        )
    if cfg.linear_tree:
        return "linear_tree fits leaf models on host each iteration"
    if getattr(booster, "_cegb_coupled", None) is not None:
        return "CEGB updates its used-feature penalty on host each iteration"
    if getattr(booster, "_multiproc", False):
        return "multi-process feeding reassembles gradients on host"
    if chaos._ARMED:
        return "chaos drill armed (per-iteration host fault hooks)"
    if booster._bins.shape[1] <= 0 or not any(booster._class_need_train):
        return "no trainable tree class"
    return None


def resolve_launch_steps(booster, *, has_eval_work: bool) -> int:
    """Final steps-per-launch for a train run: requested N, eligibility
    fallback, then the period clamp.  Warns (once per train — this runs
    once per train) when the user's explicit request is overridden."""
    cfg = booster.config
    n = resolve_requested_steps(cfg)
    if n <= 1:
        return 1
    explicit = cfg.train_steps_per_launch != "auto"
    reason = launch_ineligible_reason(booster)
    if reason is not None:
        if explicit:
            log_warning(
                f"[launch] train_steps_per_launch={n} ignored ({reason}); "
                "falling back to one iteration per dispatch"
            )
        return 1
    periods = []
    if has_eval_work:
        periods.append(max(1, cfg.metric_freq))
    if cfg.checkpoint_dir and cfg.checkpoint_interval > 0:
        periods.append(cfg.checkpoint_interval)
    if cfg.snapshot_freq > 0:
        periods.append(cfg.snapshot_freq)
    clamped = clamp_steps(n, periods)
    if clamped != n:
        log_warning(
            f"[launch] train_steps_per_launch clamped {n} -> {clamped} so "
            "launch boundaries divide the active eval/checkpoint/snapshot "
            f"periods {sorted(set(int(p) for p in periods))} (host-boundary "
            "features fire every N iterations)"
        )
    return clamped


def resolve_fleet_launch_steps(trainer, *, has_eval_work: bool) -> int:
    """Fleet variant of :func:`resolve_launch_steps`: every member must be
    launch-eligible, and the clamp covers every member's eval period (the
    fleet path has no checkpoint/snapshot work)."""
    b0 = trainer.boosters[0]
    n = resolve_requested_steps(b0.config)
    if n <= 1:
        return 1
    explicit = b0.config.train_steps_per_launch != "auto"
    for i, b in enumerate(trainer.boosters):
        reason = launch_ineligible_reason(b)
        if reason is not None:
            if explicit:
                log_warning(
                    f"[launch] train_steps_per_launch={n} ignored for the "
                    f"fleet (member {i}: {reason}); falling back to one "
                    "lockstep round per dispatch"
                )
            return 1
    periods = []
    if has_eval_work:
        periods.extend(
            max(1, b.config.metric_freq) for b in trainer.boosters
        )
    clamped = clamp_steps(n, periods)
    if clamped != n:
        log_warning(
            f"[launch] fleet train_steps_per_launch clamped {n} -> "
            f"{clamped} so launch boundaries divide every member's eval "
            "period"
        )
    return clamped


# ------------------------------------------------------------- solo runner


class LaunchRunner:
    """Compiled N-iteration scan for one Booster.

    Built lazily by ``Booster.update_launch`` and cached per N; the
    static snapshot (sampler, objective, grower params, pad/fixed-mask
    gating) is taken at build time, and :meth:`stale` tells the booster
    when a rebuild is needed (e.g. ``set_row_mask`` between trains).
    One ``run()`` = one device dispatch advancing up to N iterations,
    followed by host materialization of the N packed trees through the
    SAME ``_commit_class_tree`` path the serial loop uses (with only the
    already-applied train-score update skipped).
    """

    def __init__(self, booster, n: int):
        self._b = booster
        self._n = int(n)
        cfg = booster.config
        self._k = booster.num_tree_per_iteration
        self._trains = [
            bool(booster._class_need_train[kk] and booster._bins.shape[1] > 0)
            for kk in range(self._k)
        ]
        self._L = int(booster._grower_params.num_leaves)
        self._nn = self._L - 1
        self._any_pad = bool(booster._pad_rows) or getattr(
            booster, "_multiproc", False
        )
        self._has_fixed = getattr(booster, "_fixed_row_mask", None) is not None
        self._params = dataclasses.replace(
            booster._grower_params, grow_fused=False
        )
        # STRONG refs to the snapshotted objects: they pin the snapshot for
        # the runner's lifetime so the identity checks in stale() cannot be
        # fooled by CPython allocating a replacement object at a freed
        # object's address (id reuse would silently revive a cached
        # executable traced against the old sampler/objective constants)
        self._snap_sampler = booster._sampler
        self._snap_objective = booster.objective
        self._snap_grower_params = booster._grower_params
        self._snap_bins_shape = booster._bins.shape
        # a sampling booster's bag counters (in-bag rows, GOSS's top rows
        # and threshold ties) ride out at the tail of every packed ints row;
        # a booster that samples nothing keeps the scan it always traced
        self._bag_tail = (
            len(_BAG_COUNTERS) if type(booster._sampler) is not SampleStrategy else 0
        )
        self._fn = instrumented_jit(
            self._launch_impl,
            label=f"grow/scan{self._n}",
            donate_argnums=(0,),
            # the body closes over the grower's parameters, the sampler, the
            # objective and the quantization, all made from the Config: the
            # whole of it is a superset, and a superset errs toward a second
            # map file with the same content, a subset toward two programs
            # reading one map (PR 34)
            closure_key=repr(booster.config),
            # the scan body reads the objective's per-row arrays (labels,
            # weights), the sampler's and the per-feature tables off the
            # booster, not from its operands: they are constants of the
            # executable, and a table with other labels compiles again
            compile_args=lambda args, kwargs: {
                "baked_bytes": closed_over_bytes(self._fn, args, kwargs)
            },
        )

    def stale(self, booster) -> bool:
        return not (
            booster._sampler is self._snap_sampler
            and booster.objective is self._snap_objective
            and booster._grower_params is self._snap_grower_params
            and (getattr(booster, "_fixed_row_mask", None) is not None)
            == self._has_fixed
            and booster._bins.shape == self._snap_bins_shape
        )

    # ----------------------------------------------------------- trace body

    def _grow(self, bins, g, h, mask, fm, tkey, quant_scales):
        """Per-class grow inside the scan body: the mesh-sharded shard_map
        path (unchanged executable semantics — shard_map traces cleanly
        under scan) or serial ``grow_tree`` with the fused dispatcher
        forced to its XLA oracle.  ``quant_scales``: the traced scales of
        this tree's quantized gradients, or None."""
        b = self._b
        if b._mesh is not None:
            return b._sharded_grow(
                bins,
                g,
                h,
                mask,
                b._num_bins,
                b._nan_bins,
                fm,
                b._mono_arg,
                b._inter_arg,
                tkey if tkey is not None else jax.random.PRNGKey(0),
                b._iscat_arg,
                b._forced,
                *b._cegb_args(),
                quant_scales,
                b._bundle_end_arg,
                b._contri_arg,
            )
        return grow_tree(
            bins,
            g,
            h,
            mask,
            b._num_bins,
            b._nan_bins,
            fm,
            self._params,
            monotone=b._monotone,
            interaction_sets=b._interaction_sets,
            rng=tkey,
            is_cat=b._is_cat,
            forced=b._forced,
            quant_scales=quant_scales,
            bundle_end=b._bundle_end,
            feature_contri=b._feature_contri,
        )

    def _launch_impl(self, score, rng, bag, its, fms, bins, ones_mask, fixed):
        b = self._b
        cfg = b.config
        k = self._k
        sampler = b._sampler
        objective = b.objective
        shrink = float(b._shrinkage_rate)
        check = bool(cfg.check_numerics)
        any_pad = self._any_pad
        has_fixed = self._has_fixed
        fold_bag = "bagging_seed" in cfg.raw
        need_tkey = bool(cfg.feature_fraction_bynode < 1.0 or cfg.extra_trees)
        fold_extra = bool(cfg.extra_trees and "extra_seed" in cfg.raw)
        quantized = bool(cfg.use_quantized_grad)
        renew = quantized and bool(cfg.quant_train_renew_leaf)

        def step(carry, xs):
            score, rng, bag, finished, bad = carry
            it = xs["it"]
            fm = xs["fm"]
            halted = jnp.logical_or(finished, bad >= 0)
            # 1) gradient key + gradients (serial: _get_gradients)
            pair = jax.random.split(rng)
            rng_g, gkey = pair[0], pair[1]
            with jax.named_scope("gradients"):
                grad, hess = objective.get_gradients(score, gkey)
            # 2) device-side numerics latch (serial: _guard_gradients pulls
            # one host bool per iteration; here the verdict rides the carry)
            if check:
                ok = jnp.logical_and(
                    jnp.isfinite(grad).all(), jnp.isfinite(hess).all()
                )
            else:
                ok = jnp.asarray(True)
            # 3) pad/fixed-mask zeroing BEFORE sampling (serial: _sample)
            if any_pad or has_fixed:
                live = ones_mask[None] > 0
                if has_fixed:
                    live = jnp.logical_and(live, fixed[None] > 0)
                grad = jnp.where(live, grad, 0.0)
                hess = jnp.where(live, hess, 0.0)
            # 4) bagging key — drawn EVERY iteration like the serial loop
            pair = jax.random.split(rng_g)
            rng_b, bkey = pair[0], pair[1]
            if fold_bag:
                bkey = jax.random.fold_in(bkey, cfg.bagging_seed)
            with jax.named_scope("sample"):
                mask, grad, hess, bag_new = sampler.scan_sample(
                    it, grad, hess, bkey, bag
                )
            if any_pad:
                mask = mask * ones_mask
            if has_fixed:
                mask = mask * fixed
            if self._bag_tail:
                bag_tail = jnp.stack([
                    jnp.sum(mask > 0, dtype=jnp.int32),
                    *sampler.scan_counters(),
                ])
            # 5) per-class grow + gated score update
            rng_cur = rng_b
            new_score = score
            any_split = jnp.asarray(False)
            live_step = jnp.logical_and(jnp.logical_not(halted), ok)
            ints_rows: List[Any] = [None] * k
            floats_rows: List[Any] = [None] * k
            for kk in range(k):
                if not self._trains[kk]:
                    continue
                tkey = None
                if need_tkey:
                    pair = jax.random.split(rng_cur)
                    rng_cur, tkey = pair[0], pair[1]
                    if fold_extra:
                        tkey = jax.random.fold_in(tkey, cfg.extra_seed)
                # quantized-gradient training (serial: _quant_grow_inputs,
                # _quant_renew): the same two functions, the tree's index
                # traced, so every row rounds as in the per-iteration loop
                g_k, h_k, scales = grad[kk], hess[kk], None
                if quantized:
                    g_k, h_k, *scales = quantize_gradients(
                        g_k, h_k, b._quant_seed(), it * k + kk,
                        **b._quant_static(),
                    )
                ta, leaf_id = self._grow(bins, g_k, h_k, mask, fm, tkey, scales)
                if renew:
                    ta = ta._replace(leaf_value=renew_leaf_values(
                        leaf_id, grad[kk], hess[kk], mask, ta.num_leaves,
                        self._L, cfg.lambda_l1, cfg.lambda_l2,
                        cfg.max_delta_step,
                        measure=self._params.measure_collectives,
                    ))
                has_split = ta.num_leaves > 1
                upd = jnp.logical_and(live_step, has_split)
                with jax.named_scope("score_update"):
                    shrunk = ta.leaf_value * shrink
                    # whole-array select (NOT add-of-masked-delta): a
                    # skipped step must keep the old score bit patterns,
                    # -0.0 included
                    cand = new_score.at[kk].add(leaf_lookup(shrunk, leaf_id))
                    new_score = jnp.where(upd, cand, new_score)
                any_split = jnp.logical_or(any_split, has_split)
                ii, ff = _pack_tree_arrays_impl(ta)
                if self._bag_tail:
                    ii = jnp.concatenate([ii, bag_tail])
                ints_rows[kk] = ii
                floats_rows[kk] = ff
            zi = next(v for v in ints_rows if v is not None)
            zf = next(v for v in floats_rows if v is not None)
            ints = jnp.stack(
                [v if v is not None else jnp.zeros_like(zi) for v in ints_rows]
            )
            floats = jnp.stack(
                [v if v is not None else jnp.zeros_like(zf) for v in floats_rows]
            )
            # 6) latches + select-protected carry
            finished2 = jnp.logical_or(
                finished,
                jnp.logical_and(live_step, jnp.logical_not(any_split)),
            )
            bad2 = jnp.where(
                jnp.logical_and(
                    bad < 0,
                    jnp.logical_and(
                        jnp.logical_not(halted), jnp.logical_not(ok)
                    ),
                ),
                it,
                bad,
            )
            rng_out = jnp.where(halted, rng, rng_cur)
            bag_out = jnp.where(halted, bag, bag_new)
            return (new_score, rng_out, bag_out, finished2, bad2), {
                "ints": ints,
                "floats": floats,
            }

        carry0 = (
            score,
            rng,
            bag,
            jnp.zeros((), bool),
            jnp.full((), -1, jnp.int32),
        )
        return jax.lax.scan(step, carry0, {"it": its, "fm": fms})

    # ------------------------------------------------------------ execution

    def _operands(self, it0: int):
        """Operands of the compiled scan for the window starting at
        iteration ``it0`` (``_launch_impl`` order), plus the host
        feature-mask rows they were built from.  One place builds them, so
        ``run()`` and a caller that lowers the program for inspection
        (``self._fn.lower(*args)``, chip_smoke.py) cannot drift."""
        from .sampling import BaggingStrategy

        b = self._b
        its = jnp.asarray(np.arange(it0, it0 + self._n, dtype=np.int32))
        fm_rows = [
            b._feature_mask_np_for(it) for it in range(it0, it0 + self._n)
        ]
        fms = jnp.asarray(np.stack(fm_rows))
        is_bagging = isinstance(b._sampler, BaggingStrategy)
        bag0 = b._sampler._mask if is_bagging else jnp.zeros((1,), jnp.float32)
        fixed = getattr(b, "_fixed_row_mask", None)
        fixed_arg = fixed if fixed is not None else jnp.zeros((1,), jnp.float32)
        return (
            b._score, b._rng, bag0, its, fms, b._bins, b._ones_mask, fixed_arg,
        ), fm_rows

    def run(self) -> Tuple[int, bool]:
        """One launch: up to N iterations on device, then host replay of
        the packed trees through the serial commit path.  Returns
        ``(steps_consumed, is_finished)`` with the serial loop's
        semantics: the finishing (all-constant, rolled-back) iteration
        counts as consumed but does not advance ``_iter``."""
        b = self._b
        cfg = b.config
        k = self._k
        b._drain_pending()
        if b._finished:
            return 0, True
        # boost-from-average prologue — replicated from _update_impl so the
        # scan's step-0 gradients see the boosted score
        init_scores = [0.0] * k
        if (
            not b.models_
            and not b._has_init_score
            and b.objective is not None
            and cfg.boost_from_average
        ):
            for kk in range(k):
                s = b.objective.boost_from_score(kk)
                if abs(s) > _EPS:
                    init_scores[kk] = s
                    b._score = b._score.at[kk].add(s)
                    for entry in b._valid:
                        entry.score = entry.score.at[kk].add(s)
        elif (
            not b.models_
            and b.objective is not None
            and not cfg.boost_from_average
            and not b._has_init_score
        ):
            # first-round constant-tree hazard: if no class splits at
            # iteration 0, the serial commit injects boost_from_score into
            # the score cache on host — unreplayable mid-scan, so the first
            # iteration runs serially and launches start from iteration 1
            return 1, b.update()

        it0 = int(b._iter)
        # dispatch, fetch and replay attach under the launch span via the
        # tls stack; _note_launch puts the per-iteration counters on it
        with get_tracer().span(
            "train/launch",
            timer="boosting/update",
            args={"launch_begin": it0, "steps_per_launch": self._n,
                  **b._first_step_args(),
                  **b._seg_span_args(), **b._score_span_args()},
            ambient=True,
        ) as lsp:
            return self._run_window(lsp, it0, init_scores)

    def _run_window(self, lsp, it0: int, init_scores) -> Tuple[int, bool]:
        """``run()`` inside its ``train/launch`` span: one dispatch, one
        blocking fetch, the host replay."""
        from .sampling import BaggingStrategy

        b = self._b
        cfg = b.config
        k = self._k
        ses = get_session()
        flight = get_flight()
        wd = getattr(b, "_watchdog", None)
        S = self._n
        args, fm_rows = self._operands(it0)
        for m in fm_rows:
            b._note_live_plane(
                None if m.all() else m, int(b._bins.shape[1])
            )
        is_bagging = isinstance(b._sampler, BaggingStrategy)

        compiles_before = _compile_count()
        tracer = get_tracer()
        t0 = time.perf_counter()
        if ses.enabled:
            ses.begin_iteration()
        try:
            try:
                with tracer.span("train/launch_dispatch", phase="launch"):
                    carry, ys = self._fn(*args)
                    score, rng, bag, finished_dev, bad_dev = carry
                    # donated score: rebind before anything can raise
                    b._score = score
                    b._rng = rng
                    if is_bagging:
                        b._sampler._mask = bag
            finally:
                phases = ses.end_iteration() if ses.enabled else {}
            with tracer.span("wait/launch_fetch"):
                # [S, k, ints_len] — blocks = synced
                ints = np.asarray(ys["ints"])
                floats = np.asarray(ys["floats"])
                bad = int(bad_dev)
        except BaseException:
            if lsp is not None:  # scan failure skips _note_launch
                lsp.args["error"] = True
            raise
        wall_ms = (time.perf_counter() - t0) * 1e3

        # ---- host replay: materialize + commit in serial iteration order
        steps_done = 0
        records = []
        is_finished = False
        replay = tracer.begin("train/launch_replay", attach=True)
        try:
            for s in range(S):
                it = it0 + s
                chaos.on_iteration(it)
                if bad >= 0 and it == int(bad):
                    b._fault_dump("numerics_gradients")
                    raise NumericsError(
                        f"non-finite gradients/hessians at iteration {it} "
                        f"inside launch window [{it0}, {it0 + S}) "
                        f"(train_steps_per_launch={S}, "
                        f"objective={b._objective_name()}); model state is "
                        "intact up to the previous iteration — inspect "
                        "labels, init_score, and learning_rate"
                    )
                isc = init_scores if s == 0 else [0.0] * k
                should = False
                rec = {
                    "iter": it,
                    "trees_materialized": 0,
                    "splits": 0,
                    "grow_steps": 0,
                    "refine_count": 0,
                }
                for kk in range(k):
                    grown = None
                    if self._trains[kk]:
                        row = ints[s, kk]
                        if self._bag_tail:
                            row, tail = np.split(row, [-self._bag_tail])
                            rec.update(zip(_BAG_COUNTERS, map(int, tail)))
                        ta_host = unpack_tree_arrays(
                            row, floats[s, kk], self._nn, self._L
                        )
                        if cfg.check_numerics:
                            b._guard_tree(ta_host, it)
                        b._note_refine_rate(ta_host)
                        rec["grow_steps"] += int(ta_host.grow_steps)
                        rec["refine_count"] += int(ta_host.refine_count)
                        if int(ta_host.num_leaves) > 1:
                            ta_dev = jax.tree_util.tree_map(
                                jnp.asarray, ta_host
                            )
                            grown = (ta_dev, ta_host, None)
                            rec["trees_materialized"] += 1
                            rec["splits"] += int(ta_host.num_leaves) - 1
                    if b._commit_class_tree(
                        kk, grown, None, None, None, isc,
                        skip_train_score=True,
                    ):
                        should = True
                records.append(rec)
                steps_done += 1
                if b._finish_iteration(should):
                    is_finished = True
                    break
        finally:
            tracer.end(replay)
            self._note_launch(
                ses, flight, wd, it0, steps_done, wall_ms, phases,
                _compile_count() - compiles_before, records, is_finished,
                span=lsp,
            )
        return steps_done, is_finished

    def _note_launch(
        self, ses, flight, wd, it0, steps_done, wall_ms, phases,
        compiles_delta, records, is_finished, span=None,
    ) -> None:
        """One batched observability event per launch: the flight ring and
        watchdog see a single record carrying the N per-iteration
        sub-records (device-side counters — grow_steps, refine_count,
        splits — rode the packed carry out).  ``wall_ms`` is normalized
        per iteration so the watchdog's throughput EMA stays comparable
        with serial runs."""
        b = self._b
        steps = max(1, steps_done)
        event = {
            "event": "launch",
            "iter": it0 + steps - 1,
            "launch_begin": it0,
            "steps": steps_done,
            "steps_per_launch": self._n,
            "wall_ms": wall_ms / steps,
            "launch_wall_ms": wall_ms,
            "compiles_delta": compiles_delta,
            "trees_materialized": sum(
                r["trees_materialized"] for r in records
            ),
            "splits": sum(r["splits"] for r in records),
            "records": records,
            "finished": bool(is_finished),
        }
        if self._bag_tail:
            # one value an iteration, in iteration order
            bag = {
                name: [r.get(name, 0) for r in records] for name in _BAG_COUNTERS
            }
            event.update(bag)
            if span is not None:
                span.args.update(bag)
        if phases:
            event["phases"] = {k2: v * 1e3 for k2, v in phases.items()}
        if (
            b._mesh is not None
            and b.config.tree_learner != "voting"
            and ses.enabled
        ):
            from ..parallel.mesh import (
                MeshSpec,
                mesh_psum_bytes_per_iteration,
            )

            spec = getattr(b, "_mesh_spec", None) or MeshSpec(
                "data", data=int(b._mesh.devices.size)
            )
            coll = mesh_psum_bytes_per_iteration(
                max(1, b.config.num_leaves - 1),
                int(b._bins.shape[1]),
                int(b._grower_params.max_bin),
                leaf_batch=int(b.config.leaf_batch),
                spec=spec,
                launch_steps=steps,
            )
            coll = {k2: v * self._k for k2, v in coll.items()}
            event["collective"] = coll
            ses.set_gauge("collective_hist_bytes", coll["hist_bytes"])
            ses.set_gauge("collective_count_bytes", coll["count_bytes"])
            ses.set_gauge(
                "collective_ring_bytes_per_device",
                coll["ring_bytes_per_device"],
            )
        # the exact device counters of each iteration (they rode the packed
        # scan carry out) go on the launch span; the iterations ran inside
        # ONE scan, so the host has no time of theirs to record
        if span is not None:
            span.args.update(
                steps=steps_done,
                launch_wall_ms=wall_ms,
                compiles_delta=compiles_delta,
                finished=bool(is_finished),
                per_iteration=[dict(r) for r in records],
            )
        if ses.enabled:
            ses.inc("iterations", steps_done)
            ses.set_gauge("train/steps_per_launch_effective", float(steps_done))
            sample_device_memory("iteration")
            # per-iteration JSONL shape compatibility: one replayed
            # iteration event per consumed step, flagged from_launch so
            # offline tools (telemetry_summary.py) keep their
            # event=="iteration" filter across serial and launched runs.
            # Recorded BEFORE the deferred launch event so late eval
            # annotations still land on the launch JSONL line.
            for rec in records:
                ses.record({
                    "event": "iteration",
                    "iter": rec["iter"],
                    "wall_ms": wall_ms / steps,
                    "trees_materialized": rec["trees_materialized"],
                    "splits": rec["splits"],
                    "from_launch": True,
                })
            ses.record(event, defer=True)
        if flight.active:
            flight.note_event(event)
        if wd is not None:
            wd.observe(event, ses)


# ------------------------------------------------------------ fleet runner


class FleetLaunchRunner:
    """Scan-over-vmap: N lockstep fleet iterations per compiled launch.

    The carry holds every member's score cache, RNG key, bagging mask and
    finished/bad latches as parallel tuples; each scan step replays the
    fleet round exactly — per-member gradients/sampling in member order,
    then ONE vmapped grow per tree class with halted members select-fed
    the same zero-lane operands the serial fleet gives inactive members.
    Members that finish mid-window freeze bit-exactly (their carry slots
    are select-protected) and keep riding as no-op lanes, so the
    executable shape never changes as the fleet drains.
    """

    def __init__(self, trainer, n: int):
        self._t = trainer
        self._n = int(n)
        b0 = trainer.boosters[0]
        self._k = b0.num_tree_per_iteration
        self._trains = [
            bool(b0._class_need_train[kk] and b0._bins.shape[1] > 0)
            for kk in range(self._k)
        ]
        self._L = int(b0._grower_params.num_leaves)
        self._nn = self._L - 1
        self._fn = instrumented_jit(
            self._launch_impl,
            label=f"fleet/scan{self._n}",
            donate_argnums=(0,),
            closure_key=repr([b.config for b in trainer.boosters]),
        )

    def _launch_impl(self, scores, rngs, bags, halted0, its, fms, bins):
        t = self._t
        boosters = t.boosters
        m = len(boosters)
        k = self._k

        def member_inputs(i, score_i, rng_i, bag_i, it, fm_i):
            """Gradients + sampling for member i — the scan-form mirror of
            ``_fleet_begin_iter`` (same key order, same fold_in gating)."""
            b = boosters[i]
            cfg = b.config
            pair = jax.random.split(rng_i)
            rng_g, gkey = pair[0], pair[1]
            grad, hess = b.objective.get_gradients(score_i, gkey)
            if cfg.check_numerics:
                ok = jnp.logical_and(
                    jnp.isfinite(grad).all(), jnp.isfinite(hess).all()
                )
            else:
                ok = jnp.asarray(True)
            any_pad = bool(b._pad_rows)
            fixed = getattr(b, "_fixed_row_mask", None)
            if any_pad or fixed is not None:
                live = b._ones_mask[None] > 0
                if fixed is not None:
                    live = jnp.logical_and(live, fixed[None] > 0)
                grad = jnp.where(live, grad, 0.0)
                hess = jnp.where(live, hess, 0.0)
            pair = jax.random.split(rng_g)
            rng_b, bkey = pair[0], pair[1]
            if "bagging_seed" in cfg.raw:
                bkey = jax.random.fold_in(bkey, cfg.bagging_seed)
            mask, grad, hess, bag_new = b._sampler.scan_sample(
                it, grad, hess, bkey, bag_i
            )
            if any_pad:
                mask = mask * b._ones_mask
            if fixed is not None:
                mask = mask * fixed
            rng_cur = rng_b
            tkeys = []
            need_tkey = bool(
                cfg.feature_fraction_bynode < 1.0 or cfg.extra_trees
            )
            for kk in range(k):
                if not self._trains[kk] or not need_tkey:
                    tkeys.append(None)
                    continue
                pair = jax.random.split(rng_cur)
                rng_cur, tkey = pair[0], pair[1]
                if cfg.extra_trees and "extra_seed" in cfg.raw:
                    tkey = jax.random.fold_in(tkey, cfg.extra_seed)
                tkeys.append(tkey)
            return grad, hess, mask, tkeys, rng_cur, bag_new, ok

        def step(carry, xs):
            scores, rngs, bags, finished, bad = carry
            it = xs["it"]
            fms_step = xs["fm"]  # [M, F]
            halted = [
                jnp.logical_or(finished[i], bad[i] >= 0) for i in range(m)
            ]
            mem = [
                member_inputs(
                    i, scores[i], rngs[i], bags[i], it, fms_step[i]
                )
                for i in range(m)
            ]
            live = [
                jnp.logical_and(jnp.logical_not(halted[i]), mem[i][6])
                for i in range(m)
            ]
            zero_row = jnp.zeros_like(mem[0][0][0])
            ones_fm = jnp.ones_like(fms_step[0])
            new_scores = list(scores)
            any_split = [jnp.asarray(False) for _ in range(m)]
            ints_cls: List[Any] = []
            floats_cls: List[Any] = []
            for kk in range(k):
                if not self._trains[kk]:
                    continue
                grad_rows, hess_rows, mask_rows, fm_rows, keys = (
                    [], [], [], [], [],
                )
                for i in range(m):
                    grad, hess, mask, tkeys, _, _, _ = mem[i]
                    # serial fleet feeds inactive lanes value-preserving
                    # zero operands; select-feed the same here
                    grad_rows.append(
                        jnp.where(halted[i], zero_row, grad[kk])
                    )
                    hess_rows.append(
                        jnp.where(halted[i], zero_row, hess[kk])
                    )
                    mask_rows.append(jnp.where(halted[i], zero_row, mask))
                    fm_rows.append(
                        jnp.where(halted[i], ones_fm, fms_step[i])
                    )
                    key_i = (
                        tkeys[kk] if tkeys[kk] is not None else t._zero_key
                    )
                    keys.append(jnp.where(halted[i], t._zero_key, key_i))
                b0 = boosters[0]
                fta, fleaf = t._grow(
                    bins,
                    jnp.stack(grad_rows),
                    jnp.stack(hess_rows),
                    jnp.stack(mask_rows),
                    b0._num_bins,
                    b0._nan_bins,
                    jnp.stack(fm_rows),
                    t._mono_arg,
                    t._inter_arg,
                    jnp.stack(keys),
                    t._iscat_arg,
                    None,
                    t._cegb_p_arg,
                    t._cegb_u_arg,
                    t._qs_arg,
                    t._bundle_end_arg,
                    t._contri_arg,
                )
                ii, ff = jax.vmap(_pack_tree_arrays_impl)(fta)
                ints_cls.append(ii)
                floats_cls.append(ff)
                for i in range(m):
                    num_leaves_i = fta.num_leaves[i]
                    has_split = num_leaves_i > 1
                    upd = jnp.logical_and(live[i], has_split)
                    shrunk = fta.leaf_value[i] * float(
                        boosters[i]._shrinkage_rate
                    )
                    cand = new_scores[i].at[kk].add(
                        leaf_lookup(shrunk, fleaf[i])
                    )
                    new_scores[i] = jnp.where(upd, cand, new_scores[i])
                    any_split[i] = jnp.logical_or(any_split[i], has_split)
            finished2 = [
                jnp.logical_or(
                    finished[i],
                    jnp.logical_and(
                        live[i], jnp.logical_not(any_split[i])
                    ),
                )
                for i in range(m)
            ]
            bad2 = [
                jnp.where(
                    jnp.logical_and(
                        bad[i] < 0,
                        jnp.logical_and(
                            jnp.logical_not(halted[i]),
                            jnp.logical_not(mem[i][6]),
                        ),
                    ),
                    it,
                    bad[i],
                )
                for i in range(m)
            ]
            rngs2 = [
                jnp.where(halted[i], rngs[i], mem[i][4]) for i in range(m)
            ]
            bags2 = [
                jnp.where(halted[i], bags[i], mem[i][5]) for i in range(m)
            ]
            carry2 = (
                tuple(new_scores),
                tuple(rngs2),
                tuple(bags2),
                tuple(finished2),
                tuple(bad2),
            )
            # ys: [n_trained_classes, M, ...] per step
            return carry2, {
                "ints": jnp.stack(ints_cls),
                "floats": jnp.stack(floats_cls),
            }

        carry0 = (
            scores,
            rngs,
            bags,
            tuple(halted0),
            tuple(jnp.full((), -1, jnp.int32) for _ in range(m)),
        )
        return jax.lax.scan(step, carry0, {"it": its, "fm": fms})

    def run(self) -> int:
        """One fleet launch; returns the number of lockstep rounds
        consumed (the engine advances its round counter by this)."""
        t = self._t
        boosters = t.boosters
        m = len(boosters)
        k = self._k
        from .sampling import BaggingStrategy

        active = t.active_members()
        if not active:
            return 0
        # first-round constant-tree hazard scan BEFORE any score mutation:
        # if ANY active member needs the serial fallback (boost_from_average
        # off, no models, no init score), take it for the WHOLE fleet now.
        # Falling back after boosting earlier members would re-apply
        # boost_from_average inside _fleet_begin_iter (their models_ is
        # still empty), silently double-boosting train and valid scores.
        for i in active:
            b = boosters[i]
            if (
                not b.models_
                and b.objective is not None
                and not b.config.boost_from_average
                and not b._has_init_score
            ):
                t.update()
                return 1
        # first-round prologue per member (see LaunchRunner.run)
        init_scores_by_member = {}
        for i in active:
            b = boosters[i]
            cfg = b.config
            isc = [0.0] * k
            if (
                not b.models_
                and not b._has_init_score
                and b.objective is not None
                and cfg.boost_from_average
            ):
                for kk in range(k):
                    s = b.objective.boost_from_score(kk)
                    if abs(s) > _EPS:
                        isc[kk] = s
                        b._score = b._score.at[kk].add(s)
                        for entry in b._valid:
                            entry.score = entry.score.at[kk].add(s)
            init_scores_by_member[i] = isc

        ses = get_session()
        flight = get_flight()
        it0 = int(boosters[0]._iter)
        S = self._n
        its = jnp.asarray(np.arange(it0, it0 + S, dtype=np.int32))
        f_used = int(boosters[0]._bins.shape[1])
        fm_cube = np.zeros((S, m, f_used), dtype=bool)
        for i in range(m):
            b = boosters[i]
            for s in range(S):
                fm_cube[s, i] = b._feature_mask_np_for(it0 + s)
        fms = jnp.asarray(fm_cube)
        active_set = set(active)
        # traced [M] entries, NOT trace-time constants: externally-stopped
        # members enter as halted input VALUES so draining the fleet never
        # changes the executable shape (zero retraces as members stop)
        halted0 = tuple(
            jnp.asarray(i not in active_set) for i in range(m)
        )
        bags0 = tuple(
            b._sampler._mask
            if isinstance(b._sampler, BaggingStrategy)
            else jnp.zeros((1,), jnp.float32)
            for b in boosters
        )

        t0 = time.perf_counter()
        carry, ys = self._fn(
            tuple(b._score for b in boosters),
            tuple(b._rng for b in boosters),
            bags0,
            halted0,
            its,
            fms,
            boosters[0]._bins,
        )
        scores, rngs, bags, finished_dev, bad_dev = carry
        for i, b in enumerate(boosters):
            b._score = scores[i]
            if i in init_scores_by_member:  # active: carry advanced them
                b._rng = rngs[i]
                if isinstance(b._sampler, BaggingStrategy):
                    b._sampler._mask = bags[i]
        with get_tracer().span("wait/launch_fetch"):
            ints = np.asarray(ys["ints"])  # [S, n_trained, M, ints_len]
            floats = np.asarray(ys["floats"])
            bad = [int(x) for x in bad_dev]
        wall_ms = (time.perf_counter() - t0) * 1e3

        trained_idx = [kk for kk in range(k) if self._trains[kk]]
        steps_done = 0
        for s in range(S):
            it = it0 + s
            live_members = [
                i
                for i in active
                if not boosters[i]._finished
            ]
            if not live_members:
                break
            steps_done += 1
            for i in live_members:
                b = boosters[i]
                if bad[i] >= 0 and it == bad[i]:
                    b._fault_dump("numerics_gradients")
                    raise NumericsError(
                        f"non-finite gradients/hessians at iteration {it} "
                        f"for fleet member {i} inside launch window "
                        f"[{it0}, {it0 + S}) (train_steps_per_launch={S}, "
                        f"objective={b._objective_name()})"
                    )
                isc = (
                    init_scores_by_member[i] if s == 0 else [0.0] * k
                )
                should = False
                for kk in range(k):
                    grown = None
                    if self._trains[kk]:
                        ci = trained_idx.index(kk)
                        ta_host = unpack_tree_arrays(
                            ints[s, ci, i], floats[s, ci, i],
                            self._nn, self._L,
                        )
                        if b.config.check_numerics:
                            b._guard_tree(ta_host, it)
                        b._note_refine_rate(ta_host)
                        if int(ta_host.num_leaves) > 1:
                            ta_dev = jax.tree_util.tree_map(
                                jnp.asarray, ta_host
                            )
                            grown = (ta_dev, ta_host, None)
                    if b._commit_class_tree(
                        kk, grown, None, None, None, isc,
                        skip_train_score=True,
                    ):
                        should = True
                b._fleet_end_iter(should)
        t._round += steps_done
        if ses.enabled:
            ses.inc("fleet/iterations", steps_done)
            ses.set_gauge(
                "train/steps_per_launch_effective", float(max(1, steps_done))
            )
        if flight.active:
            flight.note_event(
                {
                    "event": "fleet_launch",
                    "round": t._round,
                    "launch_begin": it0,
                    "steps": steps_done,
                    "steps_per_launch": S,
                    "fleet": m,
                    "wall_ms": wall_ms,
                    "active": len(t.active_members()),
                }
            )
        return steps_done


__all__ = [
    "LaunchRunner",
    "FleetLaunchRunner",
    "clamp_steps",
    "launch_ineligible_reason",
    "resolve_fleet_launch_steps",
    "resolve_launch_steps",
    "resolve_requested_steps",
]
