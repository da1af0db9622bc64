"""Vmapped model-fleet training: M boosters, one compiled grow step.

A *fleet* trains M same-shape models in lockstep over one shared binned
dataset: the [N, F] bin planes, bin counts and NaN bins are broadcast
(unmapped) operands while gradients, hessians, bagging masks, feature
masks and RNG keys carry a leading model axis.  Each boosting iteration
issues ONE batched grow per tree class (``parallel.mesh.make_fleet_grow``,
a ``jax.vmap`` of the compiled grow step) instead of M serial grows, so
the whole sweep shares a single executable and the histogram phase runs
all M members per kernel launch.  Under ``tree_learner=data`` the member
histograms travel in one stacked psum payload per step.

Byte parity: the batched grow is value-identical per member to the solo
``grow_tree`` call (capacity buckets are unified across the fleet via an
``axis_name`` pmax — padding-only, see ``GrowerParams.fleet_axis_name``),
and the host-side preamble/commit reuse the Booster's own
``_fleet_begin_iter`` / ``_commit_class_tree`` methods, so every member's
model dump is byte-identical to the model its params would produce in a
solo ``lgb.train`` run.

v1 scope: members must share the training Dataset and identical
``GrowerParams`` (sweeps over seeds, learning_rate, bagging/GOSS
fractions, extra_seed, and CV-fold row masks).  Finished or early-stopped
members become value-preserving no-op lanes (zero gradients, outputs
discarded) so the executable never retraces as the fleet drains.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..obs.registry import get_session
from ..obs.trace import get_tracer
from ..obs.flight import get_flight
from ..obs.device import sample_device_memory
from .gbdt import Booster


def _same_grower_params(a, b) -> bool:
    """GrowerParams are frozen dataclasses of hashable leaves; direct
    equality is the exact static-trace-compatibility test (anything that
    differs would have produced a different executable)."""
    return a == b


def _arrays_equal(a, b) -> bool:
    if a is None or b is None:
        return a is None and b is None
    return np.array_equal(np.asarray(a), np.asarray(b))


class FleetTrainer:
    """Lockstep trainer for a list of same-shape Boosters.

    One ``update()`` call advances every active member by one boosting
    iteration with a single batched grow per tree class.  Members that
    finish (no positive-gain split) or are stopped externally
    (``stop_member``, e.g. per-member early stopping) keep their final
    state and ride along as zero-gradient lanes — the operand shapes
    never change, so the warm executable is reused to the end.
    """

    def __init__(self, boosters: List[Booster]):
        if not boosters:
            raise ValueError("fleet needs at least one booster")
        self.boosters = list(boosters)
        self._stopped = [False] * len(self.boosters)
        self._round = 0
        self._validate()
        b0 = self.boosters[0]
        import dataclasses

        from ..parallel.mesh import MeshSpec, make_fleet_grow

        # the fused Pallas grow step is a serial-path specialization; the
        # two-launch XLA composition is its byte-identical oracle, so the
        # fleet always traces the XLA path (safe under vmap everywhere)
        params = dataclasses.replace(b0._grower_params, grow_fused=False)
        spec = getattr(b0, "_mesh_spec", None)
        if spec is None:
            size = b0._mesh.size if b0._mesh is not None else 1
            spec = MeshSpec("data", data=size)
        self._grow = make_fleet_grow(b0._mesh, params, spec)
        self._mesh_spec = spec
        f_used = b0._bins.shape[1]
        # dummy operands for statically-gated-off features (same contract
        # as Booster._setup_sharded_grower: concrete arrays stand in for
        # absent optionals and are dead code inside the trace)
        self._mono_arg = (
            b0._monotone
            if b0._monotone is not None
            else jnp.zeros((f_used,), jnp.int8)
        )
        self._inter_arg = (
            b0._interaction_sets
            if b0._interaction_sets is not None
            else jnp.ones((1, f_used), bool)
        )
        self._iscat_arg = (
            b0._is_cat if b0._is_cat is not None else jnp.zeros((f_used,), bool)
        )
        self._bundle_end_arg = (
            b0._bundle_end
            if b0._bundle_end is not None
            else jnp.full((1, 1), -1, jnp.int32)
        )
        self._contri_arg = (
            b0._feature_contri
            if b0._feature_contri is not None
            else jnp.ones((f_used,), jnp.float32)
        )
        self._cegb_p_arg = jnp.zeros((f_used,), jnp.float32)
        self._cegb_u_arg = jnp.zeros((f_used,), bool)
        self._qs_arg = (jnp.float32(1.0), jnp.float32(1.0))
        self._zero_key = jnp.zeros((2,), jnp.uint32)

    # ------------------------------------------------------------ validation

    def _validate(self) -> None:
        b0 = self.boosters[0]
        for i, b in enumerate(self.boosters):
            where = f"fleet member {i}"
            if type(b) is not Booster:
                raise ValueError(
                    f"{where}: fleet v1 supports plain gbdt/goss Boosters "
                    f"only, got {type(b).__name__}"
                )
            if b.train_set is not b0.train_set:
                raise ValueError(
                    f"{where}: all fleet members must share the SAME "
                    "training Dataset object (same-shape sweeps; use "
                    "set_row_mask for CV folds)"
                )
            if not _same_grower_params(b._grower_params, b0._grower_params):
                raise ValueError(
                    f"{where}: GrowerParams differ from member 0 — fleet "
                    "members must be trace-compatible (identical "
                    "num_leaves/max_bin/hist_mode/regularization/...); "
                    "sweep seeds, learning_rate, or sampling fractions "
                    "instead"
                )
            cfg = b.config
            if b.objective is None:
                raise ValueError(f"{where}: fleet needs a built-in objective")
            if b.objective.is_renew_tree_output:
                raise ValueError(
                    f"{where}: objectives with renew_tree_output "
                    f"({type(b.objective).__name__}) are not fleet-capable"
                )
            for flag in ("linear_tree", "use_quantized_grad"):
                if getattr(cfg, flag):
                    raise ValueError(f"{where}: {flag} is not fleet-capable")
            if b._cegb_coupled is not None:
                raise ValueError(f"{where}: CEGB is not fleet-capable")
            if getattr(b, "_multiproc", False):
                raise ValueError(
                    f"{where}: multi-process feeding is not fleet-capable"
                )
            if b._forced is not None:
                raise ValueError(
                    f"{where}: forced splits are not fleet-capable"
                )
            if b._grower_params.hist_mode == "seg":
                raise ValueError(
                    f"{where}: hist_mode='seg' (Pallas sort path) is not "
                    "fleet-capable yet; use ordered/gather/full"
                )
            if b.num_tree_per_iteration != b0.num_tree_per_iteration:
                raise ValueError(f"{where}: num_tree_per_iteration differs")
            if list(b._class_need_train) != list(b0._class_need_train):
                raise ValueError(f"{where}: _class_need_train differs")
            if len(b.models_) or b._iter:
                raise ValueError(f"{where}: fleet members must be untrained")
            # dataset-derived static operands must match member 0 so the
            # shared (unmapped) operands are correct for every lane
            for name in ("_monotone", "_interaction_sets", "_is_cat",
                         "_bundle_end", "_feature_contri"):
                if not _arrays_equal(getattr(b, name), getattr(b0, name)):
                    raise ValueError(
                        f"{where}: {name} differs from member 0"
                    )

    # -------------------------------------------------------------- controls

    @property
    def size(self) -> int:
        return len(self.boosters)

    def active_members(self) -> List[int]:
        return [
            i
            for i, b in enumerate(self.boosters)
            if not (b._finished or self._stopped[i])
        ]

    def stop_member(self, i: int) -> None:
        """Externally deactivate a member (early stopping); its state is
        frozen and its lane degrades to a zero-fed no-op."""
        self._stopped[i] = True

    def done(self) -> bool:
        return not self.active_members()

    # ------------------------------------------------------------- iteration

    def update_launch(self, n: int) -> int:
        """Advance up to ``n`` lockstep rounds in ONE compiled launch
        (scan-over-vmap — boosting/launch.py).  Per-member models stay
        byte-identical to the serial round loop; externally-stopped
        members ride as select-frozen no-op lanes.  Returns the number of
        rounds consumed."""
        if int(n) <= 1:
            self.update()
            return 1
        from .launch import FleetLaunchRunner

        cache = getattr(self, "_launch_runners", None)
        if cache is None:
            cache = self._launch_runners = {}
        runner = cache.get(int(n))
        if runner is None:
            runner = cache[int(n)] = FleetLaunchRunner(self, int(n))
        return runner.run()

    def update(self) -> List[bool]:
        """One lockstep boosting iteration.  Returns the per-member
        inactive flags (True = finished or stopped) after the round."""
        boosters = self.boosters
        m = len(boosters)
        active = self.active_members()
        if not active:
            return [True] * m
        ses = get_session()
        b0 = boosters[0]
        k = b0.num_tree_per_iteration
        ops: Dict[int, dict] = {}
        for i in active:
            ops[i] = boosters[i]._fleet_begin_iter()

        should = {i: False for i in active}
        template = ops[active[0]]
        zero_row = jnp.zeros_like(template["grad"][0])
        ones_fm = jnp.ones_like(template["feature_mask"])
        for kk in range(k):
            if not (b0._class_need_train[kk] and b0._bins.shape[1] > 0):
                for i in active:
                    o = ops[i]
                    if boosters[i]._commit_class_tree(
                        kk, None, o["grad"], o["hess"], o["mask"],
                        o["init_scores"],
                    ):
                        should[i] = True
                continue
            grown = self._grow_fleet_class(kk, ops, zero_row, ones_fm)
            for i in active:
                o = ops[i]
                if boosters[i]._commit_class_tree(
                    kk, grown[i], o["grad"], o["hess"], o["mask"],
                    o["init_scores"],
                ):
                    should[i] = True

        for i in active:
            boosters[i]._fleet_end_iter(should[i])
        self._round += 1
        inactive = [
            b._finished or self._stopped[i] for i, b in enumerate(boosters)
        ]
        if ses.enabled:
            ses.inc("fleet/iterations")
            self._note_collectives(ses, k)
        flight = get_flight()
        if flight.active:
            flight.note_event(
                {
                    "event": "fleet_iteration",
                    "round": self._round,
                    "fleet": m,
                    "active": len(active),
                    "finished": sum(1 for f in inactive if f),
                }
            )
        return inactive

    def _grow_fleet_class(self, kk, ops, zero_row, ones_fm):
        """One batched grow for tree class kk: stack the per-member traced
        operands (inactive lanes get value-preserving zero slots), dispatch
        the single vmapped executable, then bulk-fetch all member trees in
        one transfer.  Returns {member index: (ta, ta_host, leaf_id)} for
        active members."""
        boosters = self.boosters
        b0 = boosters[0]
        grad_rows, hess_rows, mask_rows, fm_rows, keys = [], [], [], [], []
        for i in range(len(boosters)):
            o = ops.get(i)
            if o is None:
                grad_rows.append(zero_row)
                hess_rows.append(zero_row)
                mask_rows.append(zero_row)
                fm_rows.append(ones_fm)
                keys.append(self._zero_key)
            else:
                grad_rows.append(o["grad"][kk])
                hess_rows.append(o["hess"][kk])
                mask_rows.append(o["mask"])
                fm_rows.append(o["feature_mask"])
                r = o["tree_rngs"][kk]
                keys.append(self._zero_key if r is None else r)
        with get_tracer().span("train/grow", phase="grow", timer="tree/grow"):
            fta, fleaf = self._grow(
                b0._bins,
                jnp.stack(grad_rows),
                jnp.stack(hess_rows),
                jnp.stack(mask_rows),
                b0._num_bins,
                b0._nan_bins,
                jnp.stack(fm_rows),
                self._mono_arg,
                self._inter_arg,
                jnp.stack(keys),
                self._iscat_arg,
                None,
                self._cegb_p_arg,
                self._cegb_u_arg,
                self._qs_arg,
                self._bundle_end_arg,
                self._contri_arg,
            )
            get_session().sync(fleaf)
            sample_device_memory("grow")
        from ..ops.grower import fetch_fleet_tree_arrays

        ta_hosts = fetch_fleet_tree_arrays(fta)  # wait/fetch_tree inside
        grown = {}
        for i in ops:
            b = boosters[i]
            ta_i = jax.tree_util.tree_map(lambda a: a[i], fta)
            ta_host = ta_hosts[i]
            if b.config.check_numerics:
                b._guard_tree(ta_host, b._iter)
            b._note_refine_rate(ta_host)
            grown[i] = (ta_i, ta_host, fleaf[i])
        return grown

    def _note_collectives(self, ses, k: int) -> None:
        """Analytic psum gauges for the fleet step under a data mesh: one
        stacked [M, ...] payload per step instead of M separate rounds."""
        b0 = self.boosters[0]
        if b0._mesh is None or b0.config.tree_learner == "voting":
            return
        from ..parallel.mesh import fleet_psum_bytes_per_iteration

        coll = fleet_psum_bytes_per_iteration(
            max(1, b0.config.num_leaves - 1),
            int(b0._bins.shape[1]),
            int(b0._grower_params.max_bin),
            fleet=len(self.boosters),
            leaf_batch=int(b0.config.leaf_batch),
            spec=self._mesh_spec,
        )
        coll = {k2: v * k if k2 != "fleet" else v for k2, v in coll.items()}
        ses.set_gauge("fleet/psum_hist_bytes", coll["hist_bytes"])
        ses.set_gauge("fleet/psum_count_bytes", coll["count_bytes"])
        ses.set_gauge(
            "fleet/psum_ring_bytes_per_device", coll["ring_bytes_per_device"]
        )


__all__ = ["FleetTrainer"]
