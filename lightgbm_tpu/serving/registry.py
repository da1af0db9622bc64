"""Multi-model registry: warmed ladders, LRU budget, atomic hot-swap.

Each loaded model gets a ``ModelEntry`` carrying its Booster, a
per-model-version **scope** string, and a scoped ``StreamingPredictor``
installed as the booster's engine — so co-resident models never collide
on an executable-cache key (scoped keys) and their retrace labels are
separable (``predict/stream/{scope}/{variant}``).

Load and hot-swap both warm the FULL bucket ladder before the model can
serve a request: ``compile_predict`` AOT-lowers every ladder executable,
then one dummy predict per bucket primes the (row-local) output-transform
jits at each padded size — after that, no request of any size compiles
anything (tests assert ``compile_counts_by_label`` stays flat).

Hot-swap atomicity: the new version is built and warmed entirely off to
the side; the cutover is a single dict assignment under the registry lock
tagged with a monotonic generation counter.  Dispatchers acquire ONE
entry per dispatch call (refcounted), so every request's rows are served
by exactly one model version.  The old entry is retired — its scoped
executables evicted — only once its in-flight count drains to zero.  A
warm-up failure (including an injected ``kill_during_warmup`` chaos
fault) leaves the old generation serving and dumps the flight ring.

LRU eviction: ``memory_budget_bytes`` bounds the summed device-table
footprint estimate across resident models; loading past the budget
evicts least-recently-used idle models first.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..obs.flight import get_flight
from ..obs.registry import get_session
from ..obs.trace import get_tracer
from ..predict import (
    LADDER_MIN,
    StreamingPredictor,
    evict_exec_scope,
    ladder_buckets,
)
from ..resilience import chaos


class ModelEntry:
    """One resident model version; refcounted for drain-before-retire."""

    def __init__(self, model_id: str, version: int, booster) -> None:
        self.model_id = model_id
        self.version = int(version)
        self.scope = f"{model_id}@v{version}"
        self.booster = booster
        self.generation = 0  # assigned at publish, under the registry lock
        self.inflight = 0
        self.retired = False
        self.device_bytes = 0
        self.warm_compiles = 0
        self.pred_engine = "walk"  # resolved at warm time
        self.last_used = time.monotonic()

    def describe(self) -> Dict[str, Any]:
        return {
            "model_id": self.model_id,
            "version": self.version,
            "generation": self.generation,
            "scope": self.scope,
            "inflight": self.inflight,
            "device_bytes": self.device_bytes,
            "num_trees": len(self.booster.models_),
            "pred_engine": self.pred_engine,
        }


class ModelRegistry:
    """Keyed model store with warmed ladders and atomic cutover."""

    def __init__(
        self,
        *,
        chunk: int = 4096,
        memory_budget_bytes: int = 0,
        num_buffers: int = 2,
        kinds=("value",),
        pred_engine: Optional[str] = None,
    ) -> None:
        self.chunk = max(LADDER_MIN, int(chunk))
        self.memory_budget_bytes = int(memory_budget_bytes)
        self.num_buffers = int(num_buffers)
        self.kinds = tuple(kinds)
        # serve-level pred_engine override; None defers to each booster's
        # own config (lgb.serve(params={"pred_engine": ...}) lands here)
        self.pred_engine = pred_engine
        self._lock = threading.RLock()
        self._live: Dict[str, ModelEntry] = {}
        self._generation = 0

    # ----------------------------------------------------------- lifecycle
    def load(self, model_id: str, booster, *, warm: bool = True) -> ModelEntry:
        """Register a new model id; warms its full ladder before it is
        visible to dispatchers.  Evicts LRU idle models past the budget."""
        with self._lock:
            if model_id in self._live:
                raise ValueError(
                    f"model '{model_id}' already loaded; use hot_swap"
                )
        entry = ModelEntry(model_id, 1, booster)
        if warm:
            with get_tracer().span(
                "lifecycle/model_warm",
                "lifecycle",
                args={"model_id": model_id, "version": entry.version},
            ):
                self._warm(entry)
        evicted = []
        with self._lock:
            if model_id in self._live:
                raise ValueError(
                    f"model '{model_id}' already loaded; use hot_swap"
                )
            evicted = self._evict_for_budget_locked(entry.device_bytes)
            self._generation += 1
            entry.generation = self._generation
            self._live[model_id] = entry
        for old in evicted:
            self._retire_now(old)
        self._note_lifecycle("serve_model_load", entry)
        ses = get_session()
        if ses.enabled:
            ses.inc("serve/load_total")
        return entry

    def register_fleet(
        self,
        boosters,
        *,
        model_ids=None,
        prefix: str = "fleet",
        warm: bool = True,
    ) -> List[ModelEntry]:
        """Bulk-register a trained model fleet (engine.train_fleet output).

        Members become independent entries named ``{prefix}/{i}`` (or the
        explicit ``model_ids``), each AOT-warmed before it is visible to
        dispatchers.  Every load runs under the registry's existing memory
        budget: a fleet larger than the budget admits members in order and
        LRU-evicts idle earlier ones, exactly like any other load — there
        is no fleet-wide reservation.  On a member's warm-up failure the
        members already registered STAY live and the error propagates, so
        callers can retry or shrink the fleet without losing progress."""
        boosters = list(boosters)
        if model_ids is not None:
            ids = [str(m) for m in model_ids]
            if len(ids) != len(boosters):
                raise ValueError(
                    f"model_ids has {len(ids)} entries for "
                    f"{len(boosters)} boosters"
                )
        else:
            ids = [f"{prefix}/{i}" for i in range(len(boosters))]
        if len(set(ids)) != len(ids):
            raise ValueError("fleet model ids must be unique")
        with self._lock:
            clash = [m for m in ids if m in self._live]
        if clash:
            raise ValueError(
                f"model ids already loaded: {clash}; use hot_swap"
            )
        entries = []
        for mid, b in zip(ids, boosters):
            entries.append(self.load(mid, b, warm=warm))
        ses = get_session()
        if ses.enabled:
            ses.inc("serve/fleet_register_total")
        return entries

    def hot_swap(self, model_id: str, booster) -> ModelEntry:
        """Atomically replace the live version of ``model_id``.

        The replacement's FULL ladder is warmed before the cutover; the
        cutover is one dict assignment under the lock with a fresh
        generation.  On warm-up failure the old generation keeps serving,
        the attempt's scoped executables are dropped, and the flight
        recorder dumps (reason ``swap_warmup_failure``)."""
        with self._lock:
            old = self._live.get(model_id)
            if old is None:
                raise KeyError(f"model '{model_id}' is not loaded")
            version = old.version + 1
        entry = ModelEntry(model_id, version, booster)
        try:
            with get_tracer().span(
                "lifecycle/swap_warm",
                "lifecycle",
                args={"model_id": model_id, "to_version": version},
            ):
                self._warm(entry)
        except BaseException as e:
            evict_exec_scope(entry.scope)
            flight = get_flight()
            flight.note_sticky(
                {
                    "event": "serve_swap_failed",
                    "model_id": model_id,
                    "from_version": old.version,
                    "to_version": version,
                    "error": repr(e),
                }
            )
            get_tracer().instant(
                "lifecycle/swap_failed",
                "lifecycle",
                args={
                    "model_id": model_id,
                    "to_version": version,
                    "error": repr(e)[:200],
                },
            )
            flight.dump(f"swap_warmup_failure:{model_id}")
            ses = get_session()
            if ses.enabled:
                ses.inc("serve/swap_failed_total")
            raise
        with self._lock:
            old = self._live.get(model_id)
            self._generation += 1
            entry.generation = self._generation
            self._live[model_id] = entry
            retire_now = None
            if old is not None:
                old.retired = True
                if old.inflight == 0:
                    retire_now = old
        get_tracer().instant(
            "lifecycle/swap_flip",
            "lifecycle",
            args={
                "model_id": model_id,
                "from_version": old.version if old is not None else None,
                "to_version": entry.version,
                "generation": entry.generation,
            },
        )
        if retire_now is not None:
            self._retire_now(retire_now)
        self._note_lifecycle(
            "serve_model_swap",
            entry,
            from_version=old.version if old is not None else None,
            from_generation=old.generation if old is not None else None,
        )
        ses = get_session()
        if ses.enabled:
            ses.inc("serve/swap_total")
        return entry

    def unload(self, model_id: str) -> None:
        with self._lock:
            entry = self._live.pop(model_id, None)
            if entry is None:
                return
            entry.retired = True
            retire_now = entry.inflight == 0
        if retire_now:
            self._retire_now(entry)
        self._note_lifecycle("serve_model_unload", entry)

    def close(self) -> None:
        for model_id in list(self._live):
            self.unload(model_id)

    # ------------------------------------------------------------ dispatch
    def dispatch(
        self,
        model_id: str,
        plans: List[Tuple[np.ndarray, int]],
        **predict_kwargs,
    ) -> Tuple[np.ndarray, Dict[str, Any]]:
        """Predict a batcher's plan list under ONE entry acquisition.

        Every plan matrix is a warm ladder bucket; ``pred_chunk_rows`` is
        pinned to the registry chunk so dispatch hits exactly the warmed
        executables.  Returns the concatenated live-row predictions and
        the serving model's identity."""
        entry = self.acquire(model_id)
        if self.pred_engine is not None:
            predict_kwargs.setdefault("pred_engine", self.pred_engine)
        try:
            outs = [
                np.asarray(
                    entry.booster.predict(
                        mat,
                        pred_chunk_rows=self.chunk,
                        pred_num_buffers=self.num_buffers,
                        **predict_kwargs,
                    )
                )[:live]
                for mat, live in plans
            ]
            preds = outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)
            return preds, {
                "model_id": entry.model_id,
                "version": entry.version,
                "generation": entry.generation,
            }
        finally:
            self.release(entry)

    def acquire(self, model_id: str) -> ModelEntry:
        with self._lock:
            entry = self._live.get(model_id)
            if entry is None:
                raise KeyError(f"model '{model_id}' is not loaded")
            entry.inflight += 1
            entry.last_used = time.monotonic()
            return entry

    def release(self, entry: ModelEntry) -> None:
        with self._lock:
            entry.inflight -= 1
            retire_now = entry.retired and entry.inflight == 0
        if retire_now:
            self._retire_now(entry)

    def booster(self, model_id: str):
        """The live Booster for ``model_id`` (refresh loop's refit base)."""
        with self._lock:
            entry = self._live.get(model_id)
            if entry is None:
                raise KeyError(f"model '{model_id}' is not loaded")
            return entry.booster

    # ------------------------------------------------------------- introspect
    def models(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [e.describe() for e in self._live.values()]

    def generation(self, model_id: Optional[str] = None) -> int:
        with self._lock:
            if model_id is None:
                return self._generation
            entry = self._live.get(model_id)
            return entry.generation if entry is not None else -1

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(e.device_bytes for e in self._live.values())

    # -------------------------------------------------------------- warmup
    def _warm(self, entry: ModelEntry) -> None:
        """AOT-warm the full ladder for this entry's scoped engine, then
        prime the output transform with one dummy predict per bucket.

        The booster's ``pred_engine`` resolves ONCE here: a matmul/auto
        model that passes eligibility gets BOTH ladders warmed per scope
        (tensor + walker fallback), an ineligible one skips the matmul
        ladder entirely — warm time and HBM never double for executables
        the model can't use."""
        b = entry.booster
        engine = StreamingPredictor(b, scope=entry.scope)
        b._stream = engine  # predict() now routes through the scoped engine
        requested = self.pred_engine or getattr(b.config, "pred_engine", "walk")
        t0, t1 = b._tree_range(0, None)
        if t1 > t0 and b.models_:
            entry.pred_engine = engine.resolve_engine(
                requested, b._predict_space(t0, t1), t0, t1
            )[0]
        compiles = 0
        n_features = max(1, b.max_feature_idx + 1)
        for step, bucket in enumerate(ladder_buckets(self.chunk)):
            # chaos seam: kill_during_warmup injects a fault mid-ladder
            # (models the warmup worker dying) — hot_swap must leave the
            # old generation serving and dump the flight ring
            chaos.maybe_kill_warmup(entry.scope, step)
            compiles += b.compile_predict(
                chunk=bucket, kinds=self.kinds, pred_engine=requested
            )
            # dummy predict at exactly this bucket's padded size: the
            # convert_output/average transforms are row-count-shaped jits
            b.predict(
                np.zeros((bucket, n_features)),
                pred_chunk_rows=self.chunk,
                pred_num_buffers=self.num_buffers,
                pred_engine=requested,
            )
        entry.warm_compiles = compiles
        entry.device_bytes = self._table_bytes(engine, b, requested)

    @staticmethod
    def _table_bytes(
        engine: StreamingPredictor, booster, requested: str = "walk"
    ) -> int:
        """Estimated device residency: the stacked forest tables the
        streaming executables take as call arguments (compiled code and
        transient output buffers are not counted)."""
        import jax

        t0, t1 = booster._tree_range(0, None)
        if t1 <= t0:
            return 0
        space = booster._predict_space(t0, t1)
        resolved, _ = engine.resolve_engine(requested, space, t0, t1)
        # a matmul resolution keeps BOTH engines' tables resident (the
        # walker ladder is warmed as the compile-free fallback)
        engines = ("matmul", "walk") if resolved == "matmul" else ("walk",)
        total = 0
        for eng in engines:
            _, tables, _ = engine._tables(space, t0, t1, engine=eng)
            total += sum(
                a.nbytes
                for a in jax.tree_util.tree_leaves(tables)
                if hasattr(a, "nbytes")
            )
        return int(total)

    # ------------------------------------------------------------ eviction
    def _evict_for_budget_locked(self, incoming_bytes: int) -> List[ModelEntry]:
        """Pop LRU idle entries until the incoming model fits the budget.
        Called under the lock; retirement happens outside it."""
        if self.memory_budget_bytes <= 0:
            return []
        evicted: List[ModelEntry] = []
        while True:
            resident = sum(e.device_bytes for e in self._live.values())
            if resident + incoming_bytes <= self.memory_budget_bytes:
                break
            idle = [e for e in self._live.values() if e.inflight == 0]
            if not idle:
                break  # nothing evictable: over-budget, but keep serving
            victim = min(idle, key=lambda e: e.last_used)
            del self._live[victim.model_id]
            victim.retired = True
            evicted.append(victim)
        return evicted

    def _retire_now(self, entry: ModelEntry) -> None:
        dropped = evict_exec_scope(entry.scope)
        entry.booster._stream = None
        get_tracer().instant(
            "lifecycle/drain_retire",
            "lifecycle",
            args={
                "model_id": entry.model_id,
                "version": entry.version,
                "executables_dropped": dropped,
            },
        )
        get_flight().note_event(
            {
                "event": "serve_model_retired",
                "model_id": entry.model_id,
                "version": entry.version,
                "executables_dropped": dropped,
            }
        )
        ses = get_session()
        if ses.enabled:
            ses.inc("serve/retire_total")

    # ------------------------------------------------------------ telemetry
    def _note_lifecycle(self, event: str, entry: ModelEntry, **extra) -> None:
        get_flight().note_sticky(
            {"event": event, **entry.describe(), **extra}
        )
        ses = get_session()
        if ses.enabled:
            with self._lock:
                ses.update_gauges(
                    {
                        "serve/active_generation": float(self._generation),
                        "serve/models_loaded": float(len(self._live)),
                        "serve/resident_bytes": float(
                            sum(
                                e.device_bytes for e in self._live.values()
                            )
                        ),
                        f"serve/generation/{entry.model_id}": float(
                            entry.generation
                        ),
                    }
                )
