"""Telemetry registry: process-global counters, gauges and per-iteration
event records.

Reference analog: the C++ tree has ``Common::Timer global_timer``
(include/LightGBM/utils/common.h:979) as its only runtime observability.
Here the registry is the structured superset the perf work needs: every hot
path (booster update, grower, streaming predictor, collectives) reports into
one process-global :class:`TelemetrySession`, and each boosting iteration /
predict chunk becomes one JSON-serializable event.

Disabled (the default) the session is a handful of attribute checks — hot
paths test ``session.enabled`` once and skip everything else, so training
pays no measurable overhead.  Enabled, events accumulate in memory
(``session.events``) and, when a sink path is configured, stream to a JSONL
file (one event per line).

Iteration events are written DEFERRED: the event is visible in
``session.events`` immediately, but its JSONL line is flushed when the next
event arrives (or at ``flush_pending``/``close``), so late annotations —
eval metrics computed after the update — land inside the same line.
"""

from __future__ import annotations

import json
import threading
from typing import Any, Dict, List, Optional


def _jsonable(obj: Any) -> Any:
    """Best-effort conversion of numpy/jax scalars inside an event."""
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    item = getattr(obj, "item", None)
    if callable(item):
        try:
            return item()
        except Exception:
            pass
    return str(obj)


class TelemetrySession:
    """Process-global telemetry state (counters / gauges / events)."""

    def __init__(self) -> None:
        self.enabled = False
        self.sync_timing = False
        # deep-device observability knobs (obs_device_accounting /
        # obs_collectives): executable cost/memory capture costs an extra
        # trace per jit label, so it is explicit opt-in; measured
        # collectives ride along whenever telemetry is on
        self.device_accounting = False
        self.measure_collectives = False
        self.counters: Dict[str, int] = {}
        self.gauges: Dict[str, float] = {}
        self.events: List[Dict[str, Any]] = []
        self.sink_path = ""
        self._sink = None
        self._pending: Optional[Dict[str, Any]] = None
        self._phases: Optional[Dict[str, float]] = None
        self._lock = threading.Lock()

    # ---------------------------------------------------------- lifecycle
    def configure(
        self,
        enabled: bool = True,
        sync_timing: bool = False,
        sink_path: str = "",
        device_accounting: Optional[bool] = None,
        measure_collectives: Optional[bool] = None,
    ) -> "TelemetrySession":
        """(Re)configure the session; opens the JSONL sink when given."""
        with self._lock:
            self.enabled = bool(enabled)
            self.sync_timing = bool(sync_timing) and self.enabled
            if device_accounting is not None:
                self.device_accounting = bool(device_accounting) and self.enabled
            elif not self.enabled:
                self.device_accounting = False
            if measure_collectives is not None:
                self.measure_collectives = (
                    bool(measure_collectives) and self.enabled
                )
            elif not self.enabled:
                self.measure_collectives = False
            if sink_path != self.sink_path or not enabled:
                self._flush_pending_locked()
                if self._sink is not None:
                    self._sink.close()
                    self._sink = None
                self.sink_path = ""
            if enabled and sink_path and self._sink is None:
                self._sink = open(sink_path, "a")
                self.sink_path = sink_path
        return self

    def close(self) -> None:
        with self._lock:
            self._flush_pending_locked()
            if self._sink is not None:
                self._sink.close()
                self._sink = None
            self.sink_path = ""

    def reset(self) -> None:
        """Clear recorded data; keeps enabled/sink configuration."""
        with self._lock:
            self.counters.clear()
            self.gauges.clear()
            self.events.clear()
            self._pending = None
            self._phases = None

    # --------------------------------------------------- counters / gauges
    def inc(self, name: str, n: int = 1) -> None:
        if not self.enabled:
            return
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def set_gauge(self, name: str, value: float) -> None:
        if not self.enabled:
            return
        with self._lock:
            self.gauges[name] = value

    def update_gauges(self, values: Dict[str, float]) -> None:
        """Set many gauges under one lock acquisition (the serving plane
        publishes its whole latency window atomically so a concurrent
        /metrics scrape never sees p50 from one window and p99 from the
        next)."""
        if not self.enabled:
            return
        with self._lock:
            self.gauges.update(values)

    def set_gauge_max(self, name: str, value: float) -> None:
        """Monotone-max gauge (HBM watermarks, worst-case executable cost
        across ladder buckets: re-recording never lowers the reading)."""
        if not self.enabled:
            return
        with self._lock:
            prev = self.gauges.get(name)
            if prev is None or value > prev:
                self.gauges[name] = value

    def restore_counters(self, counters: Dict[str, int]) -> None:
        """Merge checkpointed counter values into the live session so a
        resumed run's counters continue from the killed run's totals."""
        if not self.enabled:
            return
        with self._lock:
            for name, value in counters.items():
                self.counters[name] = self.counters.get(name, 0) + int(value)

    # -------------------------------------------------------------- events
    def record(self, event: Dict[str, Any], defer: bool = False) -> None:
        """Append an event; write its JSONL line (deferred events are
        flushed when the next event arrives, so they stay annotatable)."""
        if not self.enabled:
            return
        with self._lock:
            self._flush_pending_locked()
            self.events.append(event)
            if self._sink is None:
                return
            if defer:
                self._pending = event
            else:
                self._write_locked(event)

    def record_alert(self, event: Dict[str, Any]) -> None:
        """Record an alert without flushing a deferred iteration event.

        Plain :meth:`record` flushes the pending deferred event first; an
        alert raised between an iteration's ``update`` and its late eval
        annotation must not do that (the annotation would land on the
        alert instead, and the iteration's JSONL line would miss it).  The
        alert is inserted *before* the pending event in ``events`` and its
        JSONL line is written immediately; the pending event stays pending
        and stays ``events[-1]`` for ``annotate_last``.
        """
        if not self.enabled:
            return
        with self._lock:
            if self._pending is not None and self.events and (
                self.events[-1] is self._pending
            ):
                self.events.insert(len(self.events) - 1, event)
            else:
                self.events.append(event)
            if self._sink is not None:
                self._write_locked(event)

    def annotate_last(self, fields: Dict[str, Any]) -> None:
        """Merge fields into the most recent event (pre-flush for JSONL)."""
        if not self.enabled:
            return
        with self._lock:
            if self.events:
                self.events[-1].update(fields)

    def flush_pending(self) -> None:
        with self._lock:
            self._flush_pending_locked()

    def _flush_pending_locked(self) -> None:
        if self._pending is not None and self._sink is not None:
            self._write_locked(self._pending)
        self._pending = None

    def _write_locked(self, event: Dict[str, Any]) -> None:
        self._sink.write(json.dumps(_jsonable(event)) + "\n")
        self._sink.flush()

    # -------------------------------------------------------- phase timing
    def begin_iteration(self) -> None:
        """Open a per-iteration phase accumulator (see :meth:`add_phase`)."""
        if self.enabled:
            self._phases = {}

    def end_iteration(self) -> Dict[str, float]:
        """Close the accumulator; returns {phase: seconds}."""
        phases, self._phases = self._phases, None
        return phases or {}

    def add_phase(self, name: str, seconds: float) -> None:
        """Add host wall time to ``name`` in the open iteration accumulator
        (fed by the trace spans of the layer boundaries, obs/trace.py
        ``TraceRecorder.span(phase=...)``).  A no-op when telemetry is off
        or no iteration is open, so hot paths call it unconditionally."""
        phases = self._phases
        if self.enabled and phases is not None:
            phases[name] = phases.get(name, 0.0) + seconds

    def sync(self, value: Any) -> None:
        """Block on device values inside a phase when ``obs_sync_timing`` is
        set, so the phase wall measures device time, not dispatch time."""
        if self.enabled and self.sync_timing and value is not None:
            import jax

            jax.block_until_ready(value)


_SESSION = TelemetrySession()


def get_session() -> TelemetrySession:
    """The process-global telemetry session."""
    return _SESSION


