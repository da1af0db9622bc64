"""Unified training/inference telemetry.

One process-global :class:`TelemetrySession` that every hot path reports
into:

* per-iteration event records (phase walls, commit counts, bagging counts,
  eval metrics) with an optional JSONL sink — ``registry``;
* compile accounting — ``instrumented_jit`` counts actual retraces at every
  ``jax.jit`` call site, ``compile_count()`` is the global no-recompile
  invariant — and (``obs_device_accounting=True``) executable accounting:
  ``cost_analysis()``/``memory_analysis()`` of each compiled artifact as
  ``cost/*`` / ``memory/*`` gauges — ``jit``;
* collective accounting — the data-parallel grower's psum bytes, modeled
  analytically (``parallel.psum_bytes_per_iteration``) and MEASURED by
  timed psum/pmax wrappers (``collective_measured/*``) — ``collectives``;
* live HBM watermarks via ``device.memory_stats()`` at phase boundaries
  (graceful no-op on backends without allocator stats) — ``device``;
* per-host aggregation — GlobalSyncUp-style counter/gauge merge plus
  straggler gauges for multi-host runs — ``aggregate``;
* ``jax.profiler`` trace capture over an iteration window — ``profiler``;
  the program's names in any such trace: every ``trace`` span is a profiler
  annotation in ``/host:CPU``, and ``op_scopes()`` maps a device event's
  HLO instruction to the ``jax.named_scope`` path that emitted it — ``jit``;
* the LIVE ops plane — ``flight`` (always-on bounded ring buffer with
  atomic dump-on-fault: NumericsError, degradation latch, SIGTERM),
  ``health`` (per-iteration host-side watchdog emitting severity-tagged
  alerts), ``export`` (Prometheus text-format snapshot + opt-in HTTP
  endpoint via ``obs_export_port`` and the ``Booster.health()`` API);
* distributed tracing — ``trace`` (always-on span recorder with
  ``trace_id``/``span_id``/parent links and per-category sampling,
  exported as Perfetto-loadable Chrome trace JSON via
  ``Booster.dump_trace``, ``GET /trace``, and automatically next to every
  flight dump).  See README "Distributed tracing".

Enable with ``telemetry=True`` (params/Config), stream to a file with
``telemetry_out=<path.jsonl>``, make phase walls measure device time with
``obs_sync_timing=True``, capture executable cost/memory with
``obs_device_accounting=True``.  See README "Observability".
"""

from .aggregate import (  # noqa: F401
    global_rollup,
    host_snapshot,
    merge_snapshots,
)
from .collectives import (  # noqa: F401
    collectives_snapshot,
    measured_summary,
    timed_pmax,
    timed_pmin,
    timed_psum,
)
from .device import (  # noqa: F401
    device_memory_supported,
    sample_device_memory,
)
from .export import (  # noqa: F401
    MetricsExporter,
    health_snapshot,
    prometheus_snapshot,
    sanitize_metric_name,
    set_serving_provider,
)
from .flight import (  # noqa: F401
    FlightRecorder,
    get_flight,
    install_sigterm_handler,
    list_flight_dumps,
    uninstall_sigterm_handler,
)
from .health import HealthWatchdog  # noqa: F401
from .jit import (  # noqa: F401
    compile_count,
    compile_counts_by_label,
    instrumented_jit,
    note_compile,
    note_executable,
    op_scope_maps,
    op_scopes,
    record_executable,
)
from .profiler import TraceWindow  # noqa: F401
from .registry import (  # noqa: F401
    TelemetrySession,
    get_session,
)
from .trace import (  # noqa: F401
    TraceRecorder,
    format_traceparent,
    get_tracer,
    parse_traceparent,
)

__all__ = [
    "TelemetrySession",
    "get_session",
    "FlightRecorder",
    "get_flight",
    "list_flight_dumps",
    "install_sigterm_handler",
    "uninstall_sigterm_handler",
    "HealthWatchdog",
    "MetricsExporter",
    "health_snapshot",
    "prometheus_snapshot",
    "sanitize_metric_name",
    "set_serving_provider",
    "instrumented_jit",
    "note_compile",
    "note_executable",
    "record_executable",
    "compile_count",
    "compile_counts_by_label",
    "op_scopes",
    "op_scope_maps",
    "collectives_snapshot",
    "measured_summary",
    "timed_psum",
    "timed_pmax",
    "timed_pmin",
    "sample_device_memory",
    "device_memory_supported",
    "global_rollup",
    "host_snapshot",
    "merge_snapshots",
    "TraceWindow",
    "TraceRecorder",
    "get_tracer",
    "parse_traceparent",
    "format_traceparent",
]
