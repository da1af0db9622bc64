"""``jax.profiler`` trace wiring: capture a window of boosting iterations.

``Config.profile_trace_dir`` plus ``profile_iter_start``/``profile_iter_end``
drive ``jax.profiler.start_trace``/``stop_trace`` from the training loop —
the standard way to get a TensorBoard-loadable device trace of exactly the
steady-state iterations (skipping compile/warmup noise).  While the window
is open every ``TraceRecorder`` span (obs/trace.py) and the predictor's
``TraceAnnotation`` phases are events of the ``/host:CPU`` plane.  The
grower's ``jax.named_scope`` labels (partition / histogram / split_scan /
candidate_refresh / bookkeeping / fused_grow_step ...) do NOT appear on a
TPU's device events, which are named by their HLO instruction alone; join
them through ``obs.op_scopes()`` (obs/jit.py).
"""

from __future__ import annotations

import jax

from ..utils.log import log_warning


class TraceWindow:
    """Start/stop a profiler trace over an inclusive iteration window.

    ``end_iter < 0`` means "until training ends" (the caller's ``close()``
    in a finally block stops the trace).  A failed start (e.g. profiler
    already active in the process) degrades to a warning, never an error.
    """

    def __init__(self, trace_dir: str, start_iter: int = 0, end_iter: int = -1):
        self.trace_dir = trace_dir or ""
        self.start_iter = max(0, int(start_iter))
        self.end_iter = int(end_iter)
        self._active = False
        self._done = False

    @property
    def active(self) -> bool:
        return self._active

    def on_iteration_start(self, it: int) -> None:
        if not self.trace_dir or self._active or self._done:
            return
        if it >= self.start_iter:
            try:
                jax.profiler.start_trace(self.trace_dir)
                self._active = True
            except Exception as e:  # profiler busy / unwritable dir
                self._done = True
                log_warning(f"profile_trace_dir: start_trace failed: {e!r}")

    def on_iteration_end(self, it: int) -> None:
        if self._active and 0 <= self.end_iter <= it:
            self.close()

    def close(self) -> None:
        if self._active:
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                log_warning(f"profile_trace_dir: stop_trace failed: {e!r}")
            self._active = False
            self._done = True
