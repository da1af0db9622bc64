"""Per-phase wall-clock accumulation (reference: FunctionTimer/global_timer,
include/LightGBM/utils/common.h:979-1055 — scoped timers summed per label,
summary printed at shutdown when verbosity allows).

On an async accelerator runtime, phase walls measure HOST time: dispatch cost
for jitted phases, full device time for phases that synchronize (eval pulls
scores to host).  The phases are fed by the trace spans of the layer
boundaries (obs/trace.py), which are also ``jax.profiler`` annotations.
"""

from __future__ import annotations

import threading
from collections import defaultdict
from typing import Dict


class GlobalTimer:
    def __init__(self) -> None:
        self.totals: Dict[str, float] = defaultdict(float)
        self.counts: Dict[str, int] = defaultdict(int)
        # defaultdict += is read-modify-write: concurrent phases (dask
        # workers, threaded predict) would drop increments without a lock
        self._lock = threading.Lock()

    def add(self, name: str, seconds: float) -> None:
        """One finished phase (the trace spans of the layer boundaries feed
        their wall here: obs/trace.py ``TraceRecorder.span(timer=...)``)."""
        with self._lock:
            self.totals[name] += seconds
            self.counts[name] += 1

    def reset(self) -> None:
        with self._lock:
            self.totals.clear()
            self.counts.clear()

    def summary(self) -> str:
        with self._lock:
            totals = dict(self.totals)
            counts = dict(self.counts)
        if not totals:
            return "LightGBM::timer: (no phases recorded)"
        width = max(len(k) for k in totals)
        lines = ["LightGBM::timer (host wall per phase)"]
        for name, total in sorted(totals.items(), key=lambda kv: -kv[1]):
            lines.append(
                f"  {name.ljust(width)}  {total:9.3f}s  x{counts[name]}"
            )
        return "\n".join(lines)


global_timer = GlobalTimer()
