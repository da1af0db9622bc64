"""Distributed tracing: an always-on span recorder exporting Chrome trace JSON.

The rest of the obs plane speaks counters, gauges and windowed percentiles;
this module answers "where did *this* request's 13.6 ms go?" and "what
happened *inside* launch window [24, 32)?" with a correlated span timeline
loadable in Perfetto / ``chrome://tracing``.

Design mirrors the flight recorder (``obs/flight.py``): one process-global
:class:`TraceRecorder` holding a bounded ring of finished spans, always on
by default, near-zero cost when idle — span creation is one attribute check
when inactive, and recording is a dict append under a lock.  Spans carry
stable ``trace_id``/``span_id``/``parent_id`` links (W3C trace-context
sized: 16-byte / 8-byte hex), monotonic-clock timestamps
(``time.perf_counter_ns`` — host clocks ONLY, never tracer values, so the
recorder is GL003-clean by construction), and a category used by the
per-category sampling knobs.

Every span opened through ``begin``/``span`` is also a
``jax.profiler.TraceAnnotation``: while any profiler session is live
(``profile_trace_dir``, a TensorBoard capture, the benchmark's traced
window) the span is an event of the ``/host:CPU`` plane beside the
``PjitFunction(...)`` dispatches, on the clock the device planes use, with
its scalar args as stats.  With no session the annotation is a flag check.
``add_span`` builds a span after the fact and stays ring-only.

Span taxonomy (see README "Distributed tracing"; ``wait/*`` is every place
the training loop blocks on the device):

* ``train``      — ``train/run`` > ``train/iteration`` > {``train/gradients``,
                   ``train/sample``, ``train/grow``, ``wait/fetch_tree``,
                   ``train/host_tree``, ``train/score_update``};
                   ``train/launch`` > {``train/launch_dispatch``,
                   ``wait/launch_fetch``, ``train/launch_replay``} (the
                   exact per-iteration device counters ride in its
                   ``per_iteration`` arg); ``train/eval`` >
                   ``train/eval_score`` > ``wait/eval_metric``;
                   ``train/callbacks``; ``train/checkpoint``
* ``setup``      — ``setup/import`` (the package's import);
                   ``dataset/construct`` > {``dataset/bin_fit``,
                   ``dataset/bundle``, ``dataset/pack``};
                   ``setup/booster_init`` > {``setup/objective_init``,
                   ``setup/transfer`` (a host-to-device copy of a
                   row-proportional array: ``what``, ``bytes``),
                   ``setup/kernel_import``, ``setup/add_valid``};
                   ``setup/launch_build``
* ``compile``    — ``compile/<label>``: a call through ``instrumented_jit``
                   that traced or compiled (``trace_s``, ``lower_s``,
                   ``backend_compile_s``, ``cache_retrieval_s``,
                   ``cache_hit``, ``call_s``; the launch scan's
                   ``baked_bytes``), under whatever span was open;
                   ``compile/op_scopes``, ``compile/uninstrumented``
                   (``obs/jit.py``).  With these every second from the
                   package's import to the first timed iteration has a name
* ``collective`` — ``timed_psum``/``timed_pmax`` sites with payload bytes
* ``serve``      — ``serve/batch`` > {``serve/request`` >
                   ``serve/queue_wait``, ``serve/batch_assembly``,
                   ``serve/device_dispatch``, ``serve/unpad_respond``}
* ``lifecycle``  — checkpoint writes, hot-swap warm/flip/drain, refresh
                   refits, degradation latches, fault dumps

Export is the Chrome trace-event JSON array format (``ph``/``ts``/``dur``/
``pid``/``tid``), written atomically (tmp+fsync+rename) on demand
(``Booster.dump_trace``, ``GET /trace``) and automatically next to every
flight dump (``trace_<ts>_<pid>_<n>.json`` pairs ``flight_...``).
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import threading
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple, Union

import jax
from jax.profiler import TraceAnnotation

from ..utils.timer import global_timer
from .flight import _atomic_write_text
from .registry import get_session

TRACE_SCHEMA = "lgbtpu.trace.v1"

MIN_CAPACITY = 64
DEFAULT_CAPACITY = 4096

_TRACEPARENT_RE = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)


def parse_traceparent(header: Optional[str]) -> Optional[Tuple[str, str]]:
    """Parse a W3C ``traceparent`` header into ``(trace_id, parent_span_id)``.

    Returns None for missing/malformed headers and for the all-zero ids the
    spec reserves as invalid."""
    if not header:
        return None
    m = _TRACEPARENT_RE.match(header.strip().lower())
    if m is None:
        return None
    trace_id, parent_id = m.group(2), m.group(3)
    if trace_id == "0" * 32 or parent_id == "0" * 16:
        return None
    return trace_id, parent_id


def format_traceparent(trace_id: str, span_id: str) -> str:
    """Render a W3C ``traceparent`` header (version 00, sampled flag)."""
    return f"00-{trace_id}-{span_id}-01"


class SpanHandle:
    """An open span: identity + start time; recorded when ended."""

    __slots__ = (
        "name", "cat", "trace_id", "span_id", "parent_id",
        "t0_us", "args", "tid", "_attached", "_ambient", "_annotation",
    )

    def __init__(
        self, name: str, cat: str, trace_id: str, span_id: str,
        parent_id: Optional[str], t0_us: int, args: Dict[str, Any], tid: int,
    ) -> None:
        self.name = name
        self.cat = cat
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0_us = t0_us
        self.args = args
        self.tid = tid
        self._attached = False
        self._ambient = False
        # the same span in the profiler's trace; records there only while a
        # profiler session is live, with the scalar args as the event's stats
        self._annotation = TraceAnnotation(
            name,
            **{
                k: v for k, v in args.items()
                if isinstance(v, (bool, int, float, str))
            },
        )
        self._annotation.__enter__()


class TraceRecorder:
    """Bounded ring of finished spans with Chrome trace-event export."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY) -> None:
        self._lock = threading.Lock()
        self._spans: Deque[Dict[str, Any]] = deque(
            maxlen=max(MIN_CAPACITY, int(capacity))
        )
        self.active = True
        self.default_rate = 1.0
        self.rates: Dict[str, float] = {}
        self.spans_total = 0
        self.dropped_total = 0
        self.last_dump_path = ""
        self.dump_count = 0
        self._cat_seen: Dict[str, int] = {}
        self._tls = threading.local()
        self._ambient: Optional[SpanHandle] = None
        # thread ident -> (small tid, thread name) for readable Perfetto rows
        self._tids: Dict[int, Tuple[int, str]] = {}

    # ---------------------------------------------------------- lifecycle
    def configure(
        self,
        capacity: Optional[int] = None,
        active: Optional[bool] = None,
        default_rate: Optional[float] = None,
        rates: Optional[Dict[str, float]] = None,
    ) -> "TraceRecorder":
        """(Re)configure; shrinking the ring counts truncated spans as
        dropped so the eviction accounting stays honest."""
        with self._lock:
            if capacity is not None and capacity != self._spans.maxlen:
                cap = max(MIN_CAPACITY, int(capacity))
                lost = max(0, len(self._spans) - cap)
                self.dropped_total += lost
                self._spans = deque(self._spans, maxlen=cap)
            if active is not None:
                self.active = bool(active)
            if default_rate is not None:
                self.default_rate = min(1.0, max(0.0, float(default_rate)))
            if rates is not None:
                self.rates = {
                    str(k): min(1.0, max(0.0, float(v)))
                    for k, v in rates.items()
                }
        return self

    def reset(self) -> None:
        """Clear spans and counters; keeps capacity/active/sampling."""
        with self._lock:
            self._spans.clear()
            self.spans_total = 0
            self.dropped_total = 0
            self._cat_seen.clear()
            self._ambient = None

    @property
    def capacity(self) -> int:
        return self._spans.maxlen or 0

    # ------------------------------------------------------------- helpers
    @staticmethod
    def now_us() -> int:
        """Monotonic microseconds (same epoch as ``time.perf_counter``)."""
        return time.perf_counter_ns() // 1000

    @staticmethod
    def new_trace_id() -> str:
        return os.urandom(16).hex()

    @staticmethod
    def new_span_id() -> str:
        return os.urandom(8).hex()

    def _tid(self) -> int:
        ident = threading.get_ident()
        got = self._tids.get(ident)
        if got is None:
            with self._lock:
                got = self._tids.get(ident)
                if got is None:
                    got = (len(self._tids) + 1, threading.current_thread().name)
                    self._tids[ident] = got
        return got[0]

    def _sampled(self, cat: str) -> bool:
        """Deterministic per-category sampling: of every K spans in a
        category, accept ~rate*K (counter-based, reproducible in tests)."""
        rate = self.rates.get(cat, self.default_rate)
        if rate >= 1.0:
            return True
        if rate <= 0.0:
            return False
        with self._lock:
            n = self._cat_seen.get(cat, 0) + 1
            self._cat_seen[cat] = n
        return int(n * rate) > int((n - 1) * rate)

    def _append(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped_total += 1
            self._spans.append(rec)
            self.spans_total += 1

    # ---------------------------------------------------------- span API
    def current(self) -> Optional[SpanHandle]:
        """The innermost open span on this thread, else the ambient span
        (the open training iteration/launch — used by host callbacks that
        fire on runtime threads, e.g. measured collectives)."""
        stack = getattr(self._tls, "stack", None)
        if stack:
            return stack[-1]
        return self._ambient

    def begin(
        self,
        name: str,
        cat: str = "train",
        *,
        trace_id: Optional[str] = None,
        parent: Optional[Union[SpanHandle, str]] = None,
        args: Optional[Dict[str, Any]] = None,
        attach: bool = False,
        ambient: bool = False,
    ) -> Optional[SpanHandle]:
        """Open a span; returns None when inactive or sampled out (every
        consumer treats a None handle as a no-op).  ``attach`` pushes the
        span on this thread's parent stack so nested begins/phases become
        children; ``ambient`` additionally publishes it as the process-wide
        fallback parent for cross-thread children."""
        if not self.active or not self._sampled(cat):
            return None
        cur = self.current()
        parent_id: Optional[str] = None
        if isinstance(parent, SpanHandle):
            parent_id = parent.span_id
            trace_id = trace_id or parent.trace_id
        elif isinstance(parent, str) and parent:
            parent_id = parent
        elif cur is not None:
            parent_id = cur.span_id
            trace_id = trace_id or cur.trace_id
        h = SpanHandle(
            name, cat, trace_id or self.new_trace_id(), self.new_span_id(),
            parent_id, self.now_us(), dict(args or {}), self._tid(),
        )
        if attach:
            stack = getattr(self._tls, "stack", None)
            if stack is None:
                stack = self._tls.stack = []
            stack.append(h)
            h._attached = True
        if ambient:
            self._ambient = h
            h._ambient = True
        return h

    @contextlib.contextmanager
    def under(self, handle: Optional[SpanHandle]):
        """Inside the block, an open span that was begun unattached is the
        parent of what this thread begins (and of the ``compile/*`` spans
        built after the fact); a raise leaves nothing on the stack.  A None
        handle is a no-op."""
        if handle is None:
            yield
            return
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        stack.append(handle)
        try:
            yield
        finally:
            if handle in stack:
                stack.remove(handle)

    def end(
        self,
        handle: Optional[SpanHandle],
        extra: Optional[Dict[str, Any]] = None,
        end_us: Optional[int] = None,
    ) -> None:
        """Close a span and record it; a None handle is a no-op."""
        if handle is None:
            return
        if handle._attached:
            stack = getattr(self._tls, "stack", None)
            if stack and handle in stack:
                stack.remove(handle)
            handle._attached = False
        if handle._ambient:
            if self._ambient is handle:
                self._ambient = None
            handle._ambient = False
        if extra:
            handle.args.update(extra)
        handle._annotation.__exit__(None, None, None)
        t1 = self.now_us() if end_us is None else int(end_us)
        self._append(
            {
                "name": handle.name,
                "cat": handle.cat,
                "trace_id": handle.trace_id,
                "span_id": handle.span_id,
                "parent_id": handle.parent_id,
                "ts": handle.t0_us,
                "dur": max(0, t1 - handle.t0_us),
                "tid": handle.tid,
                "args": handle.args,
            }
        )

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        cat: str = "train",
        *,
        phase: Optional[str] = None,
        timer: Union[bool, str, None] = None,
        **kwargs,
    ):
        """Context-managed span, attached as the current parent.

        One call site per layer boundary: the span's wall also feeds
        ``phase`` of the telemetry session's open iteration accumulator
        (``Booster.telemetry()`` / JSONL ``phases``) and the ``timer`` label
        of ``global_timer`` (``True``: the span's own name).  Both are fed
        whether or not the span itself was recorded (tracing off, sampled
        out)."""
        h = self.begin(name, cat, attach=True, **kwargs)
        t0 = time.perf_counter()
        try:
            yield h
        finally:
            self.end(h)
            dt = time.perf_counter() - t0
            if timer:
                global_timer.add(name if timer is True else timer, dt)
            if phase is not None:
                get_session().add_phase(phase, dt)

    def instant(
        self,
        name: str,
        cat: str = "lifecycle",
        args: Optional[Dict[str, Any]] = None,
        parent: Optional[Union[SpanHandle, str]] = None,
    ) -> None:
        """Record a zero-duration (Chrome ``ph: "i"``) event."""
        if not self.active or not self._sampled(cat):
            return
        trace_id = None
        parent_id = None
        if isinstance(parent, SpanHandle):
            parent_id, trace_id = parent.span_id, parent.trace_id
        elif isinstance(parent, str) and parent:
            parent_id = parent
        else:
            cur = self.current()
            if cur is not None:
                parent_id, trace_id = cur.span_id, cur.trace_id
        self._append(
            {
                "name": name,
                "cat": cat,
                "trace_id": trace_id or self.new_trace_id(),
                "span_id": self.new_span_id(),
                "parent_id": parent_id,
                "ts": self.now_us(),
                "dur": None,
                "tid": self._tid(),
                "args": dict(args or {}),
            }
        )

    def add_span(
        self,
        name: str,
        cat: str,
        t0_us: int,
        dur_us: int,
        *,
        trace_id: Optional[str] = None,
        span_id: Optional[str] = None,
        parent_id: Optional[str] = None,
        args: Optional[Dict[str, Any]] = None,
        tid: Optional[int] = None,
    ) -> Optional[str]:
        """Record a finished span with explicit timestamps (the batcher's
        stage decomposition and the measured collectives build spans after
        the fact, from clock readings they took themselves).  Ring-only: a
        finished span cannot be a profiler annotation.  Bypasses sampling —
        the enclosing span already made the sampling decision."""
        if not self.active:
            return None
        sid = span_id or self.new_span_id()
        rec = {
            "name": name,
            "cat": cat,
            "trace_id": trace_id or self.new_trace_id(),
            "span_id": sid,
            "parent_id": parent_id,
            "ts": int(t0_us),
            "dur": max(0, int(dur_us)),
            "tid": self._tid() if tid is None else int(tid),
            "args": dict(args or {}),
        }
        self._append(rec)
        return sid

    # ------------------------------------------------------------- queries
    def spans(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._spans)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "active": self.active,
                "capacity": self._spans.maxlen,
                "ring": len(self._spans),
                "spans_total": self.spans_total,
                "dropped_total": self.dropped_total,
                "last_dump": self.last_dump_path,
            }

    # -------------------------------------------------------------- export
    def chrome_trace(self) -> Dict[str, Any]:
        """The ring as a Chrome trace-event JSON object (Perfetto-loadable).

        Spans become ``ph: "X"`` complete events sorted by timestamp
        (monotonic ``ts``), instants become ``ph: "i"``; span identity and
        parent links ride in ``args`` so the tree survives the format."""
        with self._lock:
            spans = list(self._spans)
            tids = sorted(
                (small, name) for small, name in self._tids.values()
            )
        pid = os.getpid()
        events: List[Dict[str, Any]] = [
            {
                "name": "process_name", "ph": "M", "ts": 0,
                "pid": pid, "tid": 0, "args": {"name": "lightgbm_tpu"},
            }
        ]
        for small, name in tids:
            events.append(
                {
                    "name": "thread_name", "ph": "M", "ts": 0,
                    "pid": pid, "tid": small, "args": {"name": name},
                }
            )
        for rec in sorted(spans, key=lambda r: r["ts"]):
            args = dict(rec["args"])
            args["trace_id"] = rec["trace_id"]
            args["span_id"] = rec["span_id"]
            if rec.get("parent_id"):
                args["parent_id"] = rec["parent_id"]
            ev: Dict[str, Any] = {
                "name": rec["name"],
                "cat": rec["cat"],
                "ph": "i" if rec["dur"] is None else "X",
                "ts": rec["ts"],
                "pid": pid,
                "tid": rec["tid"],
                "args": args,
            }
            if rec["dur"] is None:
                ev["s"] = "t"
            else:
                ev["dur"] = rec["dur"]
            events.append(ev)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "metadata": {
                "schema": TRACE_SCHEMA,
                "spans_total": self.spans_total,
                "dropped_total": self.dropped_total,
            },
        }

    def chrome_trace_json(self) -> str:
        return json.dumps(self.chrome_trace())

    def dump(self, path: str) -> str:
        """Atomically write the Chrome trace JSON to ``path``; returns it."""
        _atomic_write_text(path, self.chrome_trace_json())
        with self._lock:
            self.last_dump_path = path
            self.dump_count += 1
        return path

    def dump_fault(self, directory: str, suffix: str) -> str:
        """Best-effort dump next to a flight dump (``trace_<suffix>.json``,
        where ``suffix`` matches the flight file's ``<ts>_<pid>_<n>``).
        Never raises — this runs on fault paths."""
        if not self.active or not directory:
            return ""
        try:
            return self.dump(os.path.join(directory, f"trace_{suffix}.json"))
        except Exception:
            return ""


_TRACER = TraceRecorder()


def get_tracer() -> TraceRecorder:
    """The process-global trace recorder."""
    return _TRACER


# ------------------------------------------------------------ set-up hooks
def traced_transfer(what: str, copy: Callable[[], Any]) -> Any:
    """``copy()`` (a host-to-device copy of a row-proportional array: the
    binned matrix, labels, weights, scores) under a ``setup/transfer`` span
    with ``what`` and the ``bytes`` that arrived.  The span waits for the
    copy, so it is the copy and not its dispatch; with tracing off it is a
    flag check and nothing waits."""
    tr = _TRACER
    if not tr.active:
        return copy()
    with tr.span("setup/transfer", "setup", args={"what": what}) as h:
        out = jax.block_until_ready(copy())
        if h is not None:
            h.args["bytes"] = sum(
                int(getattr(leaf, "nbytes", 0))
                for leaf in jax.tree_util.tree_leaves(out)
            )
    return out


# --------------------------------------------------------------- hot hooks
def note_collective(site: str, t0_ns: int, t1_ns: int, nbytes: int) -> None:
    """Record one measured-collective site call as a span with payload-byte
    args, parented under the ambient training span when one is open.  Host
    clocks only (the io_callback's perf_counter_ns brackets) — never tracer
    values."""
    tr = _TRACER
    if not tr.active:
        return
    parent = tr.current()
    if not tr._sampled("collective"):
        return
    tr.add_span(
        f"collective/{site}", "collective", t0_ns // 1000,
        max(0, t1_ns - t0_ns) // 1000,
        trace_id=parent.trace_id if parent else None,
        parent_id=parent.span_id if parent else None,
        args={"payload_bytes": int(nbytes)},
    )
