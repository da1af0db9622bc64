"""Compile accounting: count actual XLA retraces across the whole library,
and (opt-in) capture each compiled executable's cost/memory analysis.

``jax.jit`` only re-invokes the wrapped Python callable on a trace-cache
miss, so wrapping the function with a counter increment counts retraces
EXACTLY — including AOT ``fn.lower(...).compile()`` paths, which trace once
per lower.  Every ``jax.jit`` call site in the library routes through
:func:`instrumented_jit`; the streaming predictor's executable cache
additionally reports each compiled bucket via :func:`note_compile`, so
``compile_count()`` is the one process-global number a no-recompile test can
assert on (generalizing ``predict.streaming_compile_count()``).

Executable accounting (``obs_device_accounting=True``): when a call
retraces, the wrapper re-lowers with the same concrete arguments and records
``Compiled.cost_analysis()`` (FLOPs, bytes accessed) and
``Compiled.memory_analysis()`` (temp/argument/output/generated-code bytes)
as per-label ``cost/*`` / ``memory/*`` gauges.  The re-lower traces the
function a second time, which is why this is opt-in; the duplicate trace is
suppressed from the retrace counters so the no-recompile invariants stay
exact.  A cache HIT on a label whose analyses are not yet known (it was
traced before accounting was enabled — an earlier train in the same
process) triggers the same one-time capture; after that, hits just replay
the memoized gauge values into the current session, so a session started
after the traces were made still sees the full cost/memory families.
Backends whose executables expose neither analysis degrade to a silent
no-op (absent gauge keys, never an error).

Operation scopes (:func:`op_scopes`): a profiler trace names a device event
by its HLO instruction and carries no ``jax.named_scope``; the compiled
artifact's text carries both (``metadata={op_name="jit(f)/while/body/
split_scan/reduce"}``).  So on a call that traced, when the persistent
compilation cache keeps the program, the wrapper lowers and compiles it once
more (a hit on the entry just written), parses instruction -> scope and
writes the map beside the cache (``<cache dir>/op_scopes/``).  A later call,
a later process, or an operator joining a ``profile_trace_dir`` trace reads
the files; a warm run pays one existence check per traced label.

Compilation timeline: one set of ``jax.monitoring`` listeners, registered at
import, hears the durations jax emits while it traces, lowers, asks the
persistent cache and compiles, on the thread that does it.  A call through
:func:`instrumented_jit` that traced or compiled closes what was heard as one
span ``compile/<label>`` (category ``compile``) in the trace ring, under the
span open on that thread, with ``trace_s``, ``lower_s``,
``backend_compile_s`` (the whole ``compile_or_get_cached``: on a cache hit the
retrieval), ``cache_retrieval_s``, ``cache_hit`` and ``call_s``; the second
``lower().compile()`` of the accounting and of ``op_scopes`` goes to
``compile/op_scopes``; a compilation outside any instrumented call (an eager
op, a ``jnp.asarray``) to ``compile/uninstrumented``.  A warm call pays one
thread-local store and one comparison.
"""

from __future__ import annotations

import contextlib
import functools
import glob
import hashlib
import json
import os
import re
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

import jax

from .flight import _atomic_write_text, get_flight
from .registry import get_session
from .trace import get_tracer

_lock = threading.Lock()
_count = 0
_by_label: Dict[str, int] = {}
# bumped on every counted trace: __call__ compares before/after to detect
# "this call traced" without touching jax internals (a plain read: a trace
# on another thread at worst costs one more existence check)
_epoch = 0
# .suppress: set during the accounting re-lower; .call: the instrumented call
# open on this thread; .heard: compilation events not yet closed into a span
_tls = threading.local()


def note_compile(label: str = "jit") -> None:
    """Record one trace/compile under ``label``."""
    global _count, _epoch
    if getattr(_tls, "suppress", False):
        return  # accounting re-lower: not a new logical trace
    with _lock:
        _count += 1
        _epoch += 1
        _by_label[label] = _by_label.get(label, 0) + 1


def compile_count() -> int:
    """Total traces/compiles this process (instrumented jits + the
    streaming predictor's AOT bucket executables)."""
    # the read takes _lock like note_compile's read-modify-write: int loads
    # are CPython-atomic, but pairing the read with the lock keeps the
    # counter exact under free-threaded builds and guarantees a reader
    # never observes _count and _by_label mid-update relative to each other
    with _lock:
        return _count


def compile_counts_by_label() -> Dict[str, int]:
    """Per-call-site breakdown of :func:`compile_count`."""
    with _lock:
        return dict(_by_label)


# --------------------------------------------------- executable accounting
_COST_KEYS = (("flops", "flops"), ("bytes accessed", "bytes_accessed"))
_MEMORY_KEYS = (
    ("temp_size_in_bytes", "temp_bytes"),
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
)
# analyses survive session resets: a label traced before this session (an
# earlier train in the same process, a predictor ladder already warm) can
# replay its recorded gauges into the fresh session without re-lowering
_seen_executables: Dict[Any, Dict[str, float]] = {}  # (label, id(compiled))
_label_analyses: Dict[str, Dict[str, float]] = {}  # label -> gauge values


def _extract_analyses(label: str, compiled: Any) -> Dict[str, float]:
    """Pull cost/memory analysis out of a ``Compiled`` as a gauge-name ->
    value map.  Any backend that raises or returns nothing for an analysis
    contributes no keys — graceful no-op."""
    out: Dict[str, float] = {}
    try:
        ca = compiled.cost_analysis()
    except Exception:
        ca = None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if isinstance(ca, dict):
        for src, dst in _COST_KEYS:
            v = ca.get(src)
            if isinstance(v, (int, float)) and v >= 0:
                out[f"cost/{label}/{dst}"] = float(v)
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        for src, dst in _MEMORY_KEYS:
            v = getattr(ma, src, None)
            if isinstance(v, (int, float)) and v >= 0:
                out[f"memory/{label}/{dst}"] = float(v)
    return out


def record_executable(label: str, compiled: Any) -> None:
    """Record a ``Compiled``'s cost/memory analysis as per-label gauges.

    Gauges are max-merged: a label compiled at several shapes (ladder
    buckets, retraces) reports its worst case.
    """
    ses = get_session()
    vals = _extract_analyses(label, compiled)
    prior = _label_analyses.setdefault(label, {})
    for name, v in vals.items():
        prior[name] = max(prior.get(name, 0.0), v)
        ses.set_gauge_max(name, v)


def note_executable(label: str, compiled: Any) -> None:
    """Record an already-AOT-compiled executable (streaming predictor's
    bucket ladder).  Analysis runs once per object; repeat cache hits only
    replay the recorded gauges (so a fresh session still sees them)."""
    ses = get_session()
    if not (ses.enabled and ses.device_accounting):
        return
    key = (label, id(compiled))
    vals = _seen_executables.get(key)
    if vals is None:
        vals = _extract_analyses(label, compiled)
        _seen_executables[key] = vals
        prior = _label_analyses.setdefault(label, {})
        for name, v in vals.items():
            prior[name] = max(prior.get(name, 0.0), v)
    for name, v in vals.items():
        ses.set_gauge_max(name, v)


# --------------------------------------------------- compilation timeline
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
UNINSTRUMENTED = "uninstrumented"
_HEARD_MAX = 4096  # events a thread keeps before they go to a span unasked
OP_SCOPES_SPAN = "op_scopes"


def _heard() -> List[Tuple[str, float, float, str]]:
    """This thread's open record: (kind, start, end, jax's name for the
    function) on ``time.perf_counter``'s clock, the span ring's."""
    heard = getattr(_tls, "heard", None)
    if heard is None:
        heard = _tls.heard = []
    return heard


def _on_duration(event: str, duration: float, **kw: Any) -> None:
    kind = _DURATIONS.get(event)
    if kind is None:
        return
    end = time.perf_counter()
    heard = _heard()
    heard.append((kind, end - float(duration), end, str(kw.get("fun_name", ""))))
    if getattr(_tls, "call", None) is not None or getattr(_tls, "suppress", False):
        return  # the call that is open on this thread closes what it hears
    if kind == "backend_compile_s":
        # nobody's call: the span starts where this program's own trace (or
        # lowering) did, by jax's name for it; what was heard before that is
        # a trace no compilation followed (eval_shape, lower())
        module = str(kw.get("fun_name", ""))
        _close_compile_span(UNINSTRUMENTED, min(
            a for k, a, _b, f in heard
            if k == "backend_compile_s" or f == module or f"jit({f})" == module
        ))
    elif len(heard) >= _HEARD_MAX:
        # a thread that traces and never compiles (eval_shape in a loop)
        _close_compile_span(UNINSTRUMENTED, float("inf"))


def _on_event(event: str, **_kw: Any) -> None:
    kind = _CACHE_EVENTS.get(event)
    if kind is not None:
        now = time.perf_counter()
        _heard().append((kind, now, now, ""))


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def _union_seconds(intervals: List[Tuple[float, float]]) -> float:
    """A traced function that calls a jitted one is heard twice, the inner
    trace inside the outer: the union counts the seconds once."""
    total, reach = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return total


def _close_compile_span(name: str, t0: float, args=None) -> None:
    """Close what this thread heard since ``t0`` as the span
    ``compile/<name>`` ending now; what it heard before ``t0`` (a trace that
    no compilation followed: ``eval_shape``, ``lower()``) goes to
    ``compile/uninstrumented`` first.  ``args``: more span args, or a
    function that gives them (called only when there is a span to put them
    on)."""
    now = time.perf_counter()
    heard = _heard()
    stale = [e for e in heard if e[2] < t0]
    events = [e for e in heard if e[2] >= t0]
    del heard[:]
    if stale:  # from another time: not a child of what is open now
        _emit_compile_span(UNINSTRUMENTED, stale[0][1], stale[-1][2], stale, None,
                           parented=False)
    if events:
        _emit_compile_span(name, t0, now, events, args)


def _emit_compile_span(name, t0, t1, events, args, parented=True) -> None:
    """Never raises: the timeline must not break training."""
    try:
        if callable(args):
            try:
                args = args()
            except Exception:
                args = None  # the hook's fault leaves the clocks their span
        seconds = {
            kind: _union_seconds([(a, b) for k, a, b, _f in events if k == kind])
            for kind in _DURATIONS.values()
        }
        hits = sum(k == "hit" for k, *_ in events)
        misses = sum(k == "miss" for k, *_ in events)
        traces = sum(k == "trace_s" for k, *_ in events)
        compiled = [f for k, _a, _b, f in events if k == "backend_compile_s"]
        # neither event fires without a cache directory, nor for a program
        # the cache does not keep (it compiled in under
        # jax_persistent_cache_min_compile_time_secs)
        cache_hit = False if misses else (True if hits else None)
        ses = get_session()
        for counter, n in (("compile/cache_hits", hits),
                           ("compile/cache_misses", misses),
                           ("compile/traces", traces)):
            if n:
                ses.inc(counter, n)
        if compiled:
            # compiles_in_window counts these; the flight ring names them
            get_flight().note_event({
                "event": "compile", "label": name, "hit": cache_hit,
                "seconds": seconds["backend_compile_s"],
            })
        tracer = get_tracer()
        if not tracer.active:
            return
        span_args = {"label": name, "call_s": t1 - t0, "cache_hit": cache_hit,
                     **seconds, **(args or {})}
        if "module" not in span_args:
            span_args["module"] = ",".join(sorted(set(compiled)))
        parent = tracer.current() if parented else None
        tracer.add_span(
            f"compile/{name}", "compile", int(t0 * 1e6), int((t1 - t0) * 1e6),
            trace_id=parent.trace_id if parent else None,
            parent_id=parent.span_id if parent else None,
            args=span_args,
        )
    except Exception:
        pass


@contextlib.contextmanager
def _second_compile(label: str):
    """The ``lower().compile()`` that accounting and ``op_scopes`` make
    after a call: not a new logical trace, and a span of its own
    (``compile/op_scopes``), never part of the label's numbers."""
    t0 = time.perf_counter()
    _tls.suppress = True
    try:
        yield
    finally:
        _tls.suppress = False
        _close_compile_span(OP_SCOPES_SPAN, t0, {"of": label})


def closed_over_bytes(inst: "_InstrumentedJit", args, kwargs) -> int:
    """Bytes of the arrays the program of this call holds as constants: what
    ``inst``'s function closes over instead of taking as an operand (a scan
    body's labels), and what it makes from NumPy while it traces.  They are
    part of the executable, so other values of them are another compilation
    whatever the persistent cache holds.  Read from the constants of the
    call's own jaxpr, the arrays themselves; called after the call, it finds
    that jaxpr in jax's trace cache."""
    abstract = jax.tree_util.tree_map(_abstract, (args, kwargs))
    _tls.suppress = True  # a miss of that cache would not be a new logical trace
    try:
        consts = inst._jit.trace(*abstract[0], **abstract[1]).jaxpr.consts
    finally:
        _tls.suppress = False
    return sum(int(getattr(c, "nbytes", 0)) for c in consts)


def _has_tracer(leaves) -> bool:
    return any(isinstance(l, jax.core.Tracer) for l in leaves)


class _InstrumentedJit:
    """``jax.jit`` wrapper that counts retraces and (opt-in) captures the
    compiled executable's cost/memory analysis on each trace."""

    def __init__(self, fun, label: str, jit_kwargs: Dict[str, Any]) -> None:
        self._label = label
        # what the program closes over that its arguments do not show (see
        # ``_signature``); not a jit option
        self._closure_key = str(jit_kwargs.pop("closure_key", ""))
        # (args, kwargs) of a call that compiled -> more args for its
        # ``compile/<label>`` span; not a jit option
        self._compile_args = jit_kwargs.pop("compile_args", None)
        # introspectable by the lint IR pass (GL013 donation audit) and any
        # other tooling that needs the entry's declared jit contract
        self.jit_kwargs: Dict[str, Any] = dict(jit_kwargs)

        @functools.wraps(fun)
        def _traced(*args: Any, **kwargs: Any):
            note_compile(label)
            return fun(*args, **kwargs)

        self._jit = jax.jit(_traced, **jit_kwargs)
        # __wrapped__/__name__ flow through so jax's signature inspection
        # (static_argnames resolution by callers) sees the original function
        functools.update_wrapper(self, fun)

    def __call__(self, *args: Any, **kwargs: Any):
        before = _epoch
        outer = getattr(_tls, "call", None)  # set while an outer call traces
        _tls.call = self
        t0 = time.perf_counter()
        try:
            out = self._jit(*args, **kwargs)
        finally:
            _tls.call = outer
        traced = _epoch != before  # bumped by every counted trace
        if traced or (outer is None and getattr(_tls, "heard", None)):
            seconds = time.perf_counter() - t0
            self._close_span(t0, args, kwargs)
            if traced:
                _note_traced(self, args, kwargs, seconds)
        ses = get_session()
        if not (ses.enabled and ses.device_accounting):
            return out
        if traced:
            self._capture(args, kwargs)
        else:
            cached = _label_analyses.get(self._label)
            if cached is None:
                # cache hit on a trace made before accounting was enabled
                # (e.g. an earlier train in this process): lower once to
                # recover the artifact, then the label is cached for good
                self._capture(args, kwargs)
            else:
                for name, v in cached.items():
                    ses.set_gauge_max(name, v)
        return out

    def _close_span(self, t0: float, args, kwargs) -> None:
        """``compile/<label>`` of a call that traced or compiled.  A call
        inside an outer call's trace leaves what it heard to that call: its
        trace is part of the outer program's."""
        if getattr(_tls, "call", None) is not None:
            return

        def span_args() -> Dict[str, Any]:
            extra = {"module": re.sub(r"[^\w.\-]", "_", "jit_" + self.__name__)}
            if self._compile_args is not None:
                extra.update(self._compile_args(args, kwargs))
            return extra

        _close_compile_span(self._label, t0, span_args)

    def _capture(self, args, kwargs) -> None:
        """Re-lower with the call's concrete args and record the compiled
        artifact's analyses.  Never raises: accounting must not break
        training.  Skipped under an outer trace (tracer args — e.g. a
        nested jit inside shard_map), where lowering is not meaningful."""
        try:
            leaves = jax.tree_util.tree_leaves((args, kwargs))
            if _has_tracer(leaves):
                return
            # memoize the attempt (even an empty result) so a backend whose
            # executables expose no analyses is not re-lowered on every call
            _label_analyses.setdefault(self._label, {})
            with _second_compile(self._label):
                lowered = self._jit.lower(*args, **kwargs)
                compiled = lowered.compile()
            record_executable(self._label, compiled)
            self._record_donated(lowered)
        except Exception:
            pass

    def _record_donated(self, lowered: Any) -> None:
        """Gauge ``memory/<label>/donated_bytes``: HBM the entry hands back
        to the allocator per call (``args_info`` donated flags x aval
        bytes).  Lowering-level, so it is exact even on backends where the
        runtime ignores donation (CPU)."""
        try:
            total = 0
            for info in jax.tree_util.tree_leaves(lowered.args_info):
                if not getattr(info, "donated", False):
                    continue
                shape = getattr(info, "shape", None)
                dtype = getattr(info, "dtype", None)
                if shape is None or not hasattr(dtype, "itemsize"):
                    continue
                n = 1
                for d in shape:
                    n *= int(d)
                total += n * int(dtype.itemsize)
            if not total:  # only donating entries contribute a gauge
                return
            name = f"memory/{self._label}/donated_bytes"
            prior = _label_analyses.setdefault(self._label, {})
            prior[name] = max(prior.get(name, 0.0), float(total))
            get_session().set_gauge_max(name, float(total))
        except Exception:
            pass

    def lower(self, *args: Any, **kwargs: Any):
        return self._jit.lower(*args, **kwargs)

    def __getattr__(self, name: str):
        # delegate everything else (clear_cache, eval_shape, ...) to the jit
        return getattr(self._jit, name)


# ------------------------------------------------------------ op scopes
SCOPES_SCHEMA = "lgbtpu.op_scopes.v1"
AMBIGUOUS = "ambiguous"
# op_name segments that are structure, not scopes: control flow, call
# wrappers, and the qualified name of a loop body's function
_STRUCTURAL = re.compile(
    r"^(?:while|body|cond|branch_\d+_fun|closed_call|checkpoint|remat\d*|"
    r"custom_[jv][vj]p_call\w*|core_call|pjit|shard_map|named_call|"
    r".*<locals>.*)$"
)
# "jit(f)" names an inner jitted function; "vmap(split_scan)" is a scope
# seen through a transform
_WRAPPED = re.compile(r"^([\w.\-]+)\((.*)\)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
# instructions the device never runs as events of their own
_NO_EVENT = re.compile(r"[\]})] (?:parameter|constant|get-tuple-element|tuple)\(")

# (predicted module, signature) -> (weak instance, abstract args, kwargs):
# what a later op_scopes() needs to rebuild a map that is not on disk
_traced: Dict[Tuple[str, str], Tuple[Any, Any, Any]] = {}
_built: Dict[Tuple[str, str], Dict[str, Any]] = {}  # maps built on request
_code_hash: Optional[str] = None


def _scopes_dir() -> Optional[str]:
    cache = jax.config.jax_compilation_cache_dir
    return os.path.join(cache, "op_scopes") if cache else None


def _code_fingerprint() -> str:
    """Hash of the package's sources: a map is of the code that wrote it, so
    a cache directory shared by two checkouts never joins one's trace with
    the other's instruction names."""
    global _code_hash
    if _code_hash is None:
        h = hashlib.sha1(jax.__version__.encode())
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for d, _dirs, files in sorted(os.walk(root)):
            for f in sorted(files):
                if f.endswith(".py"):
                    with open(os.path.join(d, f), "rb") as fh:
                        h.update(fh.read())
        _code_hash = h.hexdigest()[:16]
    return _code_hash


def _abstract(x: Any) -> Any:
    """An argument as ``lower()`` takes it without holding its buffer."""
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None,
            weak_type=x.weak_type,
        )
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


def _signature(abstract: Any, closure_key: str = "") -> str:
    """Hash of what selects an executable among those of one function:
    argument shapes, dtypes, shardings and static values, the backend, the
    code, and ``closure_key``: what a bound method's program closes over
    (``instrumented_jit(..., closure_key=...)``).  Without it the launch
    scans of a quantized and a plain booster on tables of one shape shared a
    key, and whichever compiled first wrote the map both then read (PR 34:
    ``criteo67-quant.fit`` traced after ``criteo67.fit`` found no scope
    ``quantize``)."""
    leaves, treedef = jax.tree_util.tree_flatten(abstract)
    dev = jax.devices()[0]
    h = hashlib.sha1(
        f"{_code_fingerprint()}|{dev.platform}|{dev.device_kind}|{treedef}"
        f"|{closure_key}".encode()
    )
    for leaf in leaves:
        h.update(repr(leaf).encode())
    return h.hexdigest()[:16]


def _note_traced(inst: "_InstrumentedJit", args, kwargs, seconds: float) -> None:
    """After a call that traced: remember how to rebuild the label's map,
    and write it now if the persistent cache keeps this program (it took at
    least ``jax_persistent_cache_min_compile_time_secs``, so the second
    ``compile()`` is a hit) and the file is not there yet.  Never raises."""
    try:
        if _has_tracer(jax.tree_util.tree_leaves((args, kwargs))):
            return  # traced inside another program: that program's map has it
        abstract = jax.tree_util.tree_map(_abstract, (args, kwargs))
        module = re.sub(r"[^\w.\-]", "_", "jit_" + inst.__name__)
        key = (module, _signature(abstract, inst._closure_key))
        _traced[key] = (weakref.ref(inst), abstract[0], abstract[1])
        directory = _scopes_dir()
        if directory is None or seconds < float(
            jax.config.jax_persistent_cache_min_compile_time_secs
        ):
            return
        path = os.path.join(directory, f"{key[0]}-{key[1]}.json")
        if not os.path.exists(path):
            doc = _build_map(inst, key, args, kwargs)
            os.makedirs(directory, exist_ok=True)
            _atomic_write_text(path, json.dumps(doc))
    except Exception:
        pass


def _build_map(inst, key, args, kwargs) -> Dict[str, Any]:
    t0 = time.perf_counter()
    with _second_compile(inst._label):
        text = inst._jit.lower(*args, **kwargs).compile().as_text()
    module, scopes = parse_op_scopes(text)
    return {
        "schema": SCOPES_SCHEMA, "module": module, "label": inst._label,
        "signature": key[1], "code": _code_fingerprint(),
        "capture_s": time.perf_counter() - t0, "scopes": scopes,
    }


def _common_path(paths: List[List[str]]) -> List[str]:
    """The leading segments every path shares."""
    common = paths[0]
    for path in paths[1:]:
        k = 0
        while k < min(len(common), len(path)) and common[k] == path[k]:
            k += 1
        common = common[:k]
    return common


def _segment(seg: str) -> str:
    """The scope a name-stack segment holds, or ``""``."""
    m = _WRAPPED.match(seg)
    while m is not None:
        if m.group(1) in ("jit", "pjit"):
            return ""
        seg = m.group(2)
        m = _WRAPPED.match(seg)
    return "" if _STRUCTURAL.match(seg) else seg


def scope_path(op_name: str, scope_names=()) -> str:
    """``jit(step)/while/body/leaf_loop/vmap(bookkeeping)/scatter`` ->
    ``leaf_loop/bookkeeping``: what ``jax.named_scope`` put there.  The last
    segment is the primitive, unless it is one of ``scope_names`` (a
    compiler-made instruction can carry its scope alone); of names the
    compiler merged (``a;b``) the common leading part is kept."""
    paths = []
    for one in op_name.split(";"):
        parts = one.split("/")
        if parts and _segment(parts[-1]) not in scope_names:
            parts = parts[:-1]
        paths.append([seg for seg in map(_segment, parts) if seg])
    return "/".join(_common_path(paths))


def parse_op_scopes(text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction name: scope path}) of a compiled
    executable's text.  An instruction without metadata that calls a
    computation (a ``fusion``) takes the deepest common scope of that
    computation's instructions; one without either maps to ``""``."""
    module = ""
    op_names: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    members: Dict[str, List[str]] = {}
    skip = set()  # never events of their own: parameters, constants, ...
    fused = set()  # ... and what a fusion runs as one event
    comp = ""
    for line in text.splitlines():
        if not module:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                comp = c.group(1)
            continue
        name = m.group(1)
        members.setdefault(comp, []).append(name)
        o = _OP_NAME.search(line)
        op_names[name] = o.group(1) if o else None
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
            if " fusion(" in line:
                fused.add(c.group(1))
        if _NO_EVENT.search(line):
            skip.add(name)
    scope_names = {""}
    for op_name in op_names.values():
        for one in (op_name or "").split(";"):
            scope_names.update(map(_segment, one.split("/")[:-1]))
    scope_names.discard("")
    resolved: Dict[str, Optional[str]] = {}

    def resolve(name: str) -> Optional[str]:
        """The instruction's scope, or None when nothing says."""
        if name in resolved:
            return resolved[name]
        resolved[name] = None  # cycle guard
        op_name = op_names.get(name)
        if op_name is not None:
            scope: Optional[str] = scope_path(op_name, scope_names)
        elif name in calls:
            inner = [resolve(n) for n in members.get(calls[name], ())]
            paths = [s.split("/") if s else [] for s in inner if s is not None]
            scope = "/".join(_common_path(paths)) if paths else None
        else:
            scope = None
        resolved[name] = scope
        return scope

    for c in fused:
        skip.update(members.get(c, ()))
    return module, {
        name: resolve(name) or "" for name in op_names if name not in skip
    }


def op_scope_maps() -> List[Dict[str, Any]]:
    """One document per executable of this code on this backend: the files
    under ``<compilation cache dir>/op_scopes/`` plus, for labels traced in
    this process whose file is absent (no cache directory, or a program the
    cache does not keep), a map built now from the live ``instrumented_jit``
    object.  A dead object's map is simply absent."""
    docs: Dict[Tuple[str, str], Dict[str, Any]] = {}
    directory = _scopes_dir()
    code = _code_fingerprint()
    if directory is not None:
        for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
            try:
                with open(path, "r", encoding="utf-8") as fh:
                    doc = json.load(fh)
            except (OSError, ValueError):
                continue
            if doc.get("schema") == SCOPES_SCHEMA and doc.get("code") == code:
                stem = os.path.basename(path)[: -len(".json")]
                docs[tuple(stem.rsplit("-", 1))] = doc
    for key, (ref, args, kwargs) in list(_traced.items()):
        if key in docs:
            continue
        if key not in _built:
            inst = ref()
            if inst is None:
                del _traced[key]
                continue
            try:
                _built[key] = _build_map(inst, key, args, kwargs)
            except Exception:
                continue
        docs[key] = _built[key]
    return list(docs.values())


def op_scopes() -> Dict[str, Dict[str, str]]:
    """``{HLO module name: {instruction name: scope path}}`` for every
    program compiled through :func:`instrumented_jit`: the join a profiler
    trace needs to put the program's ``jax.named_scope`` names on its device
    events (an ``XLA Ops`` event is named by its instruction, inside an
    ``XLA Modules`` event named by its module).  Two executables that share
    a module name are merged; an instruction they map to different scopes
    reads ``"ambiguous"``.  See :func:`op_scope_maps` for what is found."""
    out: Dict[str, Dict[str, str]] = {}
    for doc in op_scope_maps():
        merged = out.setdefault(doc["module"], {})
        for name, scope in doc["scopes"].items():
            if merged.setdefault(name, scope) != scope:
                merged[name] = AMBIGUOUS
    return out


def instrumented_jit(fun=None, *, label: Optional[str] = None, **jit_kwargs):
    """Drop-in ``jax.jit`` that counts retraces (and, with
    ``obs_device_accounting``, captures executable cost/memory analysis).

    Usable like ``jax.jit``: direct call, decorator, or through
    ``functools.partial``-style keyword binding::

        f = instrumented_jit(impl)
        @instrumented_jit
        def g(x): ...
        @functools.partial(instrumented_jit, static_argnames=("n",))
        def h(x, n): ...

    ``closure_key`` (a string, not a jit option) tells programs of one
    function and one argument signature apart where a bound method closes
    over configuration: it enters the key of the program's ``op_scopes`` map.
    """
    if fun is None:
        return functools.partial(instrumented_jit, label=label, **jit_kwargs)
    name = label or getattr(fun, "__name__", "jit")
    return _InstrumentedJit(fun, name, jit_kwargs)
