"""Join a traced run to the program's own names (PR 27).

A v5e trace names a device event by its HLO instruction and nothing else;
the program publishes instruction -> ``jax.named_scope`` path for every
program it compiled (``lightgbm_tpu.obs.op_scopes()``, read from files beside
the compilation cache, so it answers after the Booster is gone), and its
``TraceRecorder`` spans are profiler annotations in ``/host:CPU``.  This
module makes the two joins the per-layer readers under ``layers/`` share:

* device op -> program (XLA module) by time containment in
  ``Trace.modules``, then op -> scope through the published map; the grow
  programs are the modules ``layers/grow_program_ms_per_iter.json`` names;
* the host-span tree from ``Trace.host`` by name and by nesting in time (a
  ``Span`` has no thread id; the training loop's spans are on one thread).

A reader returns ``None`` only when its source is absent (a program without
``op_scopes`` or without spans, a rehearsal without a device plane), never
for a measured zero, and ``scoped_seconds`` raises when under 95 % of the
grow programs' non-kernel device time finds its instruction in the map.

``save_window`` writes the first seconds of a traced window in
``trace_reduce``'s recorded format, whole (not the first 4000 events), so
that module containment and the closure sum can be pinned by tests;
``record_spans.py`` drives it.
"""

from __future__ import annotations

import bisect
import gzip
import json
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
COVERAGE = 0.95

# scope path -> layer, by the path's segments, first match wins: the split
# scan is called from inside candidate_refresh, so it is asked first
LAYERS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("split_scan", ("split_scan",)),
    ("score_update", ("score_update",)),
    ("gradients", ("gradients",)),
    ("sample", ("sample",)),
    ("bookkeeping", ("bookkeeping", "candidate_refresh", "init_state",
                     "leaf_values", "pack_tree")),
    # XLA operations inside the kernels' own scopes
    ("kernel_glue", ("partition", "histogram", "histogram_db0", "histogram_db1",
                     "root_histogram", "fused_grow_step")),
    # once a tree: packing the rows for the kernels, and the sort that turns
    # the segment layout back into a leaf id per row
    ("row_layout", ("pack_rows", "leaf_ids")),
)
UNSCOPED = "unscoped"  # no scope, only leaf_loop, or ambiguous
TOP_SPANS = ("train/iteration", "train/launch", "train/eval", "train/callbacks",
             "train/checkpoint")
BOUNDARY = "bench/boundary"
# the runtime's part of a dispatch that waits for buffers and for room in the
# device's queue: 50 us as a rule, seconds when the device is a program behind
RUNTIME_WAIT = "CommonPjRtLoadedExecutable::ExecutePrepare"
_DISPATCH = re.compile(r"^PjitFunction\(")


class JoinError(RuntimeError):
    pass


def layer_of(scope: str) -> str:
    segments = scope.split("/")
    for layer, names in LAYERS:
        if any(s in names for s in segments):
            return layer
    return UNSCOPED


def grow_pattern(bench_dir: str = HERE) -> "re.Pattern[str]":
    """The grow programs' module names, from the accepted reader's own file."""
    with open(os.path.join(bench_dir, "layers", "grow_program_ms_per_iter.json"),
              "r", encoding="utf-8") as fh:
        return re.compile(json.load(fh)["names"])


def published_maps(facts) -> Optional[List[Dict[str, Any]]]:
    """One ``{"module": name, "scopes": {instruction: scope}}`` per executable
    the program published, or None when it publishes none (the parent of
    PR 27 has no ``op_scope_maps``)."""
    if "op_scopes" in facts:  # a test's, or a recorded trace's own map
        return [{"module": m, "scopes": sc} for m, sc in facts["op_scopes"].items()] or None
    try:
        from lightgbm_tpu.obs import op_scope_maps
    except ImportError:
        return None
    return op_scope_maps() or None


def scopes_of_trace(trace: trace_reduce.Trace, maps) -> Dict[str, Dict[str, str]]:
    """``{module: {instruction: scope}}`` for the programs that ran.  A cache
    directory can hold several executables of one module name (the same
    function at another table's shape); the one whose instructions cover
    most of the module's device time in this trace is the one that ran."""
    ran: Dict[str, Dict[str, float]] = {}
    for op, module in module_of_ops(trace):
        if module is not None:
            names = ran.setdefault(module, {})
            names[op.name] = names.get(op.name, 0.0) + op.dur
    out = {}
    for module, names in ran.items():
        docs = [d for d in maps if d["module"] == module]
        if docs:
            out[module] = max(docs, key=lambda d: sum(
                dur for n, dur in names.items() if n in d["scopes"]))["scopes"]
    return out


def traced_iterations(facts) -> int:
    tm = facts.get("trace_mark")
    return int(tm[3] - tm[1]) if tm and tm[3] is not None else 0


def _device_trace(facts) -> Optional[trace_reduce.Trace]:
    tr = facts.get("trace")
    return tr if tr is not None and tr.devices else None


# ------------------------------------------------------------ device side


def module_of_ops(trace: trace_reduce.Trace) -> List[Tuple[trace_reduce.Op, Optional[str]]]:
    """Every operation of the busiest device with the program that ran it:
    the module event whose interval holds the operation's start."""
    mods = sorted(trace.programs(), key=lambda m: m.start)
    starts = [m.start for m in mods]
    out = []
    for o in trace.ops():
        i = bisect.bisect_right(starts, o.start + 1e-12) - 1
        name = None
        if i >= 0 and o.start < mods[i].start + mods[i].dur + 1e-9:
            name = mods[i].name
        out.append((o, name))
    return out


def scoped_seconds(facts) -> Optional[Dict[str, Dict[str, float]]]:
    """``{"grow": {layer: seconds}, "other": {layer: seconds}}`` of the
    operations that are not Pallas kernels, on the busiest device: inside the
    grow programs and outside them.  None when there is no device plane or
    no published map of a grow program that ran."""
    if "_scoped_seconds" not in facts:  # one join for the readers of a run
        facts["_scoped_seconds"] = _scoped_seconds(facts)
    return facts["_scoped_seconds"]


def _scoped_seconds(facts) -> Optional[Dict[str, Dict[str, float]]]:
    trace = _device_trace(facts)
    maps = published_maps(facts) if trace is not None else None
    if maps is None:
        return None
    scopes = scopes_of_trace(trace, maps)
    grow = grow_pattern()
    out: Dict[str, Dict[str, float]] = {"grow": {}, "other": {}}
    total = found = 0.0
    missing: Dict[str, float] = {}
    for op, module in module_of_ops(trace):
        if op.mosaic:
            continue
        in_grow = module is not None and bool(grow.search(module))
        scope = scopes.get(module, {}).get(op.name) if module else None
        if in_grow:
            total += op.dur
            if scope is None:
                missing[op.name] = missing.get(op.name, 0.0) + op.dur
            else:
                found += op.dur
        elif scope is None:
            continue  # a program the map does not know: no layer of ours
        side = out["grow" if in_grow else "other"]
        layer = layer_of(scope or "")
        side[layer] = side.get(layer, 0.0) + op.dur
    if total <= 0 or not any(grow.search(d["module"]) for d in maps):
        return None  # no grow program ran, or the program published none
    if found < COVERAGE * total:
        worst = sorted(missing.items(), key=lambda kv: -kv[1])[:5]
        raise JoinError(
            f"only {100 * found / total:.1f} % of the grow programs' non-kernel "
            f"device time finds its instruction in op_scopes() (need "
            f"{100 * COVERAGE:.0f} %); longest missing: {worst}"
        )
    return out


def grow_ms_per_iter(facts, layer: str) -> Optional[float]:
    """A layer's non-kernel device time inside the grow programs."""
    sec, n = scoped_seconds(facts), traced_iterations(facts)
    if sec is None or n <= 0:
        return None
    return sec["grow"].get(layer, 0.0) * 1e3 / n


def scope_ms_per_iter(facts, layer: str) -> Optional[float]:
    """A layer's non-kernel device time in whichever program; None where the
    scope occurs in no program that ran."""
    sec, n = scoped_seconds(facts), traced_iterations(facts)
    if sec is None or n <= 0:
        return None
    s = sec["grow"].get(layer, 0.0) + sec["other"].get(layer, 0.0)
    return s * 1e3 / n if s > 0 else None


def closure(facts) -> Optional[Tuple[float, float]]:
    """(kernels + every layer of the grow programs, the grow programs' module
    time), seconds on the busiest device: the two agree when the join is
    right (the module events also hold the gaps between operations)."""
    sec, trace = scoped_seconds(facts), _device_trace(facts)
    if sec is None:
        return None
    grow = grow_pattern()
    kernels = sum(o.dur for o, m in module_of_ops(trace)
                  if o.mosaic and m is not None and grow.search(m))
    module_s = trace.program_seconds_where(lambda m: bool(grow.search(m.name)))
    return kernels + sum(sec["grow"].values()), module_s


# -------------------------------------------------------------- host side


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _intervals(spans, pred) -> List[Tuple[float, float]]:
    return _union([(s.start, s.start + s.dur) for s in spans if pred(s.name)])


def host_unblocked_seconds(spans) -> Optional[float]:
    """Time inside the training loop's top-level spans that is inside
    neither a ``wait/*`` span, nor the harness's boundary, nor a dispatch the
    runtime holds back: what the host needs for an iteration when it is not
    waiting for the device."""
    top = [(s.start, s.start + s.dur) for s in spans if s.name in TOP_SPANS]
    if not top:
        return None
    blocked = [(s.start, s.start + s.dur) for s in spans
               if s.name.startswith("wait/") or s.name in (BOUNDARY, RUNTIME_WAIT)]
    # |top minus blocked| = |top or blocked| - |blocked|
    return (trace_reduce.union_seconds(top + blocked)
            - trace_reduce.union_seconds(blocked))


def host_dispatches(spans) -> Optional[int]:
    """``PjitFunction(...)`` events that start inside a ``train/*`` span."""
    inside = _intervals(spans, lambda n: n.startswith("train/"))
    if not inside:
        return None
    starts = [a for a, _b in inside]
    count, end = 0, -1.0
    # the profiler shows a dispatch twice, one event inside the other
    for s in sorted((s for s in spans if _DISPATCH.match(s.name)),
                    key=lambda s: (s.start, -s.dur)):
        if s.start < end:
            continue
        end = s.start + s.dur
        i = bisect.bisect_right(starts, s.start) - 1
        count += i >= 0 and s.start < inside[i][1]
    return count


def host_per_iter(facts, what, scale: float = 1.0) -> Optional[float]:
    """``what(host spans)`` of the traced window, times ``scale``, per traced
    iteration; None when the program recorded no span."""
    tr, n = facts.get("trace"), traced_iterations(facts)
    if tr is None or n <= 0:
        return None
    value = what(tr.host)
    return None if value is None else float(value) * scale / n


def booster_init_s(facts) -> Optional[float]:
    """``setup/booster_init`` of this process's last ``lgb.train``, from the
    program's span ring (set-up ends before the traced window starts)."""
    spans = facts.get("spans")
    if spans is None:
        try:
            from lightgbm_tpu.obs import get_tracer
        except ImportError:
            return None
        spans = get_tracer().spans()
    durs = [s["dur"] for s in spans if s.get("name") == "setup/booster_init"]
    return durs[-1] * 1e-6 if durs else None


# ------------------------------------------------------- recorded windows


def save_window(raw, path: str, seconds: float) -> None:
    """The first ``seconds`` after the begin mark of a traced window, whole,
    in ``trace_reduce.load_recorded``'s format."""
    devices, host, names, modules = raw
    begins = [h for h in host if h[0] == trace_reduce.BEGIN_MARK]
    if not begins:
        raise trace_reduce.TraceError("trace lacks the harness's begin mark")
    t0 = begins[0][1]
    t1 = t0 + seconds * 1e9
    doc: Dict[str, Any] = {"planes": names, "devices": {}, "modules": {}}
    for plane, evs in devices.items():
        doc["devices"][plane] = [[n, s - t0, d, w] for n, s, d, w in evs
                                 if s < t1 and s + d > t0]
    for plane, evs in modules.items():
        doc["modules"][plane] = [[n, s - t0, d, w] for n, s, d, w in evs
                                 if s < t1 and s + d > t0]
    doc["host"] = [[trace_reduce.BEGIN_MARK, 0.0, begins[0][2]],
                   [trace_reduce.END_MARK, t1 - t0, 0.0]]
    doc["host"] += [[n, s - t0, d] for n, s, d in host
                    if s < t1 and s + d > t0
                    and n not in (trace_reduce.BEGIN_MARK, trace_reduce.END_MARK)]
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with gzip.open(path, "wt", encoding="utf-8") as fh:
        json.dump(doc, fh, separators=(",", ":"))


def save_scopes(trace: trace_reduce.Trace, path: str) -> None:
    """The published maps of the programs that ran in ``trace``."""
    scopes = scopes_of_trace(trace, published_maps({}) or [])
    with gzip.open(path, "wt", encoding="utf-8") as fh:
        json.dump(scopes, fh, separators=(",", ":"), sort_keys=True)


def load_scopes(path: str) -> Dict[str, Dict[str, str]]:
    with gzip.open(path, "rt", encoding="utf-8") as fh:
        return json.load(fh)
