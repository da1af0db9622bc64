"""From a profiler trace to numbers: the one reduction every PR is read by.

``read_xplane`` + ``build`` read the ``.xplane.pb`` that ``jax.profiler`` wrote for the
traced sub-window (``jax.profiler.ProfileData``, nothing but JAX) into a
``Trace``: per device the leaf operations and the programs (XLA modules)
that ran, and the host's annotated spans, all on the trace's own clock, cut
to the window between the harness's ``bench/trace_begin`` and
``bench/trace_end`` marks.

What a v5e trace holds (read by hand, PR 26): a device plane
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per program
run, named ``jit_<fn>(<hash>)``) and ``XLA Ops`` (one event per HLO
instruction run, named by the instruction's whole text, ``%name = shape
op(...)``; a ``while`` holds its body's events).  The events carry NO
name-scope: ``jax.named_scope`` labels do not reach them.  So a layer is
read by its kernels' own names (a Pallas kernel is a ``custom-call`` whose
text says ``tpu_custom_call``) and by the program it runs in.

Rules that PR 23's refusal taught:

* ``busy_s`` is each device's own union of operation intervals, averaged
  over the devices: never a sum over devices, never above ``window_s``.
  Sums by kernel, the breakdown and the idle gaps are the busiest device's.
* containers (``while``, ``conditional``, ``call``: operations whose interval
  holds other operations of the same device) are not operations: their
  interval would count a stalled loop as busy.
* no device plane is an error that names the planes found, not a zero.

``save_recorded`` / ``load_recorded`` keep a trimmed trace as JSON so the
reduction is pinned by tests without a chip (``benchmark/traces/``).
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

BEGIN_MARK = "bench/trace_begin"
END_MARK = "bench/trace_end"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# lines of a device plane that hold operations (the others hold steps,
# modules and the framework's own markers)
_OP_LINE = "XLA Ops"
_MODULE_LINE = "XLA Modules"
_HLO = re.compile(r"^%(\S+) = (\(?[a-z0-9]+\[[0-9,]*\])?")


class TraceError(RuntimeError):
    pass


@dataclasses.dataclass
class Op:
    name: str  # the HLO instruction's name ("seg_hist_pallas_batch.16"), or a module's
    start: float  # seconds on the trace's clock, from the window's begin
    dur: float
    what: str  # "mosaic <result shape>" for a Pallas kernel, else ""

    @property
    def mosaic(self) -> bool:
        return self.what.startswith("mosaic")


@dataclasses.dataclass
class Span:
    name: str
    start: float
    dur: float


@dataclasses.dataclass
class Trace:
    window_s: float
    devices: Dict[str, List[Op]]  # plane name -> leaf ops inside the window
    host: List[Span]  # host spans inside the window (annotations, dispatches)
    planes_found: List[str]
    modules: Dict[str, List[Op]] = dataclasses.field(default_factory=dict)

    # ---- busy
    def busy_by_device(self) -> Dict[str, float]:
        return {d: union_seconds([(o.start, o.start + o.dur) for o in ops])
                for d, ops in self.devices.items()}

    @property
    def busiest(self) -> Optional[str]:
        busy = self.busy_by_device()
        return max(busy, key=busy.get) if busy else None

    @property
    def busy_s(self) -> Optional[float]:
        """Seconds in which an operation ran, averaged over the devices (the
        contract's ``device.busy_s``): each device's own union of intervals,
        so never a sum over devices and never above the window."""
        busy = self.busy_by_device()
        return min(sum(busy.values()) / len(busy), self.window_s) if busy else None

    def ops(self) -> List[Op]:
        d = self.busiest
        return self.devices[d] if d else []

    def programs(self) -> List[Op]:
        d = self.busiest
        return self.modules.get(d, []) if d else []

    # ---- sums by name, on the busiest device
    def seconds_where(self, pred) -> float:
        return float(sum(o.dur for o in self.ops() if pred(o)))

    def program_seconds_where(self, pred) -> float:
        return float(sum(o.dur for o in self.programs() if pred(o)))

    def breakdown(self) -> Dict[str, List[List[Any]]]:
        by_name: Dict[str, float] = {}
        for o in self.ops():
            key = op_label(o)
            by_name[key] = by_name.get(key, 0.0) + o.dur
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(idle_gaps(self.ops(), self.window_s), key=lambda g: g[0] - g[1])
        labelled: Dict[str, float] = {}
        # the longest gaps are labelled by what the host was doing; the
        # many short ones between operations are one entry
        for a, b in gaps[:_LABELLED_GAPS]:
            lab = host_label(self.host, a, b)
            labelled[lab] = labelled.get(lab, 0.0) + (b - a)
        rest = sum(b - a for a, b in gaps[_LABELLED_GAPS:])
        if rest > 0:
            labelled[f"gaps under {gaps[_LABELLED_GAPS - 1][1] - gaps[_LABELLED_GAPS - 1][0]:.2e}s "
                     f"({len(gaps) - _LABELLED_GAPS})"] = rest
        top_gaps = sorted(labelled.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, float(v)] for k, v in top],
                "idle_gaps": [[k, float(v)] for k, v in top_gaps]}


_LABELLED_GAPS = 200


def kind_of(name: str) -> str:
    """An instruction's name without its trailing number."""
    return re.sub(r"[.\-_]?\d+$", "", name)


def op_label(o: Op) -> str:
    """A stable label: the kind, and for a Pallas kernel its result shape
    (variants of one kernel differ by it)."""
    kind = kind_of(o.name)
    return f"{kind} [{o.what}]" if o.what else kind


def parse_hlo(text: str) -> Tuple[str, str]:
    """(name, what) of an ``XLA Ops`` event's text."""
    m = _HLO.match(text)
    if not m:
        return text[:80], ""
    what = ""
    if "tpu_custom_call" in text:
        what = "mosaic " + (m.group(2) or "").lstrip("(")
    return m.group(1), what


def union_seconds(intervals: Sequence[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def idle_gaps(ops: Sequence[Op], window_s: float) -> List[Tuple[float, float]]:
    gaps, end = [], 0.0
    for o in sorted(ops, key=lambda o: o.start):
        if o.start > end:
            gaps.append((end, o.start))
        end = max(end, o.start + o.dur)
    if window_s > end:
        gaps.append((end, window_s))
    return gaps


def host_label(host: Sequence[Span], a: float, b: float) -> str:
    """What the host was doing in [a, b]: the shortest span that covers at
    least half of it, else the span that covers most of it."""
    half, best_half, length = 0.5 * (b - a), None, float("inf")
    best_any, cover = "host: no span", 0.0
    for s in host:
        c = min(b, s.start + s.dur) - max(a, s.start)
        if c <= 0:
            continue
        if c >= half and s.dur < length:
            best_half, length = "host: " + s.name, s.dur
        if c > cover:
            best_any, cover = "host: " + s.name, c
    return best_half or best_any


def leaves_only(events: List[Tuple[str, float, float, str]]):
    """Drop containers: an event whose interval holds a later-starting event
    of the same line."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, e in enumerate(events):
        end = e[1] + e[2]
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is not None and nxt[1] < end and nxt[1] + nxt[2] <= end + 1e-12 and e[2] > 0:
            continue  # holds the next event: a container
        out.append(e)
    return out


# ------------------------------------------------------------- reading


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_xplane(path: str):
    """(device events, host events, plane names, module events), raw and
    uncut: device events per plane as (name, start_ns, dur_ns, what)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float, str]]] = {}
    modules: Dict[str, List[Tuple[str, float, float, str]]] = {}
    host: List[Tuple[str, float, float]] = []
    names: List[str] = []
    for plane in pd.planes:
        names.append(plane.name)
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == _OP_LINE:
                    evs = []
                    for e in line.events:
                        name, what = parse_hlo(e.name)
                        evs.append((name, float(e.start_ns), float(e.duration_ns), what))
                    devices[plane.name] = evs
                elif line.name == _MODULE_LINE:
                    modules[plane.name] = [
                        (re.sub(r"\(\d+\)$", "", e.name), float(e.start_ns),
                         float(e.duration_ns), "") for e in line.events
                    ]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0 or e.name in (BEGIN_MARK, END_MARK):
                        host.append((e.name, float(e.start_ns), float(e.duration_ns)))
    return devices, host, names, modules


def build(devices, host, names, modules=None, *, n_devices: int,
          allow_no_device: bool = False) -> Trace:
    begins = [h for h in host if h[0] == BEGIN_MARK]
    ends = [h for h in host if h[0] == END_MARK]
    if not begins or not ends:
        raise TraceError(f"trace lacks the harness's {BEGIN_MARK}/{END_MARK} marks")
    t0 = begins[0][1] + begins[0][2]
    t1 = ends[-1][1]
    if t1 <= t0:
        raise TraceError(f"traced window is empty: begin {t0}, end {t1}")
    out: Dict[str, List[Op]] = {}
    for plane, evs in devices.items():
        ops = []
        for name, s, d, what in leaves_only(evs):
            a, b = max(s, t0), min(s + d, t1)
            if b > a:
                ops.append(Op(name, (a - t0) * 1e-9, (b - a) * 1e-9, what))
        if ops:
            out[plane] = ops
    mods: Dict[str, List[Op]] = {}
    for plane, evs in (modules or {}).items():
        mods[plane] = [Op(n, (max(s, t0) - t0) * 1e-9, (min(s + d, t1) - max(s, t0)) * 1e-9, w)
                       for n, s, d, w in evs if min(s + d, t1) > max(s, t0)]
    if not out and not allow_no_device:
        raise TraceError("no device plane with operations in the traced window; "
                         f"planes found: {names}")
    if out and len(out) < n_devices:
        raise TraceError(f"operations on {len(out)} device planes, the cell uses "
                         f"{n_devices}; planes found: {names}")
    spans = []
    for name, s, d in host:
        a, b = max(s, t0), min(s + d, t1)
        if b > a and name not in (BEGIN_MARK, END_MARK):
            spans.append(Span(name, (a - t0) * 1e-9, (b - a) * 1e-9))
    return Trace(window_s=(t1 - t0) * 1e-9, devices=out, host=spans, planes_found=names,
                 modules=mods)


# ------------------------------------------------- recorded (trimmed) traces


def save_recorded(raw, path: str, max_ops: int = 4000, max_host: int = 400) -> None:
    """Write (devices, host, names) trimmed: the first ``max_ops`` events of
    each device after the begin mark, the marks, and the longest host spans."""
    devices, host, names, modules = raw
    begins = [h for h in host if h[0] == BEGIN_MARK]
    t0 = begins[0][1] if begins else 0.0
    doc = {"planes": names, "devices": {}, "host": [], "modules": {}}
    last = t0
    for plane, evs in devices.items():
        keep = sorted((e for e in evs if e[1] >= t0), key=lambda e: e[1])[:max_ops]
        doc["devices"][plane] = [[n, s - t0, d, sc] for n, s, d, sc in keep]
        if keep:  # containers are kept, but do not stretch the recorded window
            last = max(last, max(e[1] + e[2] for e in leaves_only(keep)))
    for plane, evs in modules.items():
        doc["modules"][plane] = [[n, s - t0, d, w] for n, s, d, w in evs
                                 if t0 <= s and s + d <= last]
    marks = [h for h in host if h[0] in (BEGIN_MARK, END_MARK)]
    spans = sorted((h for h in host if h[0] not in (BEGIN_MARK, END_MARK)
                    and t0 <= h[1] <= last), key=lambda h: -h[2])[:max_host]
    # the recorded window ends where the kept operations end
    doc["host"] = [[n, s - t0, d] for n, s, d in marks if n == BEGIN_MARK]
    doc["host"].append([END_MARK, last - t0, 0.0])
    doc["host"] += [[n, s - t0, d] for n, s, d in spans]
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with gzip.open(path, "wt", encoding="utf-8") as fh:
        json.dump(doc, fh)


def load_recorded(path: str, *, n_devices: int) -> Trace:
    with gzip.open(path, "rt", encoding="utf-8") as fh:
        doc = json.load(fh)
    devices = {p: [tuple(e) for e in evs] for p, evs in doc["devices"].items()}
    host = [tuple(h) for h in doc["host"]]
    modules = {p: [tuple(e) for e in evs] for p, evs in doc.get("modules", {}).items()}
    return build(devices, host, doc["planes"], modules, n_devices=n_devices)


def summarize(path: str, top: int = 25) -> Dict[str, Any]:
    """What a trace holds, for reading by hand before trusting a reduction:
    planes, lines, event counts, the commonest names and the stats keys."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    doc: Dict[str, Any] = {"file": path, "bytes": os.path.getsize(path), "planes": []}
    for plane in pd.planes:
        p = {"name": plane.name, "lines": []}
        for line in plane.lines:
            n, total = 0, 0.0
            names: Dict[str, List[float]] = {}
            sample = []
            for e in line.events:
                n += 1
                total += e.duration_ns
                rec = names.setdefault(e.name, [0, 0.0])
                rec[0] += 1
                rec[1] += e.duration_ns
                if len(sample) < 6 or (n % 5000 == 0 and len(sample) < 40):
                    sample.append({"name": e.name, "start_ns": e.start_ns,
                                   "dur_ns": e.duration_ns,
                                   "stats": {k: (v if isinstance(v, (int, float)) else str(v)[:300])
                                             for k, v in e.stats}})
            tops = sorted(names.items(), key=lambda kv: -kv[1][1])[:top]
            p["lines"].append({"name": line.name, "events": n, "sum_ms": total * 1e-6,
                               "top": [[k, v[0], v[1] * 1e-6] for k, v in tops],
                               "sample": sample})
        doc["planes"].append(p)
    return doc


if __name__ == "__main__":
    import sys

    print(json.dumps(summarize(find_xplane(sys.argv[1])), indent=1))
