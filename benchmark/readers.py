"""Per-layer metric readers.

Each per-layer metric of ``BENCHMARK.json`` has a file of its own under
``benchmark/layers/``: ``<metric>.py`` defining ``read(facts)``, or
``<metric>.json`` naming one of the stock readers below with its
parameters.  A reader that finds nothing to read returns ``None`` and the
harness leaves the metric out of the line; it never returns 0 for a share.

``facts`` is what one traced run knows: the reduced ``trace``
(``trace_reduce.Trace``), the boundary ``marks`` of the harness's clock, the
``tree_dumps`` of the model, shapes, set-up facts and the peaks' device
kind.
"""

from __future__ import annotations

import json
import re
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmark import contract, trace_reduce, work_model


def _traced_iterations(facts) -> int:
    tm = facts.get("trace_mark")
    if not tm or tm[3] is None:
        return 0
    return int(tm[3] - tm[1])


def _traced_trees(facts) -> List[Dict[str, Any]]:
    tm = facts["trace_mark"]
    return facts["tree_dumps"][int(tm[1]): int(tm[3])]


def _matcher(spec: Dict[str, Any]) -> Callable[[trace_reduce.Op], bool]:
    """Operations by their own names: ``names`` / ``not_names`` are regular
    expressions searched in the instruction's name, ``mosaic`` keeps only
    Pallas kernels (true) or only XLA's own operations (false)."""
    name_re = re.compile(spec["names"]) if spec.get("names") else None
    not_re = re.compile(spec["not_names"]) if spec.get("not_names") else None
    mosaic = spec.get("mosaic")

    def pred(o: trace_reduce.Op) -> bool:
        if mosaic is not None and o.mosaic != bool(mosaic):
            return False
        if not_re is not None and not_re.search(o.name):
            return False
        return name_re is None or bool(name_re.search(o.name))

    return pred


def _trace(facts) -> Optional[trace_reduce.Trace]:
    tr = facts.get("trace")
    return tr if tr is not None and tr.devices else None


# ------------------------------------------------------------ stock readers


def ops_ms_per_iter(facts, spec) -> Optional[float]:
    """Device time of the operations a name selects, on the busiest device,
    per traced iteration."""
    tr, n = _trace(facts), _traced_iterations(facts)
    if tr is None or n <= 0:
        return None
    s = tr.seconds_where(_matcher(spec))
    return s * 1e3 / n if s > 0 else None


def program_ms_per_iter(facts, spec) -> Optional[float]:
    """Device time of the programs (XLA modules) a name selects, on the
    busiest device, per traced iteration."""
    tr, n = _trace(facts), _traced_iterations(facts)
    if tr is None or n <= 0:
        return None
    s = tr.program_seconds_where(_matcher(spec))
    return s * 1e3 / n if s > 0 else None


def idle_share(facts, spec) -> Optional[float]:
    """1 - busy/window of the traced sub-window, busy averaged over the
    devices as in the line's ``device.busy_s``."""
    tr = _trace(facts)
    if tr is None:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def fact(facts, spec) -> Optional[float]:
    v = facts.get(spec["fact"])
    return float(v) if v is not None else None


def iter_wall_p50_ms(facts, spec) -> Optional[float]:
    """Median wall time per iteration between boundaries of the window, from
    leaving one boundary to arriving at the next (per launch over its
    iterations where a boundary closes several).  The profiler's own start
    and stop happen inside a boundary and are so left out."""
    marks = [m for m in facts["marks"] if m[0] >= facts["t0"]]
    per = [(b[0] - a[2]) / (b[1] - a[1]) * 1e3
           for a, b in zip(marks, marks[1:]) if b[1] > a[1]]
    return float(np.median(per)) if per else None


def step_mfu(facts, spec) -> Optional[float]:
    """The whole step's share of the chip's peak: the least time the chip
    could take for the algorithm's work of the traced iterations (the larger
    of operations over peak and bytes over peak bandwidth) over the time
    they took, per chip."""
    tr, n = _trace(facts), _traced_iterations(facts)
    if tr is None or n <= 0:
        return None
    trees = _traced_trees(facts)
    if not trees:
        return None
    peaks = work_model.peaks_for(facts["device_kind"])
    tot = work_model.sum_trees(trees)
    chips = int(facts["chips"])
    ops, byts = work_model.step_work(
        tot["rows_histogrammed"] / chips, tot["rows_partitioned"] / chips,
        facts["features"], facts["rows"] / chips * len(trees),
    )
    least, _bound = work_model.least_seconds(ops, byts, peaks)
    return 100.0 * least / tr.window_s


def kernel_roofline(facts, spec) -> Optional[float]:
    """A kernel's share of its roofline: the least time for its own
    operands over the device time of its operations."""
    tr = _trace(facts)
    if tr is None or _traced_iterations(facts) <= 0:
        return None
    seconds = tr.seconds_where(_matcher(spec))
    trees = _traced_trees(facts)
    if seconds <= 0 or not trees:
        return None
    peaks = work_model.peaks_for(facts["device_kind"])
    tot = work_model.sum_trees(trees)
    chips = int(facts["chips"])
    f = int(facts["features"])
    ops = byts = 0.0
    if "seg_hist" in spec["kernels"]:
        o, b = work_model.seg_hist_work(tot["rows_histogrammed"] / chips, f)
        ops, byts = ops + o, byts + b
    if "seg_hist_children" in spec["kernels"]:  # all but the roots
        rows = tot["rows_histogrammed"] - tot["rows"] * len(trees)
        o, b = work_model.seg_hist_work(rows / chips, f)
        ops, byts = ops + o, byts + b
    if "seg_partition" in spec["kernels"]:
        o, b = work_model.seg_partition_work(tot["rows_partitioned"] / chips,
                                             work_model.storage_planes(f))
        ops, byts = ops + o, byts + b
    # a one-hot multiply-accumulate is held against the chip's fastest way
    # to do one (int8), whatever the kernel computes in: the share of a
    # bf16 kernel can then reach 50 %, of an int8 kernel 100 %, never more
    least, _bound = work_model.least_seconds(ops, byts, peaks, int8=True)
    return 100.0 * least / seconds


def collective_ms_per_iter(facts, spec) -> Optional[float]:
    """All-reduce time on the busiest device per traced iteration; with
    ``exposed`` only the part during which no other operation runs there."""
    tr, n = _trace(facts), _traced_iterations(facts)
    if tr is None or n <= 0:
        return None
    pat = re.compile(spec.get("names", r"all-reduce|all_reduce|AllReduce|psum"))
    ops = tr.ops()
    coll = [(o.start, o.start + o.dur) for o in ops if pat.search(o.name)]
    if not coll:
        return None
    total = trace_reduce.union_seconds(coll)
    if spec.get("exposed"):
        other = [(o.start, o.start + o.dur) for o in ops if not pat.search(o.name)]
        both = trace_reduce.union_seconds(coll + other)
        total = both - trace_reduce.union_seconds(other)
    return total * 1e3 / n if total > 0 else None


STOCK: Dict[str, Callable] = {
    "ops_ms_per_iter": ops_ms_per_iter,
    "program_ms_per_iter": program_ms_per_iter,
    "idle_share": idle_share,
    "fact": fact,
    "iter_wall_p50_ms": iter_wall_p50_ms,
    "step_mfu": step_mfu,
    "kernel_roofline": kernel_roofline,
    "collective_ms_per_iter": collective_ms_per_iter,
}


def read_metric(manifest, metric: str, facts) -> Optional[float]:
    path = manifest.layer_reader_path(metric)
    if path.endswith(".py"):
        value = contract.load_module(path, f"benchmark_layer_{metric}").read(facts)
    else:
        with open(path, "r", encoding="utf-8") as fh:
            spec_doc = json.load(fh)
        reader = STOCK.get(spec_doc.get("reader"))
        if reader is None:
            raise KeyError(f"{path}: no stock reader {spec_doc.get('reader')!r}; "
                           f"have {sorted(STOCK)}")
        value = reader(facts, spec_doc)
    if value is None:
        return None
    value = float(value)
    if not np.isfinite(value):
        raise ValueError(f"reader of {metric} returned {value}")
    return value
