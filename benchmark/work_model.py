"""How much work a boosting iteration is, from shapes and the trees grown.

Implementation-independent on purpose: a PR that replaces a kernel is
measured against the same work.  Two levels:

* the ALGORITHM's work (``step_work``), for ``train_step_mfu``: the
  reference algorithm's own traffic (``DataPartition`` and
  ``DenseBin::ConstructHistogram`` of LightGBM) — rows histogrammed = N for
  the root + the smaller child's rows at every split (the subtraction trick
  is the algorithm's, so the larger child is free); rows partitioned = the
  parent's rows at every split;
* a KERNEL's work on its own operands (``seg_hist_work``,
  ``seg_partition_work``), for the kernels' roofline shares.

Each returns (operations, bytes); ``least_seconds`` turns them into the
least time the chip could take: the larger of operations over peak and
bytes over peak bandwidth, and says which bounds.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Tuple

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> Dict[str, float]:
    with open(_PEAKS_FILE, "r", encoding="utf-8") as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {_PEAKS_FILE}; "
                       f"have {sorted(table)}")
    return table[device_kind]


def tree_rows(tree_structure: Dict[str, Any]) -> Dict[str, int]:
    """From one tree of ``dump_model()``: rows histogrammed, rows
    partitioned, splits."""
    hist = part = splits = 0
    root_count = None
    stack = [tree_structure]
    while stack:
        node = stack.pop()
        if "split_index" not in node:
            if root_count is None:
                root_count = int(node.get("leaf_count", 0))
            continue
        n = int(node["internal_count"])
        if root_count is None:
            root_count = n
        kids = [node["left_child"], node["right_child"]]
        counts = [int(k.get("internal_count", k.get("leaf_count", 0))) for k in kids]
        part += n
        hist += min(counts)
        splits += 1
        stack.extend(kids)
    return {"rows_histogrammed": int(root_count or 0) + hist,
            "rows_partitioned": part, "splits": splits, "rows": int(root_count or 0)}


def step_work(rows_histogrammed: int, rows_partitioned: int, n_features: int,
              n_rows: int) -> Tuple[float, float]:
    """(operations, bytes) of one iteration of the algorithm: per row
    histogrammed, F one-byte bins read and a gradient and a hessian (8 B),
    two adds per feature; per row partitioned, a 4-byte index read and
    written; per row of the table, the gradients (score 4 B and label 4 B
    read, gradient and hessian 8 B written, ~10 operations) and the score
    update (4 B read, 4 B written)."""
    ops = rows_histogrammed * n_features * 2.0 + n_rows * 10.0
    byts = (rows_histogrammed * (n_features + 8.0) + rows_partitioned * 8.0
            + n_rows * (16.0 + 8.0))
    return ops, byts


def seg_hist_work(rows: int, n_features: int, bins: int = 256) -> Tuple[float, float]:
    """The segment histogram kernel on its own operands: every streamed row
    meets a one-hot [rows, F * bins] in a matmul with 8 accumulator rows
    (2 MACs-as-operations each), and is read once: ceil(F/2) packed bin
    planes + 5 stat planes of 2 bytes."""
    ops = 2.0 * 8.0 * rows * n_features * bins
    byts = rows * ((n_features + 1) // 2 + 5) * 2.0
    return ops, byts


def seg_partition_work(rows: int, storage_planes: int) -> Tuple[float, float]:
    """The partition moves every plane of every row of the parent's window
    once: read and written, 2 bytes a plane."""
    return rows * 3.0, rows * storage_planes * 2.0 * 2.0


def storage_planes(n_features: int) -> int:
    """Planes of 2 bytes a packed row takes: bins two to a plane + 7 stat
    planes, rounded up to 32 (the layout of ``ops/pallas/seg.py``; kept here
    as arithmetic, not imported)."""
    used = (n_features + 1) // 2 + 7
    return min(128, -(-used // 32) * 32)


def least_seconds(ops: float, byts: float, peaks: Dict[str, float], *,
                  int8: bool = False) -> Tuple[float, str]:
    t_ops = ops / (peaks["int8_ops_per_s"] if int8 else peaks["bf16_flops_per_s"])
    t_bytes = byts / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")


def psum_bytes_per_iteration(splits: int, n_features: int, bins: int = 256,
                             channels: int = 3, itemsize: int = 4) -> float:
    """Bytes one device contributes to histogram all-reduces in one
    iteration: a [F, bins, 3] float32 histogram at the root and at every
    split (the arithmetic of ``parallel/mesh.py::
    mesh_psum_bytes_per_iteration``, copied)."""
    return float((splits + 1) * n_features * bins * channels * itemsize)


def sum_trees(tree_structures: List[Dict[str, Any]]) -> Dict[str, int]:
    tot = {"rows_histogrammed": 0, "rows_partitioned": 0, "splits": 0, "rows": 0}
    for t in tree_structures:
        r = tree_rows(t)
        for k in ("rows_histogrammed", "rows_partitioned", "splits"):
            tot[k] += r[k]
        tot["rows"] = max(tot["rows"], r["rows"])
    return tot
