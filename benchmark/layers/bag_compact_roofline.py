"""bag_compact_roofline: the compaction kernel's share of its roofline: every row of the table's packed buffer, every storage plane, 2 B read and written once a tree, over 819 GB/s, over the device time of bag_compact_pallas; bound by memory."""

import os

from benchmark import contract, readers, work_model

_compact = contract.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "bag_compact_ms_per_iter.py"),
    "benchmark_layer_bag_compact_ms_per_iter",
)


def compact_bytes(rows: float, n_features: int) -> float:
    """One stable partition of the whole buffer: rows x storage planes x 2 B,
    read and written (the planes of ``work_model.storage_planes``: the
    layout's arithmetic, not imported from the program)."""
    return rows * work_model.storage_planes(n_features) * 2.0 * 2.0


def read(facts):
    seconds = _compact.kernel_seconds(facts)
    trees = readers._traced_trees(facts) if seconds else None
    if not seconds or not trees:
        return None
    rows = float(facts["rows"]) / int(facts["chips"]) * len(trees)
    peaks = work_model.peaks_for(facts["device_kind"])
    least, _bound = work_model.least_seconds(
        rows * 3.0, compact_bytes(rows, int(facts["features"])), peaks, int8=True)
    return 100.0 * least / seconds
