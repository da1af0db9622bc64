"""grouped_partition_roofline: the partition kernel's share of its roofline on a packed row of more than one plane group: rows partitioned x every plane of every group x 2 B, read and written, over 819 GB/s, over the kernel's device time, whatever way the kernel is called (one launch a group or one launch a split); bound by memory."""

from benchmark import readers, work_model

KERNELS = {"mosaic": True, "names": "^seg_partition"}
GROUP_PLANES = 128  # a plane group's cap
STAT_BLOCK = 16  # the stat planes' aligned block


def group_planes(n_features: int) -> int:
    """Planes of 2 bytes a packed row of byte-binned columns takes once it
    needs more than one 128-plane group (the layout of ``ops/pallas/seg.py``,
    kept here as arithmetic, not imported): bins two to a plane, the stat
    block at the next multiple of 16, dealt evenly over the groups in
    multiples of 16.  0 for a row that fits one group: that row is
    ``work_model.storage_planes``'s, and ``seg_partition_roofline``'s."""
    bins = (n_features + 1) // 2
    if bins + 7 <= GROUP_PLANES:
        return 0
    used = -(-bins // STAT_BLOCK) * STAT_BLOCK + STAT_BLOCK
    groups = -(-used // GROUP_PLANES)
    return groups * (-(-(-(-used // groups)) // STAT_BLOCK) * STAT_BLOCK)


def partition_bytes(rows_partitioned: float, n_features: int) -> float:
    return rows_partitioned * group_planes(n_features) * 2.0 * 2.0


def read(facts):
    tr = readers._trace(facts)
    planes = group_planes(int(facts["features"]))
    if tr is None or readers._traced_iterations(facts) <= 0 or not planes:
        return None
    seconds = tr.seconds_where(readers._matcher(KERNELS))
    trees = readers._traced_trees(facts)
    if seconds <= 0 or not trees:
        return None
    rows = work_model.sum_trees(trees)["rows_partitioned"] / int(facts["chips"])
    peaks = work_model.peaks_for(facts["device_kind"])
    least, _bound = work_model.least_seconds(
        rows * 3.0, partition_bytes(rows, int(facts["features"])), peaks, int8=True)
    return 100.0 * least / seconds
