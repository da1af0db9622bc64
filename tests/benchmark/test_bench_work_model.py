"""The work model on a hand-built tree, and the peaks table."""

import pytest

from benchmark import work_model


def leaf(n):
    return {"leaf_index": 0, "leaf_value": 0.0, "leaf_count": n}


def node(i, n, left, right):
    return {"split_index": i, "internal_count": n, "left_child": left,
            "right_child": right, "split_feature": 0, "threshold": 0.0,
            "split_gain": 1.0}


#            1000
#          /      \
#       300        700
#      /   \      /   \
#    100   200  650    50
TREE = node(0, 1000, node(1, 300, leaf(100), leaf(200)), node(2, 700, leaf(650), leaf(50)))


def test_tree_rows_by_hand():
    r = work_model.tree_rows(TREE)
    # histogrammed: root 1000 + smaller children 300 + 100 + 50
    assert r == {"rows_histogrammed": 1450, "rows_partitioned": 2000,
                 "splits": 3, "rows": 1000}
    assert work_model.tree_rows(leaf(77))["rows_histogrammed"] == 77
    tot = work_model.sum_trees([TREE, TREE])
    assert tot["rows_histogrammed"] == 2900 and tot["splits"] == 6 and tot["rows"] == 1000


def test_step_work_and_bounds():
    ops, byts = work_model.step_work(1450, 2000, 28, 1000)
    assert ops == 1450 * 28 * 2 + 1000 * 10
    assert byts == 1450 * 36 + 2000 * 8 + 1000 * 24
    peaks = work_model.peaks_for("TPU v5 lite")
    t, bound = work_model.least_seconds(ops, byts, peaks)
    assert bound == "memory" and t == pytest.approx(byts / 819e9)
    # the one-hot histogram kernel is bound by its matmul
    o, b = work_model.seg_hist_work(1_000_000, 67)
    assert o == 2 * 8 * 1_000_000 * 67 * 256 and b == 1_000_000 * (34 + 5) * 2
    t, bound = work_model.least_seconds(o, b, peaks, int8=True)
    assert bound == "compute" and t == pytest.approx(o / 393e12)
    assert work_model.least_seconds(o, b, peaks)[0] == pytest.approx(o / 197e12)


def test_storage_planes_and_partition_bytes():
    assert work_model.storage_planes(28) == 32  # 14 bin planes + 7 -> 32
    assert work_model.storage_planes(67) == 64  # 34 + 7 -> 64
    _, byts = work_model.seg_partition_work(1000, 64)
    assert byts == 1000 * 64 * 2 * 2
    assert work_model.psum_bytes_per_iteration(254, 67) == 255 * 67 * 256 * 3 * 4


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work_model.peaks_for("TPU v9 imaginary")
