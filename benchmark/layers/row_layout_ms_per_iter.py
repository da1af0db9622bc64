"""row_layout_ms_per_iter: non-kernel operations of the grow programs under pack_rows (packing the rows for the kernels) and leaf_ids (the sort that turns the segment layout back into a leaf id per row), once a tree."""

from benchmark import scope_join


def read(facts):
    return scope_join.grow_ms_per_iter(facts, "row_layout")
