"""oob_score_ms_per_iter: device time of the operations under the scope oob_score (ops/grower.py under GrowerParams.bag_window: the tree's contraction walk over the binned matrix, score_lookup.tree_leaves, which gives every row its leaf once a tree, the out-of-bag rows having no other), in whichever program runs them, per traced iteration.  A program that publishes its scopes and ran nothing under this one (no sampler; a parent of PR 35, which partitions every row) reads a measured 0."""

import os

from benchmark import contract, readers

_compact = contract.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "bag_compact_ms_per_iter.py"),
    "benchmark_layer_bag_compact_ms_per_iter",
)


def read(facts):
    seconds = _compact.scope_seconds(facts, "oob_score")
    if seconds is None:
        return None
    return seconds * 1e3 / readers._traced_iterations(facts)
