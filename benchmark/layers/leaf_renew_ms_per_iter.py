"""leaf_renew_ms_per_iter: device time of the operations under the scope renew_leaf (ops/quantize.py renew_leaf_values: the segment sums of the true gradients over leaf_id and the leaf outputs, once a tree, before the score update), in whichever program runs them, per traced iteration."""

import os

from benchmark import contract

_quantize = contract.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "grad_quantize_ms_per_iter.py"),
    "benchmark_layer_grad_quantize_ms_per_iter",
)


def read(facts):
    return _quantize.scope_ms_per_iter(facts, "renew_leaf")
