"""goss_sample_ms_per_iter: device time of the operations under the scope sample (boosting/sampling.py goss_sample: the metric |g h|, the exact selection of its top_k-th largest on the bit patterns, the hash draws of the rest, the amplification; once an iteration), in whichever program runs them (the launch scan's body, or the per-iteration loop's own dispatch), per traced iteration."""

import os

from benchmark import contract

_quantize = contract.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "grad_quantize_ms_per_iter.py"),
    "benchmark_layer_grad_quantize_ms_per_iter",
)


def read(facts):
    return _quantize.scope_ms_per_iter(facts, "sample")
