"""bag_compact_ms_per_iter: device time of the once-a-tree pass that brings the in-bag rows to the front of the packed buffer (ops/grower.py, GrowerParams.bag_window): the kernel bag_compact_pallas, by its name, and the XLA operations under the scope bag_compact around it (the bag's bits, padded); per traced iteration.  A program that publishes its scopes and ran nothing of it (no sampler; a parent of PR 35, which streams the whole table with a mask instead) reads a measured 0."""

import os

from benchmark import contract, readers, scope_join

_quantize = contract.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "grad_quantize_ms_per_iter.py"),
    "benchmark_layer_grad_quantize_ms_per_iter",
)
KERNEL = {"mosaic": True, "names": "^bag_compact"}


def scope_seconds(facts, scope):
    """Non-kernel device seconds of the operations whose published scope path
    has ``scope`` as a segment (``grad_quantize_ms_per_iter.scope_ms_per_iter``'s
    reading): None where the source is absent (no device trace, no published
    maps, no traced iteration), 0.0 where the programs that ran publish their
    scopes and none of their operations lies under this one."""
    trace, n = scope_join._device_trace(facts), scope_join.traced_iterations(facts)
    if trace is None or n <= 0 or scope_join.published_maps(facts) is None:
        return None
    return (_quantize.scope_ms_per_iter(facts, scope) or 0.0) * n / 1e3


def kernel_seconds(facts):
    """Device seconds of the compaction kernel in the traced window; None
    where no such kernel ran."""
    tr = readers._trace(facts)
    if tr is None or readers._traced_iterations(facts) <= 0:
        return None
    seconds = tr.seconds_where(readers._matcher(KERNEL))
    return seconds if seconds > 0 else None


def read(facts):
    glue = scope_seconds(facts, "bag_compact")
    if glue is None:
        return None
    n = readers._traced_iterations(facts)
    return ((kernel_seconds(facts) or 0.0) + glue) * 1e3 / n
