"""grad_quantize_ms_per_iter: device time of the operations under the scope quantize (ops/quantize.py quantize_gradients: the two maxima, the rounding offsets' integer mix and the truncation, once a tree), in whichever program runs them (the launch scan's body, or the per-iteration loop's own dispatch), per traced iteration."""

from benchmark import scope_join

SCOPE = "quantize"


def scope_ms_per_iter(facts, scope):
    """Non-kernel device time of the operations whose published scope path
    has ``scope`` as a segment; None where no program that ran has it (a
    booster without quantized gradients, a parent of PR 33)."""
    trace, n = scope_join._device_trace(facts), scope_join.traced_iterations(facts)
    maps = scope_join.published_maps(facts) if trace is not None else None
    if maps is None or n <= 0:
        return None
    scopes = scope_join.scopes_of_trace(trace, maps)
    seconds = 0.0
    for op, module in scope_join.module_of_ops(trace):
        if op.mosaic or module is None:
            continue
        path = scopes.get(module, {}).get(op.name) or ""
        if scope in path.split("/"):
            seconds += op.dur
    return seconds * 1e3 / n if seconds > 0 else None


def read(facts):
    return scope_ms_per_iter(facts, SCOPE)
