"""go_left_ms_per_iter: non-kernel operations of the grow programs under the scope go_left (ops/grower.py: the split predicate of a grouped row, decided once from the split feature's own plane before the partition moves every plane group by the same bits), per traced iteration."""

from benchmark import scope_join

SCOPE = "go_left"


def read(facts):
    trace, n = scope_join._device_trace(facts), scope_join.traced_iterations(facts)
    maps = scope_join.published_maps(facts) if trace is not None else None
    if maps is None or n <= 0:
        return None
    scopes = scope_join.scopes_of_trace(trace, maps)
    grow = scope_join.grow_pattern()
    seconds = 0.0
    for op, module in scope_join.module_of_ops(trace):
        if op.mosaic or module is None or not grow.search(module):
            continue
        scope = scopes.get(module, {}).get(op.name) or ""
        if SCOPE in scope.split("/"):
            seconds += op.dur
    # a program without the scope (a one-group row, a parent of PR 29) has
    # nothing to read
    return seconds * 1e3 / n if seconds > 0 else None
