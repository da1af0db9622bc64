"""grow_unscoped_ms_per_iter: the rest of the grow programs' non-kernel operations: no scope, leaf_loop alone, or ambiguous."""

from benchmark import scope_join


def read(facts):
    return scope_join.grow_ms_per_iter(facts, scope_join.UNSCOPED)
