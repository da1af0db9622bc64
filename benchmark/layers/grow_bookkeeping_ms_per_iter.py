"""grow_bookkeeping_ms_per_iter: non-kernel operations of the grow programs under bookkeeping, candidate_refresh (outside its split scan), init_state, leaf_values, pack_tree."""

from benchmark import scope_join


def read(facts):
    return scope_join.grow_ms_per_iter(facts, "bookkeeping")
