"""score_update_ms_per_iter: operations under the scope score_update, in whichever program (the launch scan, jit__add_tree_to_score_impl)."""

from benchmark import scope_join


def read(facts):
    return scope_join.scope_ms_per_iter(facts, "score_update")
