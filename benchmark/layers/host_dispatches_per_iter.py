"""host_dispatches_per_iter: PjitFunction(...) events that start inside the program's train/* spans, per traced iteration."""

from benchmark import scope_join


def read(facts):
    return scope_join.host_per_iter(facts, scope_join.host_dispatches)
