"""host_unblocked_ms_per_iter: host time inside train/iteration, train/launch, train/eval, train/callbacks, train/checkpoint that is inside neither a wait/* span, nor bench/boundary, nor a dispatch the runtime holds back."""

from benchmark import scope_join


def read(facts):
    return scope_join.host_per_iter(facts, scope_join.host_unblocked_seconds, scale=1e3)
