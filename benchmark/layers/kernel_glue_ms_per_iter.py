"""kernel_glue_ms_per_iter: non-kernel operations of the grow programs inside the kernels' scopes (partition, histogram*, root_histogram, fused_grow_step)."""

from benchmark import scope_join


def read(facts):
    return scope_join.grow_ms_per_iter(facts, "kernel_glue")
