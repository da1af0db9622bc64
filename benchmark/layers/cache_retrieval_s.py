"""cache_retrieval_s: seconds the persistent compilation cache took to hand back executables before the window opened: the sum of cache_retrieval_s over the compile/* spans of the set-up (jax.monitoring's cache_retrieval_time_sec).  On a warm run it is what compile_s mostly is; on a cold run it is near 0 and compile_s is compilation.  A program without compile/* spans (older than PR 37) reads a measured 0."""

from benchmark import setup_spans


def read(facts):
    return setup_spans.compile_seconds(facts, "cache_retrieval_s")
