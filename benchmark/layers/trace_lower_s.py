"""trace_lower_s: seconds jax spent tracing functions to jaxprs and lowering them to MLIR before the window opened: the sum of trace_s + lower_s over the compile/* spans of the set-up (lightgbm_tpu/obs/jit.py, from jax.monitoring's jaxpr_trace_duration and jaxpr_to_mlir_module_duration), the part of a first call that no compilation cache saves.  A program without compile/* spans (older than PR 37) reads a measured 0."""

from benchmark import setup_spans


def read(facts):
    return setup_spans.compile_seconds(facts, "trace_s", "lower_s")
