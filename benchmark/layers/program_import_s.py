"""program_import_s: duration of the setup/import span (lightgbm_tpu/__init__.py: the package's import from its first line to its last, jax's own import inside it unless jax was loaded before), from the span ring; paid once a process.  A program whose ring holds this call's set-up and no such span (older than PR 37) reads a measured 0."""

from benchmark import setup_spans


def read(facts):
    return setup_spans.import_seconds(facts)
