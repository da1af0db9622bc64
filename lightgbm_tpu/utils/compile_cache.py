"""Where JAX's persistent compilation cache lives.

One policy for every entry point that compiles a lot (chip_smoke.py,
the CLI, tests/conftest.py): the machine decides, the program
follows.  Nothing else in the package sets a cache directory.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Make sure JAX has a persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it, and
    this sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``
    (git-ignored) — a FIXED path, because the directory is part of what makes
    one run's entries findable by the next.
    """
    placed = os.environ.get(_ENV)
    if placed:
        return placed
    import jax

    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
