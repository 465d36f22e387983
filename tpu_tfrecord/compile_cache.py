"""Where XLA's persistent compilation cache lives — one owner.

Every entry point that jits (``chip_smoke.py``, ``benchmark/run.py``, the
examples, ``serving.main``) calls :func:`enable` before its first compile.
The directory is placed from OUTSIDE: where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it by itself and this module sets nothing; where it is
not, the cache goes to ``<checkout>/.jax_cache`` — a fixed, git-ignored
path (the path is part of the cache key, so a directory that moves never
hits; no temp name, pid or time ever goes into it). ``tests/conftest.py``
does not call this: tests compile tiny programs and must not depend on
what an earlier run left on disk.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The fixed fallback: ``.jax_cache`` beside the package, i.e. at the root
#: of the checkout (listed in .gitignore).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set the directory is not set in code
    (JAX's own config reads the variable); otherwise
    ``jax_compilation_cache_dir`` becomes :data:`DEFAULT_DIR`. Either way
    the cache key includes the programs' metadata."""
    import jax

    # The programs' scope names (tracing.ANNOTATIONS) are metadata, which
    # JAX leaves out of the cache key by default: an executable cached
    # before a scope was added or moved would come back without it, and a
    # profiler capture of that run would show the old names.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
