"""JAX's persistent compilation cache, placed from outside the program.

Entry points call ``enable_compile_cache()`` at the start of ``main``
(never at import). A cold process on a fresh machine then loads the
executables an earlier process of the same checkout compiled, instead of
compiling the epoch program again.

Placement rule: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this module sets no path; otherwise the cache lives at the
fixed ``<repo>/.jax_cache``. The path is part of what makes a later run
hit, so it never depends on a temp name, a PID or the time.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the repository root (``src/repro/`` is two levels below it)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def compile_cache_dir() -> str:
    """The directory the persistent cache uses: the environment's, else
    ``<repo>/.jax_cache``."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on at ``compile_cache_dir()`` and return
    that path."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
