"""Where the persistent XLA compilation cache lives — decided outside.

A chip call starts with no compiled code and every server program
unrolls its layers, so entry scripts (`chip_smoke.py`, `bench.py`,
`benchmarks/*.py`, `examples/_common.py`) turn the cache on before
their first compile. Never on `import hpx_tpu`: a library does not
pick a directory for its host.

The directory is part of the cache key, so it never carries a
temporary name, a pid or a time: where `JAX_COMPILATION_CACHE_DIR` is
set jax reads it itself and NOTHING is set in code; where it is not,
the cache goes to `<checkout>/.jax_cache` (git-ignored).
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["compile_cache_dir", "enable_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> tuple:
    """``(directory, from_env)`` the cache resolves to."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env, True
    return os.path.join(_CHECKOUT, ".jax_cache"), False


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on and return its directory. Every
    program is kept, however quick its compile: a cold chip call pays
    for the many small ones too. On the CPU platform the default
    directory is left off (returns None): CPU compiles are quick, and
    XLA:CPU reloads a cached executable with a page of machine-feature
    warnings per hit."""
    import jax
    path, from_env = compile_cache_dir()
    if not from_env:
        if jax.default_backend() == "cpu":
            return None
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
