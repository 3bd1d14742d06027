"""JAX's persistent compilation cache: one placement rule for every
entry point (``chip_smoke.py``, ``bench.py`` and ``cli.main``).

A cold process pays every XLA compile again: the solver, the filter pass
and the validator at each warmup bucket. With the cache on, a second run
on the same machine reads them back. Never enabled on import and never by
the test suite: only an entry point decides where its compiles go.
"""

from __future__ import annotations

import os

#: the fixed default: ``<checkout>/.jax_cache`` (listed in .gitignore).
#: Never derived from a temp name, a pid or the time — a directory that
#: moves never hits.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is
    JAX's own setting and wins — no other path is set in code; otherwise
    :data:`DEFAULT_DIR`. Every program is cached, the small per-bucket
    warmup programs included (minimum compile time 0). Call before the
    first compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
