"""Placement of JAX's persistent compilation cache — one rule, one place.

Every entry point that compiles (``cli.main``, ``chip_smoke.py``, the kept
scripts, ``tests/conftest.py``, the compile-worker pool)
calls :func:`enable_compile_cache` and sets no cache directory of its own.

The directory is part of the cache key's lookup path, so it must not move
between the processes of one command or between two commands:

* ``JAX_COMPILATION_CACHE_DIR`` set in the environment → JAX reads it itself
  at import; it is used verbatim and NOTHING else is set in code (an operator
  or a harness can place the cache from outside).
* unset → ``<checkout>/.jax_cache``, an absolute path derived from this
  package's location, never from the cwd and never a temporary directory.

What a key is made of is set here too. JAX keys a program without its
metadata, so an executable compiled before a ``jax.named_scope`` was added or
moved is served under the new code's key with the old ``op_name``s in it, and
graftscope's scope map (obs/scopes.py), which reads them from the executable,
names the device's work wrongly (PERF.md, PR 24: five such hits left 39 % of a
profiled epoch in no scope). The key therefore takes the metadata, and the
metadata is cut down to the op names: file names and line numbers stay out of
the lowered program, or an edit anywhere in a file would make every program
under it a miss.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """Where this process's persistent compile cache lives (see module doc)."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache(min_compile_secs: float = 0.5) -> str:
    """Turn the persistent cache on at :func:`compile_cache_dir` and return
    that directory. ``min_compile_secs``: programs that compiled faster are
    not persisted (0 keeps everything — the compile-worker channel needs
    that; the default keeps the directory free of trivial entries)."""
    import jax

    cache_dir = compile_cache_dir()
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", float(min_compile_secs)
    )
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    return cache_dir
