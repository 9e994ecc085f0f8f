"""Where XLA's persistent compilation cache lives — the one place that
decides.

The serving path pads rows to power-of-two ``B`` buckets and widths to
``K`` rungs (core/sparse.py ``_width_bucket``) across several train and
query plans, so a cold server compiles dozens of small programs;
a machine that keeps one directory between runs keeps all of them.

Rule: if ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
this module sets no directory. Otherwise the cache is ``.jax_cache/`` in
the checkout (git-ignored) — a fixed path, because the path is part of
what a later process must find again. Every entry point that will
initialise a backend calls :func:`configure` first; nothing else sets a
cache directory.

A process pinned to the CPU (``JAX_PLATFORMS=cpu``: the tests, the load
generators, a rehearsal) gets no directory from here: compile time is the
chip's problem, and XLA:CPU's loader logs a page of machine-feature
warnings for every program it reads back.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def configure() -> str:
    """Enable the persistent cache for this process (call before the
    first compile) and return the directory in use."""
    import jax

    if not os.environ.get(ENV):
        if (jax.config.jax_platforms or "").lower() == "cpu":
            return ""
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # jax skips programs that compiled in under a second; the serving
    # path's programs are mostly that small, and there are many of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir or ""
