"""Placement of JAX's persistent compilation cache for the repo's own
programs (``chip_smoke.py``, ``benchmark/run.py``).

A chip call starts cold, and compiling is most of a cold run. The cache's
directory is part of its key, so it has to be a place that does not move:
whoever runs the program names it with ``JAX_COMPILATION_CACHE_DIR`` (JAX
reads that variable itself); otherwise it is ``.jax_cache`` at the root of
the checkout, never a temporary, per-process or per-run path.
"""

from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compile_cache() -> str:
    """Call before the first compile; returns the directory in effect.

    With ``JAX_COMPILATION_CACHE_DIR`` set, no directory is set in code.
    The write thresholds are lowered either way: the eager path runs
    through dozens of programs that each compile in under JAX's default
    one-second floor, and together they are a large part of a cold
    start."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
