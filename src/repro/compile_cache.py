"""Persistent XLA compile cache for the entry points.

Every process that serves compiles the same kernels and steps: the pow2
flush buckets, the guard budgets, the fleet train step.  JAX's persistent
compile cache lets the second process, and the next run, load them instead.

The cache directory is part of what a later process must find again, so it
is fixed: ``JAX_COMPILATION_CACHE_DIR`` where it is set (JAX reads it
itself, and nothing here overrides it), and otherwise ``.jax_cache`` at the
root of the checkout.  Never a temporary, per-process or time-derived path.

`enable_compile_cache` is called by entry points (`chip_smoke.py`,
`benchmarks/run.py`, the `examples/` mains) and by federation workers,
because a spawned process does not inherit ``jax.config``.  Importing this
module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on for this process; returns its
    directory.

    The serving kernels compile in well under JAX's default one-second
    threshold, which would leave exactly them uncached, so every compile
    is written.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir
