"""Persistent XLA compilation cache for the entry points.

Every entry point (``chip_smoke.py``, ``examples/*``, ``benchmarks/run.py``)
calls ``enable()`` before its first compile; importing ``repro`` never
touches the cache. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
keeps its cache there and nothing here names another directory. Otherwise
the cache lives at ``<repo>/.jax_cache``: a fixed path, because a cache
whose directory moves between runs never hits.
"""
from __future__ import annotations

import contextlib
import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on for this process; returns its
    directory. Every program is cached, however short its compile: the
    index compiles many small variants (one per op, shape and strategy)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir


@contextlib.contextmanager
def disabled():
    """Compile with the persistent cache off inside the block (cold
    compiles, or compiles for a described chip that no run can load)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
