"""The one rule for JAX's persistent compilation cache.

If ``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and no
code of this repo sets another path. Otherwise it lives at the fixed
``<checkout>/.jax_cache`` (gitignored): the path is part of what makes a
later process find the entries, so it must not move between runs.

:func:`apply` runs at package import (``madsim_tpu/__init__.py``, loaded
by file path so it fires before any program compiles: jax latches the
cache at its first compile). When jax is not imported yet it only sets
environment variables, which jax reads at its own import, so the
host-only import path stays jax-free and every child process — spawned
fleet workers among them — inherits the same directory.
Thresholds are zeroed so every program is cached: this codebase's
programs are few, large, and identical across processes.

Correctness-neutral: the cache key covers the program and the backend
configuration (``tests/test_compile_cache.py`` asserts cached-vs-fresh
bitwise equality end to end). ``analysis/budgets.py`` turns the cache
off around its own fresh compiles.
"""
from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_THRESHOLDS = {"jax_persistent_cache_min_compile_time_secs": 0.0,
               "jax_persistent_cache_min_entry_size_bytes": -1}


def cache_dir() -> str:
    """The directory the rule picks."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def apply() -> str:
    """Point JAX's persistent cache at :func:`cache_dir`; returns it."""
    path = cache_dir()
    if "jax" in sys.modules:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
        for name, value in _THRESHOLDS.items():
            jax.config.update(name, value)
    else:
        os.environ[ENV_VAR] = path
        for name, value in _THRESHOLDS.items():
            os.environ.setdefault(name.upper(), str(value))
    return path
