"""Persistent XLA compilation cache, placed from outside.

A cold ``serve`` compiles every registered pipeline (about a minute
for the three flagship models on a v5e); jax's persistent cache keys
serialized executables by HLO + backend, so a second process that
finds the same directory pays only deserialization. Every entry point
that compiles pipelines (``serve``, ``detect2d``, ``detect3d``,
``chip_smoke.py``, the ``perf/`` scripts) calls
:func:`enable_persistent_cache` before its first compile.

Where the cache lives is decided outside the program:

  * ``JAX_COMPILATION_CACHE_DIR`` set — jax reads the variable itself;
    this module sets no path in code, it only lowers the thresholds so
    sub-second compiles are cached too.
  * unset — the fixed ``<checkout>/.jax_cache`` (git-ignored). The
    path is part of the cache key's neighbourhood: a directory named
    after a pid, a time or a temp dir would never hit.
  * CPU selected (``JAX_PLATFORMS=cpu``, or ``jax_platforms`` set in
    code) and no variable — no cache: compiles are seconds there and
    XLA:CPU AOT reloading warns about machine-feature flags.

Reference analogue: Triton caches TensorRT engines next to the model
repository for the same reason (first-load autotuning is minutes).
"""

from __future__ import annotations

import os
import pathlib

import jax

_DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"
_ENV = "JAX_COMPILATION_CACHE_DIR"


def _selected_platform() -> str:
    """The first platform jax was told to use, read WITHOUT
    initializing the backend (default_backend() would commit the
    choice and break callers that select cpu after this returns)."""
    selected = (
        getattr(jax.config, "jax_platforms", None)
        or os.environ.get("JAX_PLATFORMS")
        or ""
    )
    return selected.split(",")[0]


def enable_persistent_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory (``""`` when it stays off — see the module docstring).
    Safe to call more than once, before or after backend init."""
    path = os.environ.get(_ENV, "")
    if not path:
        if _selected_platform() == "cpu":
            return ""
        path = str(_DEFAULT_DIR)
        pathlib.Path(path).mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # default thresholds skip sub-second / small entries; a cold start
    # is made of many of those, so cache everything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
