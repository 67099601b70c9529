"""Persistent XLA compilation cache — cold-start pays compile ONCE ever.

A pix2pixHD-scale XLA compile is minute-scale, so every entry point
(``cli.train`` / ``cli.infer`` / ``cli.serve`` ``main``,
``chip_smoke.py``) turns the cache on through
:func:`enable_compilation_cache`. Where it lives is decided by ONE rule
(:func:`resolve_cache_dir`):

- ``JAX_COMPILATION_CACHE_DIR`` set: that directory. jax reads the
  variable itself, so the program sets no directory in code; an explicit
  ``--compilation_cache`` / ``compilation_cache_dir`` that names a
  DIFFERENT directory is an error, not an override.
- not set: the explicit directory if one was given, else the fixed
  :data:`DEFAULT_CACHE_DIR` inside the checkout (git-ignored). The path
  is part of the cache key, so it is never built from ``tempfile``, a
  pid or the clock — a directory that moves never hits.

Library code (the Trainer, the serving engine) enables the cache only
when its config names a directory; tests that want none get none.

Hit/miss visibility: jax.monitoring emits ``/jax/compilation_cache/
cache_hits`` / ``cache_misses`` events; the obs RetraceWatchdog counts them
(``persistent_cache_hits``/``persistent_cache_misses`` registry counters),
so a fleet that silently stopped hitting its cache shows up in metrics.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
from jax.experimental.compilation_cache import compilation_cache as _jax_cc

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: <checkout>/.jax_cache — next to the ``p2p_tpu`` package directory
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_enabled_dir: Optional[str] = None


def resolve_cache_dir(cache_dir: Optional[str] = None) -> str:
    """The directory the cache must use, by the module rule above."""
    env = os.environ.get(ENV_VAR)
    if env:
        env = os.path.abspath(env)
        if cache_dir and os.path.abspath(cache_dir) != env:
            raise ValueError(
                f"compilation cache directory {cache_dir!r} disagrees "
                f"with {ENV_VAR}={env!r} — unset one of them")
        return env
    return os.path.abspath(cache_dir) if cache_dir else DEFAULT_CACHE_DIR


def enable_compilation_cache(cache_dir: Optional[str] = None) -> str:
    """Turn the persistent cache on at :func:`resolve_cache_dir` and drop
    the min-compile-time/min-entry-size gates so every program is
    eligible — the serving buckets include sub-second toy compiles in
    tests, and on TPU the big programs clear any threshold anyway.
    Idempotent; returns the active dir. Call BEFORE the first jit compile
    you want cached."""
    global _enabled_dir
    target = resolve_cache_dir(cache_dir)
    if _enabled_dir == target:
        return target
    os.makedirs(target, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", target)
        # jax builds its cache object once, at the first compile after a
        # directory is known; a later change of directory (tests, a
        # second engine) is ignored until the object is rebuilt
        _jax_cc.reset_cache()
    _enabled_dir = target
    return target


def compilation_cache_dir() -> Optional[str]:
    """The directory enabled via :func:`enable_compilation_cache` (None if
    the cache was never enabled by this process)."""
    return _enabled_dir
