"""Persistent on-disk XLA compilation cache (serve/train startup).

Every process start pays a fresh XLA compile for executables this
checkout has already built unless they persist on disk.
``compile_time_ms{site}`` measures that cost; this module removes it
for every start after the first: a second cold start of the same model
records a visibly lower ``compile_time_ms`` (the jit still traces, but
the XLA compile is a disk hit), and a hot-reload canary of an
already-seen model shape costs milliseconds.

Where the cache lives, most specific first:

1. ``$JAX_COMPILATION_CACHE_DIR`` — placed from outside.  JAX reads the
   variable itself; this module then sets no directory in code, and
   neither of the repo's own knobs may override it.
2. ``--compile-cache-dir DIR`` on the ``serve`` and train CLIs.
3. ``$ZNICZ_COMPILE_CACHE``, for deployments that cannot touch the
   launch command.
4. :func:`default_dir` — ``<checkout>/.cache/xla``, always the same
   path: the directory is part of the cache key, so one derived from a
   temporary name, a pid or the time would never hit.

The min-compile-time / min-entry-size floors are zeroed: JAX's
defaults skip persisting sub-second compiles, which is every compile
on the CPU hosts tier-1 runs on — a cache that only works on TPU could
not be tested here.

An unwritable directory logs a warning and the process runs uncached.
"""

from __future__ import annotations

import logging
import os

_log = logging.getLogger("znicz.compilecache")

#: the deployment-side channel (same pattern as $ZNICZ_PROFILE_DIR)
ENV_VAR = "ZNICZ_COMPILE_CACHE"

#: JAX's own variable: when set, the cache is placed from outside
JAX_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the directory enable() actually activated (introspection/tests)
_active_dir: str | None = None


def dir_from_env() -> str | None:
    return os.environ.get(ENV_VAR) or None


def default_dir() -> str:
    """The fixed in-checkout cache directory (``.cache/`` is ignored
    by git)."""
    from .telemetry import buildinfo
    return os.path.join(buildinfo.repo_root(), ".cache", "xla")


def resolve_dir(cache_dir: str | None = None) -> str:
    """The directory the cache lives in, by the module docstring's
    precedence (``cache_dir`` is the ``--compile-cache-dir`` value)."""
    return (os.environ.get(JAX_ENV_VAR)
            or (os.fspath(cache_dir) if cache_dir is not None
                else dir_from_env() or default_dir()))


def active_dir() -> str | None:
    """The cache directory this process persists compiles into, or
    None when running uncached (surfaced on /statusz)."""
    return _active_dir


def enable(cache_dir: str | None = None) -> str | None:
    """Activate the persistent cache; returns the directory in use, or
    None when it could not be created (the process then runs
    uncached).  See the module docstring for where the directory comes
    from."""
    global _active_dir
    import jax
    # zero the persistence floors FIRST: with the default 1 s floor
    # every sub-second CPU compile would silently stay uncached and the
    # second-start speedup this exists for would never materialize
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    path = resolve_dir(cache_dir)
    if os.environ.get(JAX_ENV_VAR):
        # placed from outside: JAX reads the variable itself
        _active_dir = path
        return path
    try:
        os.makedirs(path, exist_ok=True)
    except OSError as e:
        _log.warning("persistent compile cache unavailable at %s (%s); "
                     "running uncached", path, e)
        return None
    jax.config.update("jax_compilation_cache_dir", path)
    _active_dir = path
    _log.info("persistent XLA compile cache at %s", path)
    return path
