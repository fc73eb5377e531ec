"""Persistent XLA compilation cache.

The full-width train step takes minutes to compile cold; the persistent
cache brings a repeat compile down to a disk read. Enabled by default
for the CLI; opt out with ``RMD_NO_COMPILE_CACHE=1``.

Where the cache lives, in order:

1. ``JAX_COMPILATION_CACHE_DIR`` — JAX's own variable. When it is set
   the cache was placed from outside (a driver, a fleet image) and this
   module sets no directory in code: ``--compile-cache``,
   ``RMD_COMPILE_CACHE`` and the env yaml's ``compile.cache`` all yield
   to it, and so does the kill switch.
2. ``--compile-cache`` (CLI) > ``RMD_COMPILE_CACHE`` > the env yaml's
   ``compile.cache`` > the repo-local ``.jax_cache`` default.

The effective directory is published in the run's ``boot`` telemetry
event, and the AOT program store (``compile.aot``) keeps its
``programs/`` directory inside it.

The reference has no equivalent (torch eager needs none); this is the
TPU-native answer to its "start training immediately" property.
"""

import os

from . import env

EXTERNAL_VAR = "JAX_COMPILATION_CACHE_DIR"

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")

# the directory the last enable_persistent_cache() call configured in
# code (None: disabled, never enabled, or placed from outside)
_configured = None


def external_dir():
    """The cache directory placed from outside the program, or None."""
    return os.environ.get(EXTERNAL_VAR) or None


def effective_dir():
    """The cache directory JAX uses, or None when the cache is off."""
    return external_dir() or _configured


def enable_persistent_cache(path: str | None = None) -> str | None:
    """Point jax at an on-disk compilation cache; returns the dir or None.

    Must run before the first compile. A directory that cannot be
    created raises: a run that was asked to cache and silently goes on
    cold costs minutes per boot and hides why.
    """
    global _configured
    import jax

    external = external_dir()
    if external is None and env.get_bool("RMD_NO_COMPILE_CACHE"):
        _configured = None
        return None

    if external is None:
        path = path or env.raw("RMD_COMPILE_CACHE") or DEFAULT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        _configured = path
    # cache everything: even small entries add up across the zoo
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return effective_dir()
