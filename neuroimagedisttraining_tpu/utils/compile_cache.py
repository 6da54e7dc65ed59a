"""Persistent XLA compilation cache: one rule, placed from outside.

The flagship 3D-CNN round program takes tens of seconds to compile; with
the persistent cache the compile is paid once per machine, not once per
process. Both CLIs, ``bench.py``, ``chip_smoke.py`` and the tests call
:func:`enable_compile_cache`, and this is the only place in non-test
code that may set ``jax_compilation_cache_dir``:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  the code sets nothing — whoever launched the process owns the
  location (a chip machine's own cache directory, a CI volume).
- not set: ``<checkout>/.jax_cache``, resolved from this package's
  ``__file__`` (git-ignored). The path is part of the cache key, so it
  never depends on ``/tmp``, a pid, a time or a ``tempfile`` name: two
  fresh processes of one checkout always share it.

``JAX_ENABLE_COMPILATION_CACHE=0`` (JAX's own switch) turns it off.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout default: three levels up from utils/compile_cache.py
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return the directory
    in effect. Call BEFORE the first compilation — programs compiled
    earlier in the process are not retroactively cached."""
    import jax

    # cache everything that took meaningfully long to build; the 0.2 s
    # floor skips trivial op-by-op executables whose disk round-trip
    # costs more than recompiling
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
