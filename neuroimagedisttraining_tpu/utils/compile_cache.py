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

The key holds the program's debug metadata (``op_name`` paths, source
lines). JAX strips them by default, and two programs that differ only in
``jax.named_scope`` names then share one entry: the process that comes
second is handed the first one's executable, whose ops a profiler trace
names by the OLD scopes (measured: a scopes-only change got 68 hits of 68
on its parent's cache and ran without one of its scopes; PERF.md, PR 23).
The trace is how this repo finds its time, so the names must be the
running code's. The price: an edit that moves lines in a traced file
recompiles the programs traced through it.
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
    # scopes are part of the program (module docstring)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
