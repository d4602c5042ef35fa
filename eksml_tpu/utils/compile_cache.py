"""Persistent XLA compilation cache.

The reference pays zero compile cost (TF 1.x kernels are precompiled);
on TPU the first jit of the full Mask-RCNN train step is minutes of
XLA work, repeated on every process start.  jax's persistent cache
makes that a one-time cost per (program, topology, cache directory):
the trainer, the benchmark and ``chip_smoke.py`` all reuse the same
serialized executables.

ONE location rule: ``JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (and then nothing else is configured), else a fixed
``.jax_cache/`` in the checkout.  The directory is part of the cache
key, so it never carries a temp, pid or time component.
"""

from __future__ import annotations

import os
import warnings

DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_cache(cache_dir: str | None = None) -> str | None:
    """Point jax at the persistent on-disk compilation cache and return
    the directory in force (None when it cannot be created — a
    read-only checkout costs a warning and cold compiles, not the run).
    ``JAX_COMPILATION_CACHE_DIR`` in the environment wins over the
    argument."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or cache_dir or DEFAULT_DIR)
    try:
        os.makedirs(cache_dir, exist_ok=True)
    except OSError as e:
        warnings.warn(f"persistent compile cache disabled: {e}",
                      stacklevel=2)
        return None
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # if compiles already happened in this process, the cache object
    # latched its (possibly disabled) state — reset so the directory
    # takes effect mid-process
    compilation_cache.reset_cache()
    # cache everything: tiny entries are free, and the expensive ones
    # (train step at 1344 px) are exactly what must not recompile
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
