"""Persistent compilation cache (utils/compile_cache.py).

The reference pays no compile cost (precompiled TF kernels); on TPU the
train-step compile is minutes of XLA work, so the cache is part of the
operational surface (train.py, __graft_entry__.py, benchmark/ enable it).
"""

import os

import jax
import jax.numpy as jnp

from eksml_tpu.utils.compile_cache import enable_persistent_cache


def test_cache_populates(tmp_path, monkeypatch):
    d = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    assert enable_persistent_cache() == d

    f = jax.jit(lambda x: x @ x.T + 1.0)
    f(jnp.ones((32, 32))).block_until_ready()
    assert os.listdir(d), "no cache entries written"


def test_env_var_wins_over_argument(tmp_path, monkeypatch):
    d = str(tmp_path / "env-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    assert enable_persistent_cache(str(tmp_path / "arg-cache")) == d


# ---- placement: where the cache lives is part of its key -------------

_PRINT_DIR = ("from eksml_tpu.utils.compile_cache import "
              "enable_persistent_cache; import jax; "
              "d = enable_persistent_cache(); "
              "assert jax.config.jax_compilation_cache_dir == d; "
              "print(d)")


def _cache_dir_of_fresh_process(env_dir=None):
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", _PRINT_DIR], cwd=root,
                         env=env, capture_output=True, text=True,
                         timeout=120, check=True)
    return out.stdout.strip().splitlines()[-1]


def test_env_dir_is_the_only_location_configured(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, that directory — and not the
    in-checkout default — is what jax is pointed at (the child asserts
    ``jax.config.jax_compilation_cache_dir`` equals what it prints)."""
    from eksml_tpu.utils import compile_cache

    d = str(tmp_path / "env-cache")
    assert _cache_dir_of_fresh_process(d) == d != compile_cache.DEFAULT_DIR
    assert os.path.isdir(d)


def test_unset_env_gives_the_same_in_checkout_path_in_two_processes():
    from eksml_tpu.utils import compile_cache

    first = _cache_dir_of_fresh_process()
    second = _cache_dir_of_fresh_process()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == second == os.path.join(root, ".jax_cache")
    assert first == compile_cache.DEFAULT_DIR


def test_default_dir_has_no_temp_pid_or_time_component():
    """The directory is part of the cache key: a path that moves never
    hits.  The module must not build it from tempfile/pid/clock."""
    import inspect

    from eksml_tpu.utils import compile_cache

    src = inspect.getsource(compile_cache)
    for needle in ("tempfile", "getpid", "time.", "uuid", "mkdtemp"):
        assert needle not in src, needle
    assert os.path.basename(compile_cache.DEFAULT_DIR) == ".jax_cache"
    assert os.path.dirname(compile_cache.DEFAULT_DIR) == os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
