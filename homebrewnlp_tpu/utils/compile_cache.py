"""Persistent XLA compilation cache placement.

A flagship process pays ~100 s of train-step compile (and the serving
engine several chunk programs) before its first useful step; jax's
persistent compilation cache serves an unchanged program from disk on the
next launch (resume after preemption, serving relaunch, the legs of
``chip_smoke.py``).  ``main.py`` installs it for every run mode, before the
first jit compile.

Placement is decided from OUTSIDE the program:

* ``JAX_COMPILATION_CACHE_DIR`` set — jax reads the variable itself; this
  module sets no directory in code, so whoever launched the process (a
  measurement harness, a deployment) owns where the cache lives and
  whether it survives the machine.
* unset — one fixed, git-ignored directory inside the checkout
  (:data:`DEFAULT_DIR`).  Fixed because the path is part of what makes a
  later process find the entries; inside the checkout so a relaunch from
  the same tree is warm with no configuration.

The two threshold knobs are forced permissive either way: jax's defaults
only persist compiles slower than ~1s / larger than a floor, which silently
skips exactly the many-small-programs profile of the stepped decode path.
"""
from __future__ import annotations

import os

#: the variable jax itself reads for ``jax_compilation_cache_dir``
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: where the cache lives when :data:`ENV_VAR` is unset (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def install_compile_cache() -> str:
    """Turn the persistent cache on for this process and return its
    directory (the environment's when :data:`ENV_VAR` is set, else
    :data:`DEFAULT_DIR`).  Idempotent; call before the first jit compile."""
    import jax
    from ..telemetry import install_compile_listener
    # the compile counters ride along: every run mode calls this before its
    # first jit, which is when the listener has to be there
    install_compile_listener()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    os.makedirs(DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # jax initialises its cache object lazily on the first compile and
    # never re-reads the config after: without the reset, a jit that ran
    # earlier in the process would leave the directory silently unused
    _reset_cache_object()
    return DEFAULT_DIR


def _reset_cache_object() -> None:
    from jax._src import compilation_cache as _cc
    _cc.reset_cache()


def uninstall_compile_cache() -> None:
    """Turn the persistent cache back off (test isolation)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", None)
    _reset_cache_object()
