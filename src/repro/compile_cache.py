"""JAX persistent compilation cache at a fixed, per-checkout location.

Entry points (``chip_smoke.py``, ``benchmarks.run``,
``benchmarks.quorum_sweep``, ``python -m repro.planner``) call ``enable()``
before their first compile, so a second run in the same checkout reads the
sweep, stream and planner compiles back instead of rebuilding them.  The
library itself never calls it: importing ``repro`` (and so the test suite)
stays cache-free.

``JAX_COMPILATION_CACHE_DIR``, when set, is honored as JAX reads it and no
other directory is configured.  Otherwise the cache lives at
``<checkout>/.jax_cache`` — a fixed path, since the directory is part of
what makes an entry findable again.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on and return its directory.

    Entries are keyed on the programs' debug metadata too (their
    ``jax.named_scope`` names and source locations).  JAX leaves it out of
    the key by default, and would then hand a program whose scopes changed
    the executable compiled from its predecessor, whose profiler trace
    carries the old names."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
