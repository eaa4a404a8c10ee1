"""Where JAX keeps its persistent compilation cache for the entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, places the cache: JAX reads the
variable itself and nothing here overrides it.  Otherwise ``enable`` points
the cache at ``.jax_cache/`` in the checkout — a fixed path, because the
path is part of what the cache matches on, so a directory that moves from
run to run never hits.  ``.gitignore`` lists it.
"""

from __future__ import annotations

import os

import jax

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compile.  Entry points (``chip_smoke.py``, the
    examples, the benchmark harness, the plan tuner) call it; library code
    and the tests never do.
    """
    placed = os.environ.get(ENV_DIR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_DIR)
    return CHECKOUT_DIR
