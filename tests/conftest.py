"""Shared test setup: deterministic seeding, JAX platform config, and the
``slow``/``fast`` markers for the test tiers.

Run with ``PYTHONPATH=src python -m pytest -x -q``; deselect the slow tier
with ``-m "not slow"`` for a faster inner loop, or run the <60 s tier-1
smoke subset with ``-m "fast and not slow"`` (also ``make smoke``).  The
fast tier is curated by module below — parity/property suites can grow in
the default tier without bloating the smoke loop.
"""

from __future__ import annotations

import os
import random

# Platform setup must happen before jax initializes a backend: this repo's
# CI container is CPU-only, and the kernels run with interpret=True there.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")

import jax
import numpy as np
import pytest

# All oracles/kernels are specified at f32 accumulation; keep x64 off so a
# user-level JAX_ENABLE_X64 cannot silently change parity tolerances.
jax.config.update("jax_enable_x64", False)

SLOW_MODULES = {
    # subprocess multi-device simulations + full training loops
    "test_dist.py",
    "test_pipeline.py",
    "test_system.py",
    "test_fault_tolerance.py",
}

FAST_MODULES = {
    # the <60 s tier-1 smoke set: core semantics, golden regressions (incl.
    # the fused-kernel tiling/time-major invariance checks), roofline.
    # Full per-stage kernel parity (test_kernels, test_fused_macro*) lives
    # in the default tier — it's worth real minutes, not smoke seconds.
    # test_ima_noise.py curates its own smoke subset with explicit
    # ``@pytest.mark.fast`` markers (one noisy-parity shape, seeded
    # determinism, the Fig. 7a moments golden) so the tier stays <60 s;
    # its wide moment sweep is marked ``slow``.
    "test_core.py",
    "test_golden_regression.py",
    "test_roofline.py",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        base = os.path.basename(str(item.fspath))
        if base in SLOW_MODULES:
            item.add_marker(pytest.mark.slow)
        if base in FAST_MODULES:
            item.add_marker(pytest.mark.fast)


@pytest.fixture(autouse=True)
def _deterministic_seeds():
    """Seed the non-JAX RNGs per test (JAX keys are explicit everywhere)."""
    random.seed(0)
    np.random.seed(0)
    yield


@pytest.fixture
def rng():
    """Canonical per-test PRNG key."""
    return jax.random.PRNGKey(0)
