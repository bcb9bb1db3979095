# NOTE: no XLA_FLAGS here — tests run on the single real CPU device.
# Multi-device integration tests spawn subprocesses that use
# `fake_device_env` below to set --xla_force_host_platform_device_count
# BEFORE importing jax (the flag must be set pre-import, and mutating it
# in-process would leak 8 fake devices into every other test).
import os
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
sys.path.insert(0, SRC)

try:
    # Hypothesis profiles for the property suite (tests/test_hypothesis.py).
    # deadline=None: jit compilation makes first-example timing meaningless.
    # CI runs derandomized (db-less, reproducible across the matrix) via
    # HYPOTHESIS_PROFILE=ci; the default profile keeps local shrinking.
    from hypothesis import settings

    settings.register_profile("default", deadline=None, max_examples=50)
    settings.register_profile(
        "ci", deadline=None, max_examples=50, derandomize=True,
        database=None, print_blob=True,
    )
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "default"))
except ImportError:  # hypothesis is optional: its tests importorskip
    pass


def fake_device_env(num_devices: int = 8) -> dict:
    """Environment for a subprocess that should see `num_devices` fake CPU
    devices: held to the CPU backend (the test process may hold the
    accelerator, which a second process cannot open), XLA_FLAGS set before
    jax import, PYTHONPATH pointing at src AND at this directory (the
    scripts import the shared `hlo_guard` collective classifier)."""
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep + tests_dir,
               JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={num_devices}"
    return env


@pytest.fixture()
def rng():
    """Per-test numpy generator, freshly seeded EVERY test. A session-scoped
    mutable generator (the previous shape of this fixture) hands each
    consumer whatever draws the tests before it left behind — values then
    depend on execution order, which breaks under pytest-randomly's
    shuffling. Function scope makes every test's draws order-independent."""
    return np.random.default_rng(0)
