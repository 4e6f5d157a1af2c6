"""Test env: force CPU with 8 virtual devices BEFORE jax initialises.

Mirrors the reference's PlacementMeshImpl-on-cpu:0 test harness
(/root/reference/tests/backend.py:45-59) but with a real 8-device mesh so
NamedSharding layouts and collectives are exercised (SURVEY.md §4 notes the
reference never tests multi-core behavior; we do).
"""
import os

# jax is not yet imported when this module loads, so in-process env
# mutation suffices
flags = os.environ.get("XLA_FLAGS", "")
os.environ["JAX_PLATFORMS"] = "cpu"
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)
