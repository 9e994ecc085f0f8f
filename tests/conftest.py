"""Test bootstrap: force an 8-device virtual CPU mesh before jax imports.

Mirrors the reference's test strategy (SURVEY.md §4): distributed logic is
tested without a cluster — here, multi-chip sharding/collectives run on
virtual CPU devices via --xla_force_host_platform_device_count.
"""

import os

# the tests are CPU tests, here and wherever they run; subprocesses they
# spawn (servers, jubavisor children) inherit the variable
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: process-level integration tests (forked servers)"
    )


@pytest.fixture
def rng():
    return np.random.default_rng(0)
