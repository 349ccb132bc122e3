"""Test configuration: run on CPU with 8 virtual devices.

Must set env vars BEFORE jax is imported anywhere (SURVEY.md test strategy:
distributed semantics are validated on a virtual device mesh the way the
reference validates Spark training in local[N] mode).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "smoke: cheap end-to-end harness checks run on every CI tier")
    config.addinivalue_line(
        "markers",
        "multiprocess: spawns real OS worker processes (jax.distributed "
        "or the elastic supervisor); every such test carries a hard "
        "subprocess timeout/deadline so a hung worker cannot wedge CI")
    config.addinivalue_line(
        "markers",
        "multihost: simulated multi-host jobs — worker processes grouped "
        "into host failure domains on localhost (elastic num_hosts); "
        "implies multiprocess discipline: a hard job_deadline_s / "
        "subprocess timeout is mandatory so a partitioned or hung host "
        "group cannot wedge CI")


@pytest.fixture
def rng():
    return np.random.default_rng(12345)
