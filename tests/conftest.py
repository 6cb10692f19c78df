"""Test env: CPU backend with 8 virtual devices (multi-chip sharding tests
run on a virtual mesh, per the driver's dryrun contract).  Both are set
through the environment, before jax is imported.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: deselected from the tier-1 run (-m 'not slow')")


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu
    paddle_tpu.seed(42)
    yield
