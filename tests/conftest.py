"""Test config: force CPU with 8 virtual devices so multi-chip sharding
tests run anywhere (the driver separately dry-runs the real-TPU path) and
f64 gradient checks work (TPU has no f64)."""

import os

# Belt: env vars (effective if jax is not yet imported).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# Braces: some pytest plugins import jax before conftest runs, in which case
# only a config update before backend initialization still works.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    assert jax.devices()[0].platform == "cpu", jax.devices()
    assert len(jax.devices()) == 8, jax.devices()


@pytest.fixture
def rng():
    return np.random.default_rng(12345)
