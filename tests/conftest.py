"""Test configuration: run JAX on a virtual 8-device CPU mesh.

The env vars must be set before jax is imported.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# CRUSH bulk kernels need exact int64 straw2 draws
jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: minutes-long; deselected by the tier-1 run")
