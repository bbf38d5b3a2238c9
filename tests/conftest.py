"""Test harness: force an 8-virtual-device CPU mesh before JAX initializes.

This is the multi-device test capability the reference lacks (SURVEY.md section 4):
sharding/collective behavior is validated on a faked 8-device host mesh, no TPUs
required.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

# Entry points place a persistent compile cache (vitax/platform.py); this
# process stays off it, as it always was: serializing executables after a few
# hundred in-process tests has crashed the interpreter before. The cache's own
# tests run the real CLIs in subprocesses.
jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: subprocess entry-point smoke tests (~30s each)")


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs
