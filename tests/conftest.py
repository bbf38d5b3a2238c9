"""Test harness: force an 8-virtual-device CPU mesh before JAX initializes.

This is the multi-device test capability the reference lacks (SURVEY.md section 4):
sharding/collective behavior is validated on a faked 8-device host mesh, no TPUs
required.
"""

import os
import signal

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


# A hang costs one case and not the run. Sized against the two whole-cell
# compiles of tests/test_aot_tpu_compile.py (167 s and 70 s beside five busy
# workers, ISSUE 46).
TEST_SECONDS = 300


@pytest.fixture(autouse=True)
def _time_limit(request):
    """Fails the test with its name when it has run TEST_SECONDS (SIGALRM on
    the main thread, where pytest and its xdist workers run tests)."""
    def expired(signum, frame):
        raise TimeoutError(
            f"{request.node.nodeid} ran past its {TEST_SECONDS} s")

    before = signal.signal(signal.SIGALRM, expired)
    signal.alarm(TEST_SECONDS)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, before)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs
