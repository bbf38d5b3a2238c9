"""Process-level platform glue: compile-cache placement and backend queries.

Backend selection is JAX's own: `JAX_PLATFORMS=cpu` in the environment pins
the process to the host CPU (the test suite, CPU rehearsals); with the
variable unset JAX takes the TPU where it finds one and otherwise falls back
to the CPU without a word — so the entry points that measure
(chip_smoke.py, benchmark/run.py) check the platform themselves. Nothing
here re-pins.
"""

import os

import jax

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, inside the checkout, gitignored: the directory is part of the cache
# key, so one made from a temp name, pid or time would never hit
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    Called first thing by every entry point that compiles (trainer, server,
    chip_smoke.py, the benchmark, the tools), so restarts and the processes of one
    chip call share compiled programs. Where JAX_COMPILATION_CACHE_DIR is set
    the cache is placed from outside and nothing is set in code (JAX reads
    the variable itself); otherwise the fixed in-checkout directory is used.
    """
    from_env = os.environ.get(COMPILE_CACHE_ENV, "")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR


def backend_platform() -> str:
    """Platform name ("cpu"/"tpu"/"gpu") of the default backend.

    The sanctioned single query point: library code should call this (or
    `device_kind()`) instead of `jax.devices()[0].platform`, so that backend
    selection stays a process-level decision made here.
    """
    return jax.devices()[0].platform  # vtx: ignore[VTX104] sanctioned single query point


def device_kind() -> str:
    """Hardware kind of the default backend's first device (e.g. "TPU v4")."""
    return jax.devices()[0].device_kind  # vtx: ignore[VTX104] sanctioned single query point
