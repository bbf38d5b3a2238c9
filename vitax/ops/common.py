"""What the kernel families share: the lane width, the VMEM limit a kernel
asks for, whether a kernel runs in interpret mode, the trace context under
which a `jax.jit` around a `pl.pallas_call` is traced once a process, and the
three-bfloat16-term form of a float32 operand."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from vitax.platform import backend_platform

LANES = 128
VMEM_LIMIT = 64 * 1024 * 1024           # of the v5e's 128 MiB
NT = (((1,), (1,)), ((), ()))           # a @ b^T
TN = (((0,), (0,)), ((), ()))           # a^T @ b
f32 = jnp.float32


def interpret() -> bool:
    # run the kernels in Pallas interpret mode off-TPU (tests on CPU).
    # VITAX_FORCE_MOSAIC=1 overrides: emit REAL Mosaic kernels regardless of
    # the host backend — for AOT compiles against TPU topology targets
    # (tools/aot_topology.py), where the host is CPU but the compile target
    # is a TPU and interpret-mode lowering would silently swap the
    # production kernels out of the program being proven.
    if os.environ.get("VITAX_FORCE_MOSAIC"):
        return False
    return backend_platform() != "tpu"


def compiler_params(*dimension_semantics: str):
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def one_trace_context():
    """The abstract mesh the call is traced under, set to itself. JAX traces a
    `custom_vjp`'s rules under an empty abstract mesh where the primal's
    context has none; the two mean the same and key `jax.jit`'s cache of
    traces apart, so that a step would trace a jitted kernel's body twice."""
    return jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh())


def thirds(x):
    """float32 x as three bfloat16 terms, hi + mid + lo = x to 24 bits."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(f32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(f32)).astype(jnp.bfloat16)
