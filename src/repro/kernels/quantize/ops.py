"""Public jit'd wrappers: padding, dispatch (Pallas on TPU / ref elsewhere).

Same contract as the other kernel subpackages: callers pass 1-D vectors and
a scalar scale; the (n, 1)/(1, 1) carriage and block padding stay internal.
Padding rows carry x = 0 and noise = 0, which quantize to exactly 0 — no-ops
in the integer psum downstream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel, ref


def _use_pallas(force: bool | None) -> bool:
    if force is not None:
        return force
    return jax.default_backend() == "tpu"


def _pad1(x: jax.Array, block_n: int) -> jax.Array:
    pad = (-x.shape[0]) % block_n
    return jnp.pad(x, (0, pad)) if pad else x


@functools.partial(
    jax.jit, static_argnames=("budget", "block_n", "use_pallas", "interpret")
)
def quantize(
    x: jax.Array,
    noise: jax.Array,
    scale: jax.Array,
    *,
    budget: int,
    block_n: int = 256,
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Stochastic-round x:(n,) f32 -> (n,) int8 under the shared ``scale``.

    ``noise``:(n,) uniform [0, 1) draws; ``scale`` a nonnegative scalar;
    ``budget`` the per-worker integer capacity (see kernel.py).
    """
    n = x.shape[0]
    scale = jnp.asarray(scale, jnp.float32)
    if not _use_pallas(use_pallas) and not interpret:
        return ref.quantize(x, noise, scale, budget)
    xp = _pad1(x.astype(jnp.float32), block_n).reshape(-1, 1)
    np_ = _pad1(noise.astype(jnp.float32), block_n).reshape(-1, 1)
    out = kernel.quantize(
        xp, np_, ref.inverse_step(scale, budget).reshape(1, 1),
        budget=budget, block_n=block_n, interpret=interpret,
    )
    return out[:n, 0]


@functools.partial(
    jax.jit, static_argnames=("budget", "block_n", "use_pallas", "interpret")
)
def dequantize(
    q: jax.Array,
    scale: jax.Array,
    *,
    budget: int,
    block_n: int = 256,
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Summed integers q:(n,) -> (n,) f32 under the shared ``scale``."""
    n = q.shape[0]
    scale = jnp.asarray(scale, jnp.float32)
    if not _use_pallas(use_pallas) and not interpret:
        return ref.dequantize(q, scale, budget)
    qp = _pad1(q, block_n).reshape(-1, 1)
    out = kernel.dequantize(
        qp, ref.step(scale, budget).reshape(1, 1),
        budget=budget, block_n=block_n, interpret=interpret,
    )
    return out[:n, 0]
