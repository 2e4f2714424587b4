"""Pure-jnp oracles for the quantize kernels.

Bit-compatible with ``kernel.py`` (the stochastic noise and the scalar
factors are explicit operands, so both paths compute the identical floor),
used by tests and as the off-TPU dispatch target of ``ops.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_EPS = 1e-30


def quantize(
    x: jax.Array, noise: jax.Array, scale: jax.Array, budget: int
) -> jax.Array:
    """clip(floor(x * budget / scale + noise), -budget, budget) as int8.

    With ``noise ~ U[0, 1)`` this is exact stochastic rounding:
    ``E[quantize(x)] = x * budget / scale`` elementwise, and for
    ``|x| <= scale`` the clip never binds (floor of a value in
    [-budget, budget + 1) lands in [-budget, budget]).
    """
    v = jnp.floor(x.astype(jnp.float32) * inverse_step(scale, budget) + noise)
    return jnp.clip(v, -budget, budget).astype(jnp.int8)


def dequantize(q: jax.Array, scale: jax.Array, budget: int) -> jax.Array:
    """q * scale / budget as f32 (q is the *summed* integer vector)."""
    return q.astype(jnp.float32) * step(scale, budget)


# The two scalar factors are computed here, in XLA, for both paths: the
# kernels receive them as operands instead of dividing themselves, so a
# division that rounds differently inside a kernel cannot move a floor.
def inverse_step(scale: jax.Array, budget: int) -> jax.Array:
    """budget / scale: f32 value -> integer units."""
    return budget / (scale + _EPS)


def step(scale: jax.Array, budget: int) -> jax.Array:
    """scale / budget: integer units -> f32 value."""
    return scale / budget
