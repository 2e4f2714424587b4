"""Pure-jnp oracles for the power-method matvec kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# The f32 maths: a TPU's default precision would round f32 operands to bf16.
_HI = jax.lax.Precision.HIGHEST


def matvec(a: jax.Array, v: jax.Array) -> jax.Array:
    """A @ v with f32 accumulation: v (m,) -> (n,), or a block (m, k) ->
    (n, k). Unreshaped, so a ``vmap`` of the vector product is the block
    product."""
    return jnp.dot(a, v, precision=_HI, preferred_element_type=jnp.float32)


def rmatvec(a: jax.Array, u: jax.Array) -> jax.Array:
    return jnp.dot(a.T, u, precision=_HI, preferred_element_type=jnp.float32)


def power_iter_step(x: jax.Array, r: jax.Array, v: jax.Array):
    """One two-sided power iteration on the implicit MTLS gradient A = X^T R:
    returns (u, v') unit-normalized. Oracle for ops.power_iter_step."""
    t = matvec(r, v)  # (n,1)
    u = rmatvec(x, t)  # (d,1)
    u = u / (jnp.linalg.norm(u) + 1e-30)
    s = matvec(x, u)  # (n,1)
    v2 = rmatvec(r, s)  # (m,1)
    v2 = v2 / (jnp.linalg.norm(v2) + 1e-30)
    return u, v2
