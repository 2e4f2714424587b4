"""Fused stochastic-rounding quantize / dequantize Pallas kernels.

Hot pair of the compressed power-method collectives (``repro/comm``): before
an integer psum every worker turns its local f32 contribution into int8 under
a shared per-vector scale, and turns the summed integers back into f32 after.
The fusion target is one VMEM pass per call — scale, stochastic round, clip
and cast happen on the block in registers instead of four XLA HLOs with HBM
round-trips between them.

Stochastic rounding is ``floor(x * budget / scale + noise)`` with uniform
``noise`` in [0, 1): exactly unbiased (``E[q] = x * budget / scale``). The
noise is an explicit input (host-side ``jax.random.uniform``) rather than an
in-kernel ``pltpu.prng_random_bits`` call so the kernel is deterministic
given its operands. The scalar factors budget/scale and scale/budget are
operands too, computed in XLA by ``ref.py``'s helpers for both paths, so the
kernel holds only a multiply, an add and a floor, each exactly rounded: it
agrees with the jnp reference bit for bit, in interpret mode and on TPU.

``budget`` is the per-worker integer capacity: with N workers summing into
int8 the shared scale maps each contribution into [-budget, budget] with
``budget = 127 // N``, so any partial sum of the all-reduce is bounded by
``N * budget <= 127`` and the s8 wire dtype can never overflow.

Vectors are carried as (n, 1) matrices like the other kernels in this repo;
the scalar factor rides along as a (1, 1) block re-fetched at every grid
step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quantize_kernel(x_ref, noise_ref, inv_ref, o_ref, *, budget):
    """o = clip(floor(x * inv + noise), -budget, budget) as int8."""
    v = jnp.floor(x_ref[...].astype(jnp.float32) * inv_ref[0, 0] + noise_ref[...])
    o_ref[...] = jnp.clip(v, -budget, budget).astype(jnp.int8)


def _dequantize_kernel(q_ref, step_ref, o_ref):
    """o = q * step as f32."""
    o_ref[...] = q_ref[...].astype(jnp.float32) * step_ref[0, 0]


@functools.partial(jax.jit, static_argnames=("budget", "block_n", "interpret"))
def quantize(
    x: jax.Array,
    noise: jax.Array,
    inv: jax.Array,
    *,
    budget: int,
    block_n: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Stochastic-round x:(n,1) f32 to int8; ``inv``:(1,1) is budget/scale.

    ``n`` must divide ``block_n`` (ops.py pads; zero rows quantize to 0).
    VMEM/step: two f32 blocks + the int8 output block.
    """
    n = x.shape[0]
    assert n % block_n == 0, (n, block_n)
    assert 1 <= budget <= 127, budget
    return pl.pallas_call(
        functools.partial(_quantize_kernel, budget=budget),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int8),
        interpret=interpret,
    )(x, noise, inv)


@functools.partial(jax.jit, static_argnames=("budget", "block_n", "interpret"))
def dequantize(
    q: jax.Array,
    step: jax.Array,
    *,
    budget: int,
    block_n: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Map summed integers q:(n,1) back to f32; ``step``:(1,1) is scale/budget."""
    n = q.shape[0]
    assert n % block_n == 0, (n, block_n)
    assert 1 <= budget <= 127, budget
    return pl.pallas_call(
        _dequantize_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
    )(q, step)
