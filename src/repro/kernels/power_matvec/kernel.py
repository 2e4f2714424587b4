"""Blocked matvec / rmatvec Pallas kernels — the DFW-TRACE power-method hot spot.

The distributed power method on the implicit gradient A = X^T R is a chain of
four streaming matvecs per iteration (t=Rv, u=X^T t, s=Xu, v'=R^T s). Each is
bandwidth-bound (~1 FLOP/byte in bf16), so the kernel goal is exactly one HBM
pass over the matrix per call with MXU-aligned (block_r x block_c) VMEM tiles;
vectors are carried as (len, 1) matrices so the reduction runs on the MXU.

A block of k > 1 probe columns goes through the same one pass over A. Its
products come out transposed, (k, len): the A tile is then the matrix
unit's stationary operand and the k probe rows stream past it, where a
(len, k) product would fill k of the unit's 128 columns (at k = 32 that
made the pass half again as slow on a v5e).

Accumulation is always f32 via ``preferred_element_type`` regardless of the
input dtype (bf16 inputs keep full-precision partial sums); f32 inputs are
multiplied at f32 precision (``..precision``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..precision import mxu_precision


def _matvec_kernel(a_ref, v_ref, o_ref):
    """out[i] += A[i,j] @ v[j]; grid=(rows, cols), cols innermost."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a, v = a_ref[...], v_ref[...]
    o_ref[...] += jnp.dot(
        a, v, precision=mxu_precision(a, v), preferred_element_type=jnp.float32
    )


def _rmatvec_kernel(a_ref, u_ref, o_ref):
    """out[j] += A[i,j]^T @ u[i]; grid=(cols, rows), rows innermost."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a, u = a_ref[...], u_ref[...]
    o_ref[...] += jnp.dot(
        a.T, u, precision=mxu_precision(a, u), preferred_element_type=jnp.float32
    )


def _matmat_kernel(a_ref, vt_ref, o_ref):
    """out[:, i] += v[j]^T @ A[i,j]^T, products as rows; grid=(rows, cols)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a, vt = a_ref[...], vt_ref[...]
    o_ref[...] += jax.lax.dot_general(
        vt, a, (((1,), (1,)), ((), ())),
        precision=mxu_precision(a, vt), preferred_element_type=jnp.float32,
    )


def _rmatmat_kernel(a_ref, u_ref, o_ref):
    """out[:, j] += u[i]^T @ A[i,j], products as rows; grid=(cols, rows)."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a, u = a_ref[...], u_ref[...]
    o_ref[...] += jax.lax.dot_general(
        u, a, (((0,), (0,)), ((), ())),
        precision=mxu_precision(a, u), preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit, static_argnames=("block_r", "block_c", "interpret")
)
def matvec(
    a: jax.Array,
    v: jax.Array,
    *,
    block_r: int = 256,
    block_c: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """A @ v for A:(n,m), v:(m,k) -> (n,k). Dims must divide the block shape
    (ops.py pads). VMEM/step: block_r*block_c*bytes(A) + 2 probe blocks."""
    n, m = a.shape
    k = v.shape[1]
    assert n % block_r == 0 and m % block_c == 0, (a.shape, block_r, block_c)
    if k > 1:
        return pl.pallas_call(
            _matmat_kernel,
            grid=(n // block_r, m // block_c),
            in_specs=[
                pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
                pl.BlockSpec((k, block_c), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((k, block_r), lambda i, j: (0, i)),
            out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
            interpret=interpret,
        )(a, v.T).T
    return pl.pallas_call(
        _matvec_kernel,
        grid=(n // block_r, m // block_c),
        in_specs=[
            pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
            pl.BlockSpec((block_c, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
    )(a, v)


@functools.partial(
    jax.jit, static_argnames=("block_r", "block_c", "interpret")
)
def rmatvec(
    a: jax.Array,
    u: jax.Array,
    *,
    block_r: int = 256,
    block_c: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """A^T @ u for A:(n,m), u:(n,k) -> (m,k)."""
    n, m = a.shape
    k = u.shape[1]
    assert n % block_r == 0 and m % block_c == 0, (a.shape, block_r, block_c)
    if k > 1:
        return pl.pallas_call(
            _rmatmat_kernel,
            grid=(m // block_c, n // block_r),
            in_specs=[
                pl.BlockSpec((block_r, block_c), lambda j, i: (i, j)),
                pl.BlockSpec((block_r, k), lambda j, i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((k, block_c), lambda j, i: (0, j)),
            out_shape=jax.ShapeDtypeStruct((k, m), jnp.float32),
            interpret=interpret,
        )(a, u).T
    return pl.pallas_call(
        _rmatvec_kernel,
        grid=(m // block_c, n // block_r),
        in_specs=[
            pl.BlockSpec((block_r, block_c), lambda j, i: (i, j)),
            pl.BlockSpec((block_r, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_c, 1), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
        interpret=interpret,
    )(a, u)
