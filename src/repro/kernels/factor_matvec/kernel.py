"""Fused factor-form scoring Pallas kernel — the serving-path hot spot.

A DFW-Trace iterate never exists as a dense d x m matrix: it is the factor
triple ``(A, s, B)`` with ``A: (r, n_in)``, ``B: (r, n_out)`` and the scored
product ``Y = ((X @ A^T) * s) @ B`` for a request batch ``X: (b, n_in)``.
Serving cost is O(b * r * (n_in + n_out)) instead of the dense matmul's
O(b * n_in * n_out) — the whole point of keeping iterates factored
(paper §2.2; rank r <= T after T epochs).

The fusion target is the rank-r intermediate ``T = (X @ A^T) * s``: computed
once per batch block into a VMEM scratch buffer and consumed by every
``n_out`` block without ever visiting HBM. Grid is (batch blocks, out
blocks) with the out axis innermost:

    j == 0:  t_scratch = dot(x_blk, A^T) * s     one MXU pass over A
    all j:   o_blk     = dot(t_scratch, B_blk)   one MXU pass over B total

so X and A are read exactly once per batch block and B exactly once per
call — the information-theoretic minimum for the two-stage product. Both
dots accumulate in f32 via ``preferred_element_type`` regardless of input
dtype, and f32 operands multiply at f32 precision. The running iterate
scale ``alpha`` is folded into ``s`` by the ops layer, so the kernel itself
is scale-free.

Rows of A/B at indices >= the live rank carry s == 0 (``low_rank`` zeroes
them by construction), so rank padding — like batch padding — is an exact
no-op, not an approximation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..precision import mxu_precision


def _factor_matvec_kernel(x_ref, a_ref, s_ref, b_ref, o_ref, t_ref):
    """o[i, j] = ((x[i] @ a^T) * s) @ b[j]; grid=(batch, out), out innermost."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _stage1():
        x, a = x_ref[...], a_ref[...]
        t_ref[...] = (
            jnp.dot(x, a.T, precision=mxu_precision(x, a),
                    preferred_element_type=jnp.float32)
            * s_ref[...].T
        )

    t, b = t_ref[...], b_ref[...]
    o_ref[...] = jnp.dot(
        t, b, precision=mxu_precision(t, b), preferred_element_type=jnp.float32
    )


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_o", "interpret")
)
def factor_matvec(
    x: jax.Array,
    a: jax.Array,
    s: jax.Array,
    b: jax.Array,
    *,
    block_b: int = 128,
    block_o: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """((X @ A^T) * s) @ B for X:(bt, n_in), A:(r, n_in), s:(r, 1),
    B:(r, n_out) -> (bt, n_out) f32.

    ``bt`` must divide ``block_b`` and ``n_out`` must divide ``block_o``
    (ops.py pads; zero rows/columns are exact no-ops). ``r`` and ``n_in``
    ride whole: VMEM/step is block_b*n_in (X) + r*(n_in + block_o) (A, B)
    + block_b*r (scratch) + the output block — serving ranks are <= the
    epoch budget, so the factors are small by construction; very large
    n_in belongs to the jnp reference path, not this kernel.
    """
    bt, n_in = x.shape
    r = a.shape[0]
    n_out = b.shape[1]
    assert a.shape == (r, n_in), (a.shape, x.shape)
    assert s.shape == (r, 1), s.shape
    assert b.shape == (r, n_out), b.shape
    assert bt % block_b == 0 and n_out % block_o == 0, (
        x.shape, b.shape, block_b, block_o,
    )
    return pl.pallas_call(
        _factor_matvec_kernel,
        grid=(bt // block_b, n_out // block_o),
        in_specs=[
            pl.BlockSpec((block_b, n_in), lambda i, j: (i, 0)),
            pl.BlockSpec((r, n_in), lambda i, j: (0, 0)),
            pl.BlockSpec((r, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((r, block_o), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bt, n_out), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_b, r), jnp.float32)],
        interpret=interpret,
    )(x, a, s, b)
