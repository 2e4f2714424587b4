"""Blocked COO matvec Pallas kernel — the matrix-completion power-method hot spot.

The implicit completion gradient ``G = P_Omega(W - M)`` is a COO sparse matrix
(entry shard per worker). Its matvec ``(G v)[i] = sum_e vals_e v[cols_e]
[rows_e == i]`` is a gather-multiply-scatter chain; TPUs have no native
VMEM gather/scatter, so both halves are expressed as one-hot matmuls that run
on the MXU:

    gather:  x[g_e]    = onehot(g, in_dim)  @ x          (block_e x in_dim)
    scatter: out[seg] += onehot(seg, out)^T @ contrib    (out_dim x block_e)

The grid walks entry blocks; index/value blocks stream through VMEM exactly
once per call (one HBM pass over the shard) while the dense vectors stay
resident. The extra one-hot FLOPs are the standard TPU trade for
bandwidth-bound sparse ops — each is ``block_e * dim`` MACs on the MXU, and
the entry shard, not the dense work, is the traffic that matters.
Accumulation is always f32 via ``preferred_element_type``, and the one-hot
products run at f32 precision so gathered values are not rounded to bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..precision import mxu_precision


def _coo_matvec_kernel(seg_ref, gat_ref, vals_ref, x_ref, o_ref):
    """out[seg_e] += vals_e * x[gat_e]; grid=(entry blocks,)."""
    e = pl.program_id(0)

    @pl.when(e == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    seg = seg_ref[...]  # (block_e, 1) int32 output coordinate
    gat = gat_ref[...]  # (block_e, 1) int32 gather coordinate
    vals = vals_ref[...].astype(jnp.float32)
    block_e = seg.shape[0]
    in_dim = x_ref.shape[0]
    out_dim = o_ref.shape[0]

    gather = (
        jax.lax.broadcasted_iota(jnp.int32, (block_e, in_dim), 1) == gat
    ).astype(jnp.float32)
    hi = mxu_precision(gather)  # f32 one-hots: the values ride at f32
    xe = jnp.dot(
        gather, x_ref[...].astype(jnp.float32), precision=hi,
        preferred_element_type=jnp.float32,
    )
    contrib = vals * xe  # (block_e, 1)

    scatter = (
        jax.lax.broadcasted_iota(jnp.int32, (block_e, out_dim), 1) == seg
    ).astype(jnp.float32)
    o_ref[...] += jnp.dot(
        scatter.T, contrib, precision=hi, preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("out_dim", "block_e", "interpret"))
def coo_matvec(
    seg: jax.Array,
    gat: jax.Array,
    vals: jax.Array,
    x: jax.Array,
    *,
    out_dim: int,
    block_e: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Segment-reduce ``vals * x[gat]`` into ``seg`` -> (out_dim, 1) f32.

    ``seg``/``gat``/``vals`` are (p, 1) with p a block_e multiple (ops.py
    pads; vals==0 padding rows are exact no-ops regardless of their indices).
    ``x`` is (in_dim, 1). VMEM/step: 3 entry blocks + both dense vectors.
    """
    p = seg.shape[0]
    assert p % block_e == 0, (p, block_e)
    return pl.pallas_call(
        _coo_matvec_kernel,
        grid=(p // block_e,),
        in_specs=[
            pl.BlockSpec((block_e, 1), lambda e: (e, 0)),
            pl.BlockSpec((block_e, 1), lambda e: (e, 0)),
            pl.BlockSpec((block_e, 1), lambda e: (e, 0)),
            pl.BlockSpec((x.shape[0], 1), lambda e: (0, 0)),
        ],
        out_specs=pl.BlockSpec((out_dim, 1), lambda e: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((out_dim, 1), jnp.float32),
        interpret=interpret,
    )(seg, gat, vals, x)
