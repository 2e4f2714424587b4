"""Dot precision inside the TPU kernels.

At its default precision the TPU's matrix unit multiplies f32 operands in a
single bf16 pass, a relative error near 1e-3. The f32 kernels here promise
f32 maths (their startup checks compare them with f32 references at 1e-4),
so their dots ask for ``Precision.HIGHEST``; bf16 operands keep the default.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def mxu_precision(*operands):
    """``HIGHEST`` when every operand is f32, else ``None`` (the default)."""
    if all(x.dtype == jnp.float32 for x in operands):
        return jax.lax.Precision.HIGHEST
    return None
