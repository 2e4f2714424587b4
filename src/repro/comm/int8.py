"""int8 stochastic-rounding reducer: quantize -> s8 psum -> dequantize.

Per ``exchange`` of a (dim,) f32 vector:

1. every worker computes its local absmax and a scalar f32 ``pmax`` makes it
   the *shared* per-vector scale s (the "scale exchange" — 8 wire bytes),
2. the local contribution is stochastically rounded onto the integer grid
   ``[-b, b]`` with ``b = 127 // N`` via the fused ``kernels/quantize``
   Pallas kernel (jnp ref off-TPU),
3. one s8 all-reduce sums the integers — ``2 * dim`` wire bytes instead of
   the dense ``8 * dim`` (4x lighter; the scale pmax is amortized),
4. the sum is mapped back to f32 by ``dequantize`` (* s / b).

Unbiasedness: stochastic rounding gives ``E[q_j] = x_j * b / s`` exactly
(noise uniform in [0, 1)), so ``E[dequant(sum_j q_j)] = sum_j x_j`` — the
LMO direction estimate is noisier but not biased, which is the regime the
paper's Theorem 2 (multiplicative LMO error) already covers.

Overflow safety: ``|x_j| <= s`` by construction of the shared scale, so every
worker's integers lie in [-b, b] and any partial sum of the ring all-reduce
is bounded by ``N * b <= 127`` — the s8 wire dtype cannot wrap.

The sacrifice is log2(N) bits of per-worker resolution (b = 15 at N = 8).
The power method tolerates it: each iteration renormalizes, and FW corrects
residual direction error over epochs.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels.quantize import ops as qops
from . import base


@dataclasses.dataclass(frozen=True)
class Int8Reducer(base.Reducer):
    """Stateless (no error feedback): quantization noise is zero-mean, so
    there is no systematic residual to feed back. Its ``state_spec`` is
    therefore ``()`` — checkpoints save nothing for it, and a bit-exact
    resume needs only the carried PRNG key (the stochastic-rounding noise
    is keyed off the epoch counter folded into the run key)."""

    num_workers: int = 1
    use_pallas: Optional[bool] = None
    interpret: bool = False

    def __post_init__(self):
        if not 1 <= self.num_workers <= 127:
            raise ValueError(
                f"int8 reducer supports 1..127 workers (got {self.num_workers}): "
                "the per-worker budget 127 // N must stay >= 1"
            )

    @property
    def spec(self) -> str:  # type: ignore[override]
        return "int8"

    @property
    def budget(self) -> int:
        return max(1, 127 // self.num_workers)

    def exchange(self, x, state, *, slot, key, axis_name=None, weight=None,
                 groups=None):
        # weight is ignored: x of a masked worker is exactly zero, which
        # quantizes to zero — no stale state to guard (stateless).
        # groups restricts both the scale pmax and the s8 psum to each
        # worker's own axis_index_group (the hier inter-group hop); the
        # shared-scale overflow argument holds per group since the budget is
        # sized to the group width.
        x = x.astype(jnp.float32)
        scale = base.pmax(jnp.max(jnp.abs(x)), axis_name, groups)
        noise = jax.random.uniform(
            base.fold_axis_index(key, axis_name), x.shape, jnp.float32
        )
        kw = dict(
            budget=self.budget, use_pallas=self.use_pallas, interpret=self.interpret
        )
        q = qops.quantize(x, noise, scale, **kw)
        total = base.psum(q, axis_name, groups)  # s8 on the wire
        return qops.dequantize(total, scale, **kw), state

    def wire_bytes(self, dim: int, num_workers: int) -> int:
        # s8 ring all-reduce (2x) + the f32 scalar scale pmax (2x * 4B)
        return 2 * 1 * dim + 2 * 4


def verify_quantize_kernels(
    key: jax.Array,
    *,
    num_workers: int = 8,
    dim: int = 384,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    tol: float = 1e-6,
) -> float:
    """Startup check (same role as ``launch/dfw.verify_kernels``): the
    dispatched quantize/dequantize pair must match the jnp reference on a
    random probe. Returns the max abs error observed.

    Agreement is exact, on TPU as on CPU: both paths consume the same
    explicit noise and the same XLA-computed factors budget/scale and
    scale/budget (``kernels/quantize/ref.py``), leaving the kernel only
    exactly-rounded f32 multiplies, adds and floors. There is no matmul, so
    no precision mode applies. One integer off is an error of 1, far past
    ``tol``."""
    from ..kernels.quantize import ref as qref

    b = max(1, 127 // num_workers)
    kx, kn = jax.random.split(key)
    x = jax.random.normal(kx, (dim,), jnp.float32)
    noise = jax.random.uniform(kn, (dim,), jnp.float32)
    scale = jnp.max(jnp.abs(x))
    q_got = qops.quantize(
        x, noise, scale, budget=b, use_pallas=use_pallas, interpret=interpret
    )
    q_want = qref.quantize(x, noise, scale, b)
    d_got = qops.dequantize(
        q_got, scale, budget=b, use_pallas=use_pallas, interpret=interpret
    )
    # One explicit batched pull for both error scalars (REP002): float() on
    # each jnp reduction would block on two implicit device->host syncs.
    err_q, err_d = jax.device_get((
        jnp.max(jnp.abs(q_got.astype(jnp.int32) - q_want.astype(jnp.int32))),
        jnp.max(jnp.abs(d_got - qref.dequantize(q_want, scale, b))),
    ))
    err = max(float(err_q), float(err_d))
    if err > tol:
        raise AssertionError(
            f"quantize kernel diverges from jnp reference: max abs err {err:.3e} "
            f"> tol {tol:.1e} (budget={b})"
        )
    return err
