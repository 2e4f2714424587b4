"""Multi-task least squares (paper §5.1, Fig. 1): data from the seed, and
the plain reference.

F(W) = 1/2 ||X W - Y||_F^2,  W (d, m),  ||W||_* <= mu.

Data: Gaussian rows X and noiseless targets Y = X W* for a rank-r truth
W* = U diag(s) V.T, U and V the Q factors of Gaussian matrices and
s = linspace(1, 0.1, r) scaled to sum 1, so ||W*||_* = 1 (the generator of
the repository's ``benchmarks/common.py::mtls_problem``). The rows are
drawn on the device from the seed; W* comes from the fixed ``TRUTH_SEED``,
part of the configuration as a model's weights would be: every seed then
poses the same problem on other rows.

The reference is straightforward ``jax.numpy``: the residual and the dense
(d, m) gradient of a block of rows, and ||X D||^2 for the line search, at
``Precision.HIGHEST`` in float32 or in the dtype it is asked for
(``bench/reference.py`` sums the blocks). It imports nothing of the
program but its task.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import work

HI = jax.lax.Precision.HIGHEST
TRUTH_SEED = 0


def task(cfg):
    from repro.core import tasks

    return tasks.MultiTaskLeastSquares(d=cfg["d"], m=cfg["m"])


@partial(jax.jit, static_argnames=("d", "m", "rank"))
def truth(*, d, m, rank):
    """The planted W* (d, m): rank ``rank``, trace norm 1."""
    ku, kv = jax.random.split(jax.random.PRNGKey(TRUTH_SEED))
    u = jnp.linalg.qr(jax.random.normal(ku, (d, rank), jnp.float32))[0]
    v = jnp.linalg.qr(jax.random.normal(kv, (m, rank), jnp.float32))[0]
    s = jnp.linspace(1.0, 0.1, rank, dtype=jnp.float32)
    return jnp.dot(u * (s / jnp.sum(s)), v.T, precision=HI)


@partial(jax.jit, static_argnames=("rows", "d", "m", "rank"))
def _shard(key, shard, *, rows, d, m, rank):
    x = jax.random.normal(jax.random.fold_in(key, shard), (rows, d), jnp.float32)
    return x, jnp.dot(x, truth(d=d, m=m, rank=rank), precision=HI)


def make_shard(key, shard: int, rows: int, cfg):
    """Rows ``[shard * rows, (shard + 1) * rows)`` of the data set, on the
    device that ``key`` is on."""
    return _shard(key, shard, rows=rows, d=cfg["d"], m=cfg["m"],
                  rank=cfg["planted_rank"])


def block_loss_grad(x, y, w, dtype):
    """F over a block of rows and its dense gradient X.T (X W - Y), in
    ``dtype`` (float32 at HIGHEST)."""
    prec = HI if dtype == jnp.float32 else None
    x, y, w = x.astype(dtype), y.astype(dtype), w.astype(dtype)
    r = jnp.dot(x, w, precision=prec) - y
    return 0.5 * jnp.sum(r * r), jnp.dot(x.T, r, precision=prec)


def block_step_terms(x, d, dtype):
    """||X D||^2 over a block of rows, the line search's denominator."""
    prec = HI if dtype == jnp.float32 else None
    xd = jnp.dot(x.astype(dtype), d.astype(dtype), precision=prec)
    return jnp.sum(xd * xd)


def epoch_work(rows, cfg, k_iters, width):
    """(flops, bytes) one ``block:width`` epoch requires on ``rows`` rows."""
    return work.mtls_block_epoch(rows, cfg["d"], cfg["m"], k_iters, width)


def power_work(rows, cfg, k_iters, width):
    """(flops, bytes) the block power iterations' k-wide passes over X and
    R require per epoch."""
    return work.mtls_block_power(rows, cfg["d"], cfg["m"], k_iters, width)
