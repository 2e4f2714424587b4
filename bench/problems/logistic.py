"""Multinomial logistic head: data from the seed, and the plain reference.

F(W) = sum_i [ logsumexp(x_i W) - (x_i W)_{y_i} ],  W (d, m),  ||W||_* <= mu.

Data: Gaussian features with labels from a planted rank-10 W* and a share
of labels redrawn uniformly (the generator of the repository's
``benchmarks/imagenet_head.py``), drawn on the device. The planted factors
come from the fixed ``TRUTH_SEED``, part of the configuration as a model's
weights would be: every seed then poses the same problem on other rows, so
the work of a run hangs on the seed no more than on the rows, and every
shard of a sharded deployment labels its rows with the same W*.

The reference is straightforward ``jax.numpy``: the dense logits, the
softmax and the dense (d, m) gradient of a block of rows, at
``Precision.HIGHEST`` in float32 or in the dtype it is asked for
(``bench/reference.py`` sums the blocks). It imports nothing of the
program.
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

    return tasks.MultinomialLogistic(d=cfg["d"], m=cfg["m"])


@partial(jax.jit, static_argnames=("rows", "d", "m", "rank", "noise"))
def _shard(key, shard, *, rows, d, m, rank, noise):
    ku, kv = jax.random.split(jax.random.PRNGKey(TRUTH_SEED))
    wu = jax.random.normal(ku, (d, rank), jnp.float32)
    wv = jax.random.normal(kv, (rank, m), jnp.float32)
    kx, kf, kr = jax.random.split(jax.random.fold_in(key, shard), 3)
    x = jax.random.normal(kx, (rows, d), jnp.float32)
    y = jnp.argmax(jnp.dot(jnp.dot(x, wu, precision=HI), wv, precision=HI),
                   axis=1).astype(jnp.int32)
    flip = jax.random.bernoulli(kf, noise, (rows,))
    y = jnp.where(flip, jax.random.randint(kr, (rows,), 0, m, jnp.int32), y)
    return x, y


def make_shard(key, shard: int, rows: int, cfg):
    """Rows ``[shard * rows, (shard + 1) * rows)`` of the data set, on the
    device that ``key`` is on."""
    return _shard(key, shard, rows=rows, d=cfg["d"], m=cfg["m"],
                  rank=cfg["planted_rank"], noise=cfg["label_noise"])


def block_loss_grad(x, y, w, dtype):
    """F over a block of rows and its dense gradient
    X.T (softmax(X W) - onehot(y)), in ``dtype`` (float32 at HIGHEST)."""
    prec = HI if dtype == jnp.float32 else None
    x, w = x.astype(dtype), w.astype(dtype)
    z = jnp.dot(x, w, precision=prec)
    lse = jax.scipy.special.logsumexp(z, axis=-1)
    zy = jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0]
    p = jax.nn.softmax(z, axis=-1) - jax.nn.one_hot(y, w.shape[1], dtype=dtype)
    return jnp.sum(lse - zy), jnp.dot(x.T, p, precision=prec)


def epoch_work(rows, cfg, k_iters, width):
    """(flops, bytes) one epoch requires on ``rows`` rows."""
    return work.logistic_epoch(rows, cfg["d"], cfg["m"], k_iters)


def power_work(rows, cfg, k_iters, width):
    """(flops, bytes) the power iterations' kernels require per epoch."""
    return work.logistic_power(rows, cfg["d"], cfg["m"], k_iters)
