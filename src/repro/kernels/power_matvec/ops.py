"""Public jit'd wrappers: padding, dispatch (Pallas on TPU / ref elsewhere)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel, ref


def _use_pallas(force: bool | None) -> bool:
    if force is not None:
        return force
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, mult_r: int, mult_c: int) -> jax.Array:
    n, m = x.shape
    pr, pc = (-n) % mult_r, (-m) % mult_c
    if pr == 0 and pc == 0:
        return x
    return jnp.pad(x, ((0, pr), (0, pc)))


# Tiles of a k-wide pass (k > 1): its products are (k, len), so row and
# column tiles both lie along lanes and are multiples of 128; at most
# 512 x 1024 f32 (2 MiB a buffer), the fastest of a v5e sweep at
# 100,000 x 1000.
_WIDE_ROWS, _WIDE_COLS, _LANE = 512, 1024, 128


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _tiles(n: int, m: int, k: int, block_r, block_c) -> tuple:
    """``(block_r, block_c)`` for an (n, m) matrix and k probe columns:
    the caller's, else 256 x 256 for a vector and, for a block, row tiles of
    512 and the columns split evenly into the fewest tiles of <= 1024."""
    if k == 1:
        return block_r or 256, block_c or 256
    if block_r is None:
        block_r = min(_WIDE_ROWS, _round_up(n, _LANE))
    if block_c is None:
        col_tiles = -(-m // _WIDE_COLS)
        block_c = _round_up(-(-m // col_tiles), _LANE)
    return block_r, block_c


@functools.partial(jax.jit, static_argnames=("block_r", "block_c", "use_pallas", "interpret"))
def matvec(
    a: jax.Array,
    v: jax.Array,
    *,
    block_r: int | None = None,
    block_c: int | None = None,
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> jax.Array:
    """A @ v: v (m,) -> (n,), or a block v (m, k) -> (n, k) in one pass over
    A. Zero-pads to block multiples (zeros are exact no-ops)."""
    if not _use_pallas(use_pallas) and not interpret:
        return ref.matvec(a, v)
    n, m = a.shape
    vb = v.reshape(m, -1)
    block_r, block_c = _tiles(n, m, vb.shape[1], block_r, block_c)
    ap = _pad_to(a, block_r, block_c)
    vp = _pad_to(vb, block_c, 1)
    out = kernel.matvec(ap, vp, block_r=block_r, block_c=block_c, interpret=interpret)
    return out[:n] if v.ndim == 2 else out[:n, 0]


@functools.partial(jax.jit, static_argnames=("block_r", "block_c", "use_pallas", "interpret"))
def rmatvec(
    a: jax.Array,
    u: jax.Array,
    *,
    block_r: int | None = None,
    block_c: int | None = None,
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> jax.Array:
    """A^T @ u: u (n,) -> (m,), or a block u (n, k) -> (m, k)."""
    if not _use_pallas(use_pallas) and not interpret:
        return ref.rmatvec(a, u)
    n, m = a.shape
    ub = u.reshape(n, -1)
    block_r, block_c = _tiles(n, m, ub.shape[1], block_r, block_c)
    ap = _pad_to(a, block_r, block_c)
    up = _pad_to(ub, block_r, 1)
    out = kernel.rmatvec(ap, up, block_r=block_r, block_c=block_c, interpret=interpret)
    return out[:m] if u.ndim == 2 else out[:m, 0]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def power_iter_step(
    x: jax.Array,
    r: jax.Array,
    v: jax.Array,
    *,
    use_pallas: bool | None = None,
    interpret: bool = False,
):
    """One two-sided power iteration on the implicit gradient A = X^T R.

    Four streaming kernel calls; X and R are each read exactly twice per
    iteration (information-theoretic minimum for the two-sided step).
    Returns unit (u, v')."""
    kw = dict(use_pallas=use_pallas, interpret=interpret)
    t = matvec(r, v, **kw)
    u = rmatvec(x, t, **kw)
    u = u / (jnp.linalg.norm(u) + 1e-30)
    s = matvec(x, u, **kw)
    v2 = rmatvec(r, s, **kw)
    v2 = v2 / (jnp.linalg.norm(v2) + 1e-30)
    return u, v2
