"""The plain reference of DFW-Trace and the judge of an answer.

Nothing here imports the program. The judge takes an answer -- an iterate
W and the loss the solver reported for it -- and computes from the data
alone, in float32 at ``Precision.HIGHEST``:

- ``loss_rel``: |reported loss - F(W)| / F(W). A solver whose state has
  drifted from its iterate, that left rows out of the loss, or that
  computed in a lower precision, reports a loss that is not F(W).
- ``gap_ratio``: the exact duality gap <W, G> + mu sigma_1(G) at W over the
  gap the fit was asked to reach. A fit that stopped before it reached the
  gap, or that returned an iterate it never moved, reads above 1.
- ``w_rel``: ||W - W_ref||_F / ||W_ref||_F, where W_ref is this reference's
  own solve from the job's key for as many epochs as the job ran. It
  follows the exact path, so it sees what leaves the answer good but not
  the one asked for: rows left out, or chips that each followed their own
  shard.

``solve`` is DFW-Trace written out on the dense (d, m) gradient: K two-sided
block power iterations of width k on G, the blended atom
S = -mu sum_j c_j u_j v_j.T with c = sigma / sum(sigma) (a block of width
k > 1 starts from the block it converged to the epoch before), the default
2 / (t + 2) step or the exact line search, and a stop once the estimated
gap <W, G> + mu max(sigma) falls to ``gap_tol`` (never, for ``-inf``: the
solve then runs ``max_epochs``). Epoch t starts its power iterations from a
normal draw of ``fold_in(key, t)`` made a unit vector, as the program
draws its start from the job's key. Run in bfloat16 it is the control that
the judge must refuse.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HI = jax.lax.Precision.HIGHEST
BLOCK_ROWS = 8192
_BUILT: dict = {}


def _rows_mesh(x):
    """The mesh and axis the rows of ``x`` are split over (one device's
    mesh when ``x`` lives on one device)."""
    sh = x.sharding
    if isinstance(sh, NamedSharding) and sh.spec and sh.spec[0] is not None:
        return sh.mesh, sh.spec[0]
    return Mesh(np.array(sorted(x.devices(), key=lambda d: d.id)), ("rows",)), "rows"


def _block(rows: int) -> int:
    return next(b for b in range(min(BLOCK_ROWS, rows), 0, -1) if rows % b == 0)


def _over_rows(problem, name, fn, x, y, w, dtype):
    """Sum ``fn(x_block, y_block, w, dtype)`` over blocks of rows on every
    chip, then over the chips, so that no (n, m) array is ever whole."""
    mesh, axis = _rows_mesh(x)
    key = (problem.__name__, name, tuple(d.id for d in mesh.devices.flat), axis,
           jnp.dtype(dtype).name)
    if key not in _BUILT:
        def local(xs, ys, w):
            rb = _block(xs.shape[0])
            xb = xs.reshape((-1, rb) + xs.shape[1:])
            yb = ys.reshape((-1, rb) + ys.shape[1:])

            def body(acc, blk):
                out = fn(blk[0], blk[1], w, dtype)
                return jax.tree.map(jnp.add, acc, out), None

            first = jax.eval_shape(lambda: fn(xb[0], yb[0], w, dtype))
            zero = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), first)
            acc, _ = jax.lax.scan(body, zero, (xb, yb))
            return jax.tree.map(lambda a: jax.lax.psum(a, axis), acc)

        _BUILT[key] = jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=(P(axis), P(axis), P()), out_specs=P(),
            check_vma=False))
    return _BUILT[key](x, y, w)


def loss_grad(problem, x, y, w, dtype=jnp.float32):
    """F(W) and the dense (d, m) gradient, summed over blocks of rows."""
    return _over_rows(problem, "loss_grad", problem.block_loss_grad, x, y, w, dtype)


def line_step(problem, x, w, s, g, dtype=jnp.float32):
    """The exact step <-G, D> / ||X D||^2 toward S along D = S - W, clipped
    to [0, 1]."""
    dirn = (s - w).astype(dtype)
    def terms(xb, _, d_, dt):
        return problem.block_step_terms(xb, d_, dt)

    sq = _over_rows(problem, "step", terms, x, x, dirn, dtype)
    numer = -jnp.sum(g.astype(jnp.float32) * dirn.astype(jnp.float32))
    return jnp.clip(numer / jnp.maximum(sq.astype(jnp.float32), 1e-30), 0.0, 1.0)


def dense_iterate(u, s, v, alpha):
    """W = alpha * U.T diag(s) V for the factored iterate's (r, d), (r,),
    (r, m) factors, in float32."""
    return _dense(u, s, v, alpha)


@jax.jit
def _dense(u, s, v, alpha):
    return jnp.dot((u.T * (alpha * s)), v, precision=HI)


@jax.jit
def _sigma1(g):
    return jnp.linalg.svd(g.astype(jnp.float32), compute_uv=False)[0]


def exact_gap(problem, x, y, w, mu):
    """(F(W), exact duality gap at W) in float32."""
    loss, g = loss_grad(problem, x, y, w)
    inner = jnp.sum(w * g)
    return float(loss), float(inner + mu * _sigma1(g))


def initial_gap(problem, x, y, mu, d, m):
    """The exact gap at W = 0: mu * sigma_1(grad F(0))."""
    return exact_gap(problem, x, y, jnp.zeros((d, m), jnp.float32), mu)[1]


def judge(problem, x, y, mu, gap_target, w, reported_loss, w_ref):
    """The numbers compared for one answer (see the module docstring)."""
    w = w.astype(jnp.float32)
    loss, gap = exact_gap(problem, x, y, w, mu)
    return {"loss_rel": abs(float(reported_loss) - loss) / abs(loss),
            "gap_ratio": gap / gap_target,
            "w_rel": float(_rel_dist(w, w_ref.astype(jnp.float32)))}


@jax.jit
def _rel_dist(w, w_ref):
    return jnp.linalg.norm(w - w_ref) / jnp.linalg.norm(w_ref)


@partial(jax.jit, static_argnames=("iters",))
def _block_power(g, v, *, iters):
    prec = HI if g.dtype == jnp.float32 else None
    for _ in range(iters):
        u = jnp.linalg.qr(jnp.dot(g, v, precision=prec).astype(jnp.float32))[0]
        u = u.astype(g.dtype)
        vv = jnp.dot(g.T, u, precision=prec)
        sig = jnp.linalg.norm(vv.astype(jnp.float32), axis=0)
        v = (vv / (sig.astype(g.dtype) + 1e-30)).astype(g.dtype)
    return u, v, sig


def solve(problem, x, y, *, d, m, mu, k_iters, width, step, gap_tol,
          max_epochs, key, dtype=jnp.float32, gaps=None):
    """Reference DFW-Trace. Returns (W, reported loss at W, epochs run);
    appends each epoch's estimated gap to ``gaps`` when given."""
    w = jnp.zeros((d, m), dtype)
    epochs = 0
    for t in range(max_epochs):
        _, g = loss_grad(problem, x, y, w, dtype)
        if width == 1 or t == 0:
            v0 = jax.random.normal(jax.random.fold_in(key, t), (m, width))
            v0 = jnp.linalg.qr(v0)[0].astype(dtype)
        else:  # a block starts from the block it converged to last epoch
            v0 = jnp.linalg.qr(v.astype(jnp.float32))[0].astype(dtype)
        u, v, sig = _block_power(g, v0, iters=k_iters)
        gap = float(jnp.sum(w.astype(jnp.float32) * g.astype(jnp.float32))
                    + mu * jnp.max(sig))
        epochs = t + 1
        if gaps is not None:
            gaps.append(gap)
        c = (sig / jnp.sum(sig)).astype(dtype)
        s = (-mu) * jnp.dot(u * c, v.T).astype(dtype)
        if step == "linesearch":
            gamma = line_step(problem, x, w, s, g, dtype)
        else:
            gamma = 2.0 / (t + 2.0)
        w = ((1.0 - gamma) * w + gamma * s).astype(dtype)
        if gap <= gap_tol:
            break
    loss, _ = loss_grad(problem, x, y, w, dtype)
    return w, float(loss), epochs
