"""Least squares holds f32 where the TPU's default f32 product would not.

On a TPU an f32 matrix product without a pinned precision rounds its
operands to bfloat16 and sums the products in f32. The least-squares
residual R = X W - Y cancels most of what the products carry as W nears
the truth, so a bf16 product in the block update or the line search moves
the loss far more than f32 rounding does. These tests run the task's
``update`` and ``linesearch_terms`` with that product emulated on the CPU
(every ``dot_general`` that leaves its precision unpinned sees bf16
operands) and hold them to the f32 maths.
"""
import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tasks

LOSS_REL = 1e-4  # the least-squares benchmark cell's limit on the reported loss


def _eval(jaxpr, consts, args, round_all):
    env = {}

    def read(v):
        return v.val if isinstance(v, jex_core.Literal) else env[v]

    env.update(zip(jaxpr.constvars, consts))
    env.update(zip(jaxpr.invars, args))
    for eqn in jaxpr.eqns:
        ins = [read(v) for v in eqn.invars]
        if eqn.primitive.name in ("pjit", "jit"):
            sub = eqn.params["jaxpr"]
            outs = _eval(sub.jaxpr, sub.consts, ins, round_all)
        else:
            if eqn.primitive.name == "dot_general" and (
                    round_all or eqn.params["precision"] is None):
                ins = [x.astype(jnp.bfloat16).astype(x.dtype) for x in ins]
            outs = eqn.primitive.bind(*ins, **eqn.params)
            if not eqn.primitive.multiple_results:
                outs = [outs]
        env.update(zip(eqn.outvars, outs))
    return [read(v) for v in jaxpr.outvars]


def _tpu_default(fn, round_all=False):
    """``fn`` with the TPU's default f32 product: every product whose
    precision is unpinned (or, with ``round_all``, every product) takes
    bfloat16-rounded operands."""
    def run(*args):
        closed, shape = jax.make_jaxpr(fn, return_shape=True)(*args)
        out = _eval(closed.jaxpr, closed.consts, jax.tree.leaves(args), round_all)
        return jax.tree.unflatten(jax.tree.structure(shape), out)
    return run


def _problem(n=4096, d=64, m=48, rank=10):
    """Rows, a rank-10 truth of trace norm 1 with its SVD, and Y = X W*,
    in float64."""
    rng = np.random.default_rng(0)
    u = np.linalg.qr(rng.normal(size=(d, rank)))[0]
    v = np.linalg.qr(rng.normal(size=(m, rank)))[0]
    s = np.linspace(1.0, 0.1, rank)
    s /= s.sum()
    x = rng.normal(size=(n, d))
    w_star = (u * s) @ v.T
    return x, w_star, u, s, v


def _setup():
    """W = 0.9 W* and the block atom S = W* (mu = 1): a step of 0.9 lands on
    0.99 W*, where R is a hundredth of Y."""
    x, w_star, u, s, v = _problem()
    y = x @ w_star
    w0 = 0.9 * w_star
    task = tasks.MultiTaskLeastSquares(d=x.shape[1], m=y.shape[1])
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    state = tasks.MTLSState(x=f32(x), y=f32(y), r=f32(x @ w0 - y))
    # S = -mu u_c v.T with the blend weights folded into u_c.
    return task, state, f32(-(u * s)), f32(v), x, y, w0, w_star


def _update_loss_rel(round_all):
    task, state, u_c, v, x, y, w0, w_star = _setup()
    gamma, mu = 0.9, 1.0
    new = _tpu_default(task.update, round_all)(state, u_c, v, jnp.float32(gamma),
                                               jnp.float32(mu))
    exact = 0.5 * np.sum((x @ ((1 - gamma) * w0 + gamma * w_star) - y) ** 2)
    got = 0.5 * np.sum(np.asarray(new.r, np.float64) ** 2)
    return abs(got - exact) / exact


def _linesearch_rel(round_all):
    task, state, u_c, v, x, y, w0, w_star = _setup()
    numer, denom = _tpu_default(task.linesearch_terms, round_all)(
        state, u_c, v, jnp.float32(1.0))
    xd = x @ (w_star - w0)
    r = x @ w0 - y
    want = (-np.sum(r * xd), np.sum(xd * xd))
    return max(abs(float(g) - w) / abs(w) for g, w in zip((numer, denom), want))


def test_block_update_holds_the_loss_under_tpu_default_products():
    assert _update_loss_rel(round_all=False) < LOSS_REL


def test_block_linesearch_terms_hold_under_tpu_default_products():
    assert _linesearch_rel(round_all=False) < LOSS_REL


@pytest.mark.parametrize("what", [_update_loss_rel, _linesearch_rel])
def test_unpinned_products_would_exceed_the_bound(what):
    """The emulation has teeth: with every product at bf16 operands, as
    before the least-squares products were pinned, the same numbers read
    well above the bound."""
    assert what(round_all=True) > 10 * LOSS_REL
