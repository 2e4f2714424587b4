"""Paper tasks (§2.3) with Appendix-B *sufficient information* updates.

Each task exposes the implicit-gradient operator interface consumed by the FW
driver and the distributed power method. State lives per-worker (per mesh
shard of the sample axis n); the driver psums the O(d+m) vectors.

Interface (duck-typed; see ``frank_wolfe.DFWTask``):
    init_state(X, Y)      -> state pytree (local shard)
    matvec(state, v)      -> local  grad_j @ v          (d,)
    rmatvec(state, u)     -> local  grad_j^T @ u        (m,)
    matmat(state, V)      -> local  grad_j @ V          (d,k)  (block LMO)
    rmatmat(state, U)     -> local  grad_j^T @ U        (m,k)
    update(state,u,v,g,mu)-> state after W <- (1-g)W - g*mu u v^T
    local_loss(state)     -> local loss contribution    ()
    inner_w_grad(state)   -> local <W, grad_j>          ()   (duality gap)
    local_grad(state)     -> dense local gradient (d,m)      (baselines only)
    linesearch(...)       -> optional closed-form step (MTLS only)

The products run through the kernels (``kernels/power_matvec`` for X and
R, ``kernels/mc_matvec`` for the observed entries); each task's
``kernels`` field says how (``_KERNELS``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..kernels.mc_matvec import ops as mc_ops
from ..kernels.power_matvec import ops as pm_ops


# XLA products pinned to f32: TPU's default precision rounds f32 operands
# to bf16 (and least squares' residual R = XW - Y cancels what they carry).
_HI = jax.lax.Precision.HIGHEST

# A task's ``kernels`` field, as the kernel ops take it: "auto" leaves the
# choice to the ops (Pallas on a TPU, the jnp reference elsewhere),
# "pallas" forces the kernels (a TPU program lowered from another host),
# "interpret" runs them in the Pallas interpreter (tests on the CPU), and
# "ref" runs the jnp references: the f32 maths, the oracle that
# ``launch/dfw.verify_kernels`` holds the other routes to.
_KERNELS = {
    "auto": dict(use_pallas=None, interpret=False),
    "ref": dict(use_pallas=False, interpret=False),
    "pallas": dict(use_pallas=True, interpret=False),
    "interpret": dict(use_pallas=True, interpret=True),
}


def _kernel_kw(kernels: str) -> dict:
    if kernels not in _KERNELS:
        raise ValueError(f"kernels={kernels!r}; pick one of {sorted(_KERNELS)}")
    return _KERNELS[kernels]


def _columns(product, s, b: jax.Array) -> jax.Array:
    """``product(s, .)`` on each column of the (., k) block ``b``: the block
    products of a task whose kernels take one vector at a time."""
    return jax.vmap(partial(product, s), in_axes=1, out_axes=1)(b)


def _uvt(xu: jax.Array, v: jax.Array, precision=None) -> jax.Array:
    """The atom's action on the sample axis: ``outer(xu, v)`` for a rank-1
    (vector) atom, ``xu @ v.T`` for a rank-k block whose u columns already
    carry the blend weights (the ``block:k`` solver's ``S = -mu sum_j c_j
    u_j v_j^T`` with ``c`` folded into ``u``), at ``precision``. ndim is
    static, so the rank-1 path stays byte-identical to the pre-block
    code."""
    if xu.ndim == 1:
        return jnp.outer(xu, v)
    return jnp.matmul(xu, v.T, precision=precision)


def _entrywise_uv(
    u: jax.Array, v: jax.Array, rows: jax.Array, cols: jax.Array
) -> jax.Array:
    """(sum_j u[rows, j] * v[cols, j]) — the atom evaluated on a COO entry
    shard; reduces to ``u[rows] * v[cols]`` for rank-1 vectors."""
    if u.ndim == 1:
        return jnp.take(u, rows) * jnp.take(v, cols)
    return jnp.sum(
        jnp.take(u, rows, axis=0) * jnp.take(v, cols, axis=0), axis=-1
    )


# ---------------------------------------------------------------------------
# Multi-task least squares:  F(W) = 1/2 ||XW - Y||_F^2
# ---------------------------------------------------------------------------


class MTLSState(NamedTuple):
    """Low-rank ('sufficient information') representation, paper App. B.

    Stores the residual R = X W - Y instead of the d x m gradient; every
    FW quantity is a chain of matvecs through X and R. Memory O(n_j(d+m)).
    """

    x: jax.Array  # (n_j, d)
    y: jax.Array  # (n_j, m)
    r: jax.Array  # (n_j, m) residual X W - Y


@dataclasses.dataclass(frozen=True)
class MultiTaskLeastSquares:
    d: int
    m: int
    kernels: str = "auto"  # "auto" | "ref" | "pallas" | "interpret"

    def init_state(self, x: jax.Array, y: jax.Array) -> MTLSState:
        # W^0 = 0  =>  R = -Y
        return MTLSState(x=x, y=y, r=-y)

    # grad = X^T R ; never materialized: two power_matvec passes.
    def matvec(self, s: MTLSState, v: jax.Array) -> jax.Array:
        kw = _kernel_kw(self.kernels)
        return pm_ops.rmatvec(s.x, pm_ops.matvec(s.r, v, **kw), **kw)

    def rmatvec(self, s: MTLSState, u: jax.Array) -> jax.Array:
        kw = _kernel_kw(self.kernels)
        return pm_ops.rmatvec(s.r, pm_ops.matvec(s.x, u, **kw), **kw)

    # The same chains take a whole (m, k) / (d, k) probe block at once:
    # each pass reads X or R once for all k columns.
    def matmat(self, s: MTLSState, v: jax.Array) -> jax.Array:
        return self.matvec(s, v)

    def rmatmat(self, s: MTLSState, u: jax.Array) -> jax.Array:
        return self.rmatvec(s, u)

    def update(self, s: MTLSState, u, v, gamma, mu) -> MTLSState:
        # R' = X[(1-g)W + g S] - Y = (1-g)R - g Y - g mu (X u) v^T
        # (block atoms: u (d,k) with blend weights folded in, v (m,k))
        xu = jnp.dot(s.x, u, precision=_HI)
        r = (1.0 - gamma) * s.r - gamma * s.y - (gamma * mu) * _uvt(xu, v, _HI)
        return MTLSState(x=s.x, y=s.y, r=r)

    def local_loss(self, s: MTLSState) -> jax.Array:
        return 0.5 * jnp.sum(s.r * s.r)

    def inner_w_grad(self, s: MTLSState) -> jax.Array:
        # <W, X^T R> = <X W, R> = <R + Y, R>
        return jnp.sum((s.r + s.y) * s.r)

    def local_grad(self, s: MTLSState) -> jax.Array:
        return jnp.dot(s.x.T, s.r, precision=_HI)

    def linesearch_terms(self, s: MTLSState, u, v, mu):
        """Local (numerator, denominator) of the closed-form step (App. B):

        gamma* = <-grad, D> / <X^T X D, D>,  D = S - W,
        computed via X D = -mu (X u) v^T - (R + Y)  — all O(n_j(d+m)).
        Returns local contributions; caller psums then divides.
        """
        xd = -(mu) * _uvt(jnp.dot(s.x, u, precision=_HI), v, _HI) - (s.r + s.y)
        numer = -jnp.sum(s.r * xd)
        denom = jnp.sum(xd * xd)
        return numer, denom


class MTLSDenseState(NamedTuple):
    """Dense sufficient information (paper App. B, 'dense' column):
    (X^T X, X^T Y, grad). Memory O(d^2 + dm); epoch cost independent of n_j.
    Preferable when n_j >> max(d, m)."""

    xtx: jax.Array  # (d, d) fixed
    xty: jax.Array  # (d, m) fixed
    g: jax.Array  # (d, m) local gradient X^T X W - X^T Y


@dataclasses.dataclass(frozen=True)
class MultiTaskLeastSquaresDense:
    d: int
    m: int

    def init_state(self, x: jax.Array, y: jax.Array) -> MTLSDenseState:
        xty = x.T @ y
        return MTLSDenseState(xtx=x.T @ x, xty=xty, g=-xty)

    def matvec(self, s: MTLSDenseState, v: jax.Array) -> jax.Array:
        return s.g @ v

    def rmatvec(self, s: MTLSDenseState, u: jax.Array) -> jax.Array:
        return s.g.T @ u

    def update(self, s: MTLSDenseState, u, v, gamma, mu) -> MTLSDenseState:
        # grad' = (1-g) grad + g (X^T X S - X^T Y),  X^T X S = -mu (X^T X u) v^T
        rank1 = -(mu) * jnp.outer(s.xtx @ u, v)
        g = (1.0 - gamma) * s.g + gamma * (rank1 - s.xty)
        return MTLSDenseState(xtx=s.xtx, xty=s.xty, g=g)

    def local_grad(self, s: MTLSDenseState) -> jax.Array:
        return s.g


# ---------------------------------------------------------------------------
# Multinomial logistic regression:
#   F(W) = sum_i [ logsumexp(x_i W) - (x_i W)_{y_i} ]
# ---------------------------------------------------------------------------


class LogisticState(NamedTuple):
    x: jax.Array  # (n_j, d)
    y: jax.Array  # (n_j,) int labels
    z: jax.Array  # (n_j, m) logits X W  (low-rank-updated)


@dataclasses.dataclass(frozen=True)
class MultinomialLogistic:
    d: int
    m: int
    kernels: str = "auto"  # "auto" | "ref" | "pallas" | "interpret"

    def init_state(self, x: jax.Array, y: jax.Array) -> LogisticState:
        return LogisticState(x=x, y=y, z=jnp.zeros((x.shape[0], self.m), x.dtype))

    def _probs(self, s: LogisticState) -> jax.Array:
        return jax.nn.softmax(s.z, axis=-1)

    # grad = X^T (P - H); H is one-hot(y). Never materialized. The X
    # products are power_matvec passes; the P products stay in XLA, pinned
    # to f32 (a TPU's default precision would round their operands to bf16).
    def matvec(self, s: LogisticState, v: jax.Array) -> jax.Array:
        pv = jnp.dot(self._probs(s), v, precision=_HI) - v[s.y]  # (n_j,)
        return pm_ops.rmatvec(s.x, pv, **_kernel_kw(self.kernels))

    def rmatvec(self, s: LogisticState, u: jax.Array) -> jax.Array:
        t = pm_ops.matvec(s.x, u, **_kernel_kw(self.kernels))  # (n_j,)
        return (jnp.dot(self._probs(s).T, t, precision=_HI)
                - jnp.zeros((self.m,), t.dtype).at[s.y].add(t))

    def matmat(self, s: LogisticState, v: jax.Array) -> jax.Array:
        return _columns(self.matvec, s, v)

    def rmatmat(self, s: LogisticState, u: jax.Array) -> jax.Array:
        return _columns(self.rmatvec, s, u)

    def update(self, s: LogisticState, u, v, gamma, mu) -> LogisticState:
        z = (1.0 - gamma) * s.z - (gamma * mu) * _uvt(s.x @ u, v)
        return LogisticState(x=s.x, y=s.y, z=z)

    def local_loss(self, s: LogisticState) -> jax.Array:
        lse = jax.scipy.special.logsumexp(s.z, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(s.z, s.y[:, None], axis=-1)[:, 0])

    def inner_w_grad(self, s: LogisticState) -> jax.Array:
        # <W, X^T(P-H)> = <Z, P - H>
        p = self._probs(s)
        zy = jnp.take_along_axis(s.z, s.y[:, None], axis=-1)[:, 0]
        return jnp.sum(s.z * p) - jnp.sum(zy)

    def local_grad(self, s: LogisticState) -> jax.Array:
        p = self._probs(s)
        h = jax.nn.one_hot(s.y, self.m, dtype=p.dtype)
        return s.x.T @ (p - h)

    def errors(self, s: LogisticState, top_k: int = 5) -> jax.Array:
        """Local count of top-k misclassifications (paper's error metric)."""
        _, idx = jax.lax.top_k(s.z, top_k)
        hit = jnp.any(idx == s.y[:, None], axis=-1)
        return jnp.sum(~hit)


# ---------------------------------------------------------------------------
# Matrix completion:  F(W) = 1/2 sum_{(i,j) in Omega} (W_ij - M_ij)^2
# ---------------------------------------------------------------------------


class MCState(NamedTuple):
    """Sparse sufficient information (paper App. B, completion column).

    A worker stores only its shard of observed entries in COO layout plus the
    residual *on those entries* — never the d x m matrix. Every FW quantity is
    a segment-gather/scatter chain over the entry axis, so per-worker memory
    and per-epoch compute are O(|Omega_j| + d + m).

    ``weight`` is a {0, 1} padding mask: the distributed driver pads shards to
    equal entry counts (static shapes under shard_map) with weight-0 dummy
    entries. ``resid`` is stored *pre-masked* (``weight * (W_ij - M_ij)``), so
    padding entries contribute exactly zero to every reduction and matvec.
    """

    rows: jax.Array  # (p_j,) int32 global row index of each observed entry
    cols: jax.Array  # (p_j,) int32 global column index
    vals: jax.Array  # (p_j,) observed values M_ij (arbitrary on padding)
    resid: jax.Array  # (p_j,) weight * (W_ij - M_ij)
    weight: jax.Array  # (p_j,) {0,1} mask; 0 marks padding entries


def pack_observations(
    rows, cols, vals, weight=None
) -> Tuple[jax.Array, jax.Array]:
    """Pack COO observations into the generic ``(x, y)`` driver arrays.

    Returns ``idx`` (p, 2) int32 = [row, col] and ``yw`` (p, 2) f32 =
    [value, weight] — the shapes ``MatrixCompletion.init_state`` consumes and
    ``launch/dfw.shard_rowwise`` shards along the entry axis.
    """
    rows = jnp.asarray(rows, jnp.int32)
    cols = jnp.asarray(cols, jnp.int32)
    vals = jnp.asarray(vals, jnp.float32)
    w = jnp.ones_like(vals) if weight is None else jnp.asarray(weight, jnp.float32)
    return jnp.stack([rows, cols], axis=1), jnp.stack([vals, w], axis=1)


@dataclasses.dataclass(frozen=True)
class MatrixCompletion:
    """Paper §2.3 task 3. The gradient is supported on observed entries only:
    ``grad = P_Omega(W - M)``, a sparse matrix with the residuals as values —
    matvec/rmatvec are scatter-reductions over the entry shard (App. B)."""

    d: int
    m: int
    kernels: str = "auto"  # "auto" | "ref" | "pallas" | "interpret"

    def init_state(self, idx: jax.Array, yw: jax.Array) -> MCState:
        # W^0 = 0  =>  resid = weight * (0 - M)
        rows = idx[:, 0].astype(jnp.int32)
        cols = idx[:, 1].astype(jnp.int32)
        vals = yw[:, 0]
        weight = yw[:, 1]
        return MCState(rows=rows, cols=cols, vals=vals,
                       resid=-weight * vals, weight=weight)

    # grad @ v: scatter resid_e * v[col_e] into rows. Never materialized.
    def matvec(self, s: MCState, v: jax.Array) -> jax.Array:
        return mc_ops.matvec(s.rows, s.cols, s.resid, v, self.d,
                             **_kernel_kw(self.kernels))

    def rmatvec(self, s: MCState, u: jax.Array) -> jax.Array:
        return mc_ops.rmatvec(s.rows, s.cols, s.resid, u, self.m,
                              **_kernel_kw(self.kernels))

    def matmat(self, s: MCState, v: jax.Array) -> jax.Array:
        return _columns(self.matvec, s, v)

    def rmatmat(self, s: MCState, u: jax.Array) -> jax.Array:
        return _columns(self.rmatvec, s, u)

    def update(self, s: MCState, u, v, gamma, mu) -> MCState:
        # W' = (1-g)W - g mu u v^T on the observed entries:
        # resid' = (1-g) resid - g w M - g mu w u[rows] v[cols]
        # (block atoms sum their k columns entrywise — see _entrywise_uv)
        uv = s.weight * _entrywise_uv(u, v, s.rows, s.cols)
        resid = (1.0 - gamma) * s.resid - gamma * s.weight * s.vals - (gamma * mu) * uv
        return s._replace(resid=resid)

    def local_loss(self, s: MCState) -> jax.Array:
        # weight^2 == weight for a {0,1} mask, so resid^2 is already masked
        return 0.5 * jnp.sum(s.resid * s.resid)

    def inner_w_grad(self, s: MCState) -> jax.Array:
        # <W, grad> over observed entries; W_ij = resid + M_ij there, and
        # padding terms vanish with resid == 0.
        return jnp.sum((s.resid + s.weight * s.vals) * s.resid)

    def local_grad(self, s: MCState) -> jax.Array:
        """Dense d x m gradient P_Omega(W - M) — baselines/tests only."""
        return jnp.zeros((self.d, self.m), s.resid.dtype).at[s.rows, s.cols].add(
            s.resid
        )

    def linesearch_terms(self, s: MCState, u, v, mu):
        """Local (numerator, denominator) of the exact step for the quadratic
        objective: gamma* = <-grad, D> / ||P_Omega(D)||^2 with D = S - W,
        restricted to the entry shard (all O(p_j))."""
        # w * D_ij = -mu w u_i v_j - w W_ij, with w W_ij = resid + w M_ij
        dw = -(mu) * s.weight * _entrywise_uv(u, v, s.rows, s.cols) - (
            s.resid + s.weight * s.vals
        )
        numer = -jnp.sum(s.resid * dw)
        denom = jnp.sum(dw * dw)
        return numer, denom

    def rmse(self, s: MCState) -> jax.Array:
        """Local RMSE over this shard's (non-padding) observed entries."""
        return jnp.sqrt(
            jnp.sum(s.resid * s.resid) / jnp.maximum(jnp.sum(s.weight), 1.0)
        )
