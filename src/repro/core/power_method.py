"""Distributed power method — the heart of DFW-TRACE (paper Alg. 2, lines 5-10).

The paper's BSP exchange (workers send ``u_{k+1,j} = grad_j @ v_k`` to a master
which aggregates and broadcasts) maps onto SPMD as a ``psum`` over the data
mesh axes: every device holds an implicit shard ``A_j`` of the gradient
``A = sum_j A_j`` and only the O(d+m) iteration vectors cross the network.

All functions are pure and work both serially (``axis_name=None``) and inside
``shard_map`` (``axis_name='data'`` or ``('pod','data')``).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp

# The exact-psum master aggregate goes through the comm layer's chokepoint
# (never a raw lax.psum here — lint rule REP001): the Reducer subsystem owns
# every vector collective so encodings and wire-byte accounting stay in one
# place. comm never imports core, so this is cycle-free.
from ..comm.base import psum as _psum

AxisName = Optional[Union[str, Sequence[str]]]
_EPS = 1e-30


def _exchange(reducer, local: jax.Array, cs, **kw):
    """``reducer.exchange`` under the ``comm.exchange`` name scope, so that
    the device trace names the collective apart from the local product
    (computed by the caller, outside the scope)."""
    with jax.named_scope("comm.exchange"):
        return reducer.exchange(local, cs, **kw)


def _aggregate(local: jax.Array, axis_name: AxisName) -> jax.Array:
    """The exact-psum aggregate under the ``comm.exchange`` name scope."""
    with jax.named_scope("comm.exchange"):
        return _psum(local, axis_name)


class PowerResult(NamedTuple):
    """Top singular triple estimate after K two-sided power iterations."""

    u: jax.Array  # (d,)  left singular vector estimate, unit norm
    v: jax.Array  # (m,)  right singular vector estimate, unit norm
    sigma: jax.Array  # ()  top singular value estimate (= ||A^T u|| >= 0)


class BlockPowerResult(NamedTuple):
    """Top-k singular block estimate after K block power iterations.

    ``u``/``v`` columns pair up as rank-1 atoms (``u_j^T A v_j = sigma_j``;
    the v columns are unit but not mutually orthogonal mid-convergence);
    ``probe`` is the *orthonormalized* right block — the thing to warm-start
    the next epoch's iteration from. ``iters`` counts the iterations that
    actually executed (< K when the adaptive stop fired early)."""

    u: jax.Array  # (d, k) left block, orthonormal columns
    v: jax.Array  # (m, k) right block, unit columns (atom directions)
    sigma: jax.Array  # (k,) singular value estimates (unordered, >= 0)
    probe: jax.Array  # (m, k) orthonormal right block (warm-start carry)
    iters: jax.Array  # () int32 iterations executed


def collective_rounds_contract(num_iters: int, topology=None):
    """The paper's communication budget as a declared, checkable contract:
    K two-sided power iterations execute exactly 2K aggregation rounds
    (one all-reduce per matvec/rmatvec pair side), never 2K+1 — the
    carried-sigma invariant. Consumed by ``tests/test_power_method.py`` and
    ``tools/repro_contracts.py`` against the compiled HLO of a shard_map'd
    ``power_iterations``.

    With a ``topology`` (``repro.comm.Topology``) the 2K exchanges route
    through that graph instead of a flat all-reduce, and the contract pins
    the graph's own collective profile (``ppermute`` rounds for gossip,
    intra+inter split for hier) via ``Topology.collective_contract``."""
    from ..analysis.contracts import Contract  # lazy: analysis is tooling

    if topology is not None:
        return topology.collective_contract(
            2 * num_iters,
            name=(
                f"power_method.collective_rounds"
                f"[K={num_iters},topology={topology.spec}]"
            ),
        )
    return Contract(
        name=f"power_method.collective_rounds[K={num_iters}]",
        collective_counts={"all-reduce": 2.0 * num_iters},
    )


def block_collective_rounds_contract(num_iters: int, k: int, topology=None):
    """Block analogue of ``collective_rounds_contract``: K block iterations
    still execute exactly 2K all-reduce rounds — the (k,k) Gram
    orthogonalization runs on the *already-reduced replicated* block, so
    widening the probe from a vector to k columns multiplies the payload of
    each round by k but never adds a round. ``k`` is part of the name (and
    of wire-byte accounting); the round count is k-free by construction."""
    from ..analysis.contracts import Contract  # lazy: analysis is tooling

    if topology is not None:
        return topology.collective_contract(
            2 * num_iters,
            name=(
                f"power_method.block_collective_rounds"
                f"[K={num_iters},k={k},topology={topology.spec}]"
            ),
        )
    return Contract(
        name=f"power_method.block_collective_rounds[K={num_iters},k={k}]",
        collective_counts={"all-reduce": 2.0 * num_iters},
    )


def sphere_vector(key: jax.Array, m: int, dtype=jnp.float32) -> jax.Array:
    """Uniform random vector on the unit (m-1)-sphere.

    The paper has all workers draw the *same* v0 via a shared seed; in SPMD the
    key is replicated so this holds by construction with zero communication.
    """
    v = jax.random.normal(key, (m,), dtype=dtype)
    return v / (jnp.linalg.norm(v) + _EPS)


def power_iterations(
    matvec: Callable[[jax.Array], jax.Array],
    rmatvec: Callable[[jax.Array], jax.Array],
    v0: jax.Array,
    num_iters: int,
    *,
    axis_name: AxisName = None,
    worker_weight: Optional[jax.Array] = None,
    reducer=None,
    comm_state=None,
    key: Optional[jax.Array] = None,
):
    """Run ``num_iters`` two-sided power iterations on the implicit operator.

    ``matvec(v)``/``rmatvec(u)`` compute the *local* contribution ``A_j v`` /
    ``A_j^T u``; this routine psums them over ``axis_name`` (paper's
    aggregate-and-broadcast) and normalizes. The estimate ``sigma = ||A^T u||``
    is the norm of the *last* aggregated ``rmatvec`` — carried out of the loop,
    never recomputed, so an epoch costs exactly ``2 * num_iters`` collective
    rounds (regression-pinned in tests/test_power_method.py).

    ``worker_weight`` implements straggler mitigation: a 0/1 (or fractional)
    scalar multiplying the local contribution. Because each iteration
    renormalizes, dropping workers only reorients the estimate toward the
    surviving data's gradient — an unbiased LMO for the surviving partition
    (same weighting argument the paper uses for SVA).

    ``reducer`` (a ``repro.comm.Reducer``, or a ``repro.comm.Topology`` —
    anything with the ``exchange`` contract) reroutes the two vector
    aggregations through a compressed collective and/or a non-flat exchange
    graph. Under a per-node topology (gossip) the aggregates differ across
    workers, so ``u``/``v``/``sigma`` become per-node estimates. Default
    ``None`` preserves the exact-psum behavior bit for bit and returns a
    plain ``PowerResult``;
    with a reducer the return is ``(PowerResult, comm_state)`` where
    ``comm_state`` is the reducer's threaded per-worker state (pass the
    previous epoch's back in; ``None`` starts fresh via
    ``reducer.init_state``) and ``key`` feeds stochastic encodings (defaults
    to a constant key — pass a per-epoch key for unbiasedness across epochs).

    The two-sided iteration guarantees ``u^T A v = ||A^T u|| >= 0``, so the
    trace-norm LMO solution is always ``S* = -mu u v^T`` with no sign fix.
    """
    if num_iters < 1:
        raise ValueError(
            f"num_iters={num_iters}: power_iterations needs >= 1 iteration "
            "(0 returns u=0, sigma=0 and silently corrupts the caller)"
        )
    w = 1.0 if worker_weight is None else worker_weight
    d_probe = matvec(v0)  # shapes only; cheap under jit (dead if K>=1 reuses)
    u0 = jnp.zeros_like(d_probe)
    sigma0 = jnp.zeros((), jnp.float32)

    if reducer is None:

        def body(_, carry):
            _, v, _ = carry
            u = _aggregate(w * matvec(v), axis_name)
            u = u / (jnp.linalg.norm(u) + _EPS)
            vv = _aggregate(w * rmatvec(u), axis_name)
            nv = jnp.linalg.norm(vv)
            v = vv / (nv + _EPS)
            return (u, v, nv)

        u, v, sigma = jax.lax.fori_loop(0, num_iters, body, (u0, v0, sigma0))
        return PowerResult(u=u, v=v, sigma=sigma)

    if key is None:
        key = jax.random.PRNGKey(0)
    if comm_state is None:
        comm_state = reducer.init_state(u0.shape[0], v0.shape[0])

    def body(i, carry):
        _, v, _, cs = carry
        ki = jax.random.fold_in(key, i)
        # worker_weight rides along so stateful reducers can tell a masked
        # worker (whose w*matvec is zero but whose residual is not) from a
        # live one — see comm/base.Reducer.exchange.
        uu, cs = _exchange(
            reducer, w * matvec(v), cs, slot="u",
            key=jax.random.fold_in(ki, 0), axis_name=axis_name,
            weight=worker_weight,
        )
        u = uu / (jnp.linalg.norm(uu) + _EPS)
        vv, cs = _exchange(
            reducer, w * rmatvec(u), cs, slot="v",
            key=jax.random.fold_in(ki, 1), axis_name=axis_name,
            weight=worker_weight,
        )
        nv = jnp.linalg.norm(vv)
        v = vv / (nv + _EPS)
        return (u, v, nv, cs)

    u, v, sigma, comm_state = jax.lax.fori_loop(
        0, num_iters, body, (u0, v0, sigma0, comm_state)
    )
    return PowerResult(u=u, v=v, sigma=sigma), comm_state


def orthonormalize_block(b: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Orthonormalize the columns of ``b`` via Cholesky-QR on the (k,k) Gram.

    The Gram ``G = B^T B`` is tiny (k x k) and — in this codebase's BSP
    layout — computed on a block that is already replicated post-all-reduce,
    so the orthogonalization costs zero communication rounds (in a
    row-sharded layout it would cost one (k,k) all-reduce; see
    docs/ALGORITHMS.md). The jitter keeps the factorization defined for
    rank-deficient blocks; an all-zero block maps to an all-zero block. It
    is ``eps`` times the Gram's trace, which bounds the Gram's f32
    rounding: the null eigenvalues of a rank-10 block of width 32 round
    down to -7e-8 of the trace at d = 1000, more than ``eps`` times the
    mean eigenvalue (3e-8 of the trace) would cover.
    """
    k = b.shape[-1]
    # f32 Gram: TPU's default precision would round B to bf16 before the
    # Cholesky factorization.
    g = jnp.dot(b.T, b, precision=jax.lax.Precision.HIGHEST)
    jitter = eps * jnp.trace(g) + 1e-30
    chol = jnp.linalg.cholesky(g + jitter * jnp.eye(k, dtype=b.dtype))
    # B @ inv(L)^T via one triangular solve of the (k, n) system.
    return jax.scipy.linalg.solve_triangular(chol, b.T, lower=True).T


def _orth(b: jax.Array) -> jax.Array:
    """``orthonormalize_block`` under the ``dfw.orth`` scope, so the device
    trace splits the block LMO's k-wide algebra from its kernels."""
    with jax.named_scope("dfw.orth"):
        return orthonormalize_block(b)


def block_power_step(
    matmat: Callable[[jax.Array], jax.Array],
    rmatmat: Callable[[jax.Array], jax.Array],
    q: jax.Array,
    *,
    reduce: Callable[[jax.Array], jax.Array] = lambda x: x,
) -> tuple:
    """One warm-started half-pair of block power iteration: ``p =
    orth(reduce(A q)); q' = reduce(A^T p)``. Returns ``(p, q')``.

    This is the shared primitive between the FW block LMO below and
    PowerSGD gradient compression (``optim/compression.py``): both do
    exactly one aggregated-matmat -> Gram-orthonormalize -> aggregated-
    rmatmat step per call, warm-starting ``q`` from the previous round.
    ``reduce`` is the aggregation (psum for the LMO, pmean for PowerSGD's
    averaged gradients; identity when serial)."""
    p = orthonormalize_block(reduce(matmat(q)))
    return p, reduce(rmatmat(p))


def block_power_iterations(
    matvec: Callable[[jax.Array], jax.Array],
    rmatvec: Callable[[jax.Array], jax.Array],
    v0: jax.Array,
    num_iters: int,
    *,
    axis_name: AxisName = None,
    worker_weight: Optional[jax.Array] = None,
    reducer=None,
    comm_state=None,
    key: Optional[jax.Array] = None,
    adapt_rtol: Optional[float] = None,
    adapt_ref: Optional[jax.Array] = None,
    matmat: Optional[Callable[[jax.Array], jax.Array]] = None,
    rmatmat: Optional[Callable[[jax.Array], jax.Array]] = None,
):
    """Distributed *block* power iteration: ``(d,k)``/``(m,k)`` probe blocks
    instead of vectors — the rank-k LMO engine of the ``block:k`` solver
    tier (BlockFW, arXiv:1708.02105).

    Per iteration: all-reduce the local block matvec (flattened through the
    ``Reducer`` contract, so int8/topk encodings compose unchanged),
    Cholesky-QR orthonormalize the replicated result against its (k,k)
    Gram, all-reduce the block rmatvec, read per-column sigmas off it, and
    orthonormalize again for the next round — exactly ``2 * num_iters``
    collective rounds, the same count as ``power_iterations`` with k-times
    wider payloads (``block_collective_rounds_contract``).

    ``v0`` is the (m, k) starting block — a previous epoch's converged
    probe for warm starts (it is re-orthonormalized here, so any
    nonzero-column block is a valid start). ``adapt_rtol`` enables the
    spectral-gap-adaptive stop: once the largest per-column sigma change of
    an iteration falls below ``adapt_rtol * max(adapt_ref, max sigma)``,
    the remaining iterations become ``lax.cond`` no-ops — the static HLO
    round count stays 2K, the executed matvecs and collectives stop.
    Callers pass ``adapt_ref`` as the scale on which the duality-gap
    certificate lives (the FW epoch uses ``|<W, grad>| / mu``), so
    iterations are spent only while they still move the certificate.

    ``matmat``/``rmatmat`` are the local block products ``A_j V`` /
    ``A_j^T U`` where the caller has them as one product (one pass over the
    data for all k columns); without them the block products are ``vmap``s
    of ``matvec``/``rmatvec``, k vector products.

    Always returns ``(BlockPowerResult, comm_state)`` (``reducer=None``
    uses the exact dense psum with ``()`` state).
    """
    if num_iters < 1:
        raise ValueError(
            f"num_iters={num_iters}: block_power_iterations needs >= 1 "
            "iteration (0 returns a zero block and corrupts the caller)"
        )
    if v0.ndim != 2:
        raise ValueError(f"v0 must be (m, k), got shape {v0.shape}")
    if reducer is None:
        from ..comm.base import DenseReducer  # leaf import; no cycle

        reducer = DenseReducer()
    if key is None:
        key = jax.random.PRNGKey(0)
    m, k = v0.shape
    w = 1.0 if worker_weight is None else worker_weight
    if matmat is None:
        matmat = jax.vmap(matvec, in_axes=1, out_axes=1)
    if rmatmat is None:
        rmatmat = jax.vmap(rmatvec, in_axes=1, out_axes=1)
    d = matmat(v0).shape[0]  # shapes only; dead under jit (loop recomputes)
    if comm_state is None:
        comm_state = reducer.init_state(d * k, m * k)

    u0 = jnp.zeros((d, k), v0.dtype)
    sigma0 = jnp.zeros((k,), jnp.float32)
    va0 = v0 / (jnp.linalg.norm(v0, axis=0, keepdims=True) + _EPS)
    init = (u0, _orth(v0), va0, sigma0, comm_state,
            jnp.zeros((), jnp.bool_), jnp.zeros((), jnp.int32))

    def live(i, c):
        _, v, _, sigma, cs, done, iters = c
        ki = jax.random.fold_in(key, i)
        uu, cs = _exchange(
            reducer, (w * matmat(v)).reshape(-1), cs, slot="u",
            key=jax.random.fold_in(ki, 0), axis_name=axis_name,
            weight=worker_weight,
        )
        ub = _orth(uu.reshape(d, k))
        vv, cs = _exchange(
            reducer, (w * rmatmat(ub)).reshape(-1), cs, slot="v",
            key=jax.random.fold_in(ki, 1), axis_name=axis_name,
            weight=worker_weight,
        )
        vv = vv.reshape(m, k)
        sig = jnp.linalg.norm(vv, axis=0)
        v_atoms = vv / (sig[None, :] + _EPS)
        if adapt_rtol is not None:
            ref = jnp.max(sig)
            if adapt_ref is not None:
                ref = jnp.maximum(ref, adapt_ref)
            done = done | (
                jnp.max(jnp.abs(sig - sigma)) <= adapt_rtol * (ref + _EPS)
            )
        return (ub, _orth(vv), v_atoms, sig, cs, done,
                iters + 1)

    def body(i, c):
        # Once the adaptive criterion fires, remaining iterations are
        # no-ops: the static collective count stays 2K (cond branches are
        # counted once by analysis/hlo), the executed work stops.
        return jax.lax.cond(c[5], lambda c: c, lambda c: live(i, c), c)

    u, v_next, v_atoms, sigma, comm_state, _, iters = jax.lax.fori_loop(
        0, num_iters, body, init
    )
    return (
        BlockPowerResult(u=u, v=v_atoms, sigma=sigma, probe=v_next,
                         iters=iters),
        comm_state,
    )


def power_method_dense(
    a: jax.Array,
    key: jax.Array,
    num_iters: int,
    *,
    axis_name: AxisName = None,
) -> PowerResult:
    """Power method on an explicit (possibly sharded-by-rows-of-n) matrix."""
    return power_iterations(
        lambda v: a @ v,
        lambda u: a.T @ u,
        sphere_vector(key, a.shape[1], a.dtype),
        num_iters,
        axis_name=axis_name,
    )


@functools.partial(jax.jit, static_argnames=("num_iters",))
def top_singular_pair(a: jax.Array, key: jax.Array, num_iters: int = 50) -> PowerResult:
    """Serial oracle used by tests and NAIVE-DFW (exact-ish for modest K)."""
    return power_method_dense(a, key, num_iters)
