"""DFW-TRACE driver (paper Algorithm 2).

``make_epoch_step`` builds one jit-able FW epoch: distributed power method on
the implicit gradient -> step size (default 2/(t+2) or closed-form line
search) -> sufficient-information update + factored-iterate append. The same
function runs serially (axis_name=None) or inside shard_map over the data mesh
axes — the paper's BSP master is just ``psum``.

**Unified carry.** Every epoch consumes and produces one ``EpochCarry``
``(state, iterate, comm_state, t, key)``. ``comm_state`` is always present —
an empty pytree ``()`` for the exact-psum dense reducer — so there is a single
epoch signature regardless of the collective encoding; no caller branches on
whether a reducer is installed.

Execution lives in ``core/engine.py``: the schedule K(t) is partitioned into
maximal constant-K segments and each segment runs as one ``jax.lax.scan`` over
epochs, so a whole ``const:K`` run is a single jit dispatch with host
transfers only at segment boundaries. ``fit`` below is the serial /
single-process driver on top of that engine; the multi-worker driver (mesh
build, row-wise state sharding, worker sampling, the up-front kernel check)
lives in ``launch/dfw.py``.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import low_rank
from .power_method import (
    AxisName,
    block_power_iterations,
    orthonormalize_block,
    power_iterations,
    sphere_vector,
)
from .trace_norm import duality_gap

# Scalar psums (loss, <W,grad>, line-search terms) stay *exact* by design —
# see comm/base.py — but still route through the comm chokepoint rather than
# raw lax.psum (lint rule REP001), so collective call sites stay auditable.
from ..comm.base import pmax as _pmax
from ..comm.base import psum as _psum

# Solver-spec grammar lives in the shared ``repro.specs`` module (one
# SpecError style across the solver/comm/topology axes); re-exported here
# because this module coined ``parse_solver`` and call sites import it from
# here.
from ..specs import SolverSpec, parse_solver  # noqa: F401

PyTree = Any


class EpochAux(NamedTuple):
    loss: jax.Array  # F(W^t) (pre-update), psum'd
    gap: jax.Array  # duality-gap estimate at W^t
    sigma: jax.Array  # power-method top-singular-value estimate
    gamma: jax.Array  # step size actually taken
    piters: jax.Array  # power iterations actually executed (float32 scalar)


class EpochCarry(NamedTuple):
    """Everything one FW epoch threads to the next — the single epoch
    signature shared by the serial and sharded drivers.

    ``comm_state`` is the reducer's per-worker state pytree (``()`` for the
    dense exact-psum reducer — always present so the carry's structure never
    depends on the collective encoding). ``t`` is the on-device epoch counter
    (int32, so it can live inside ``lax.scan``); ``key`` is the replicated
    run PRNG key — each epoch folds ``t`` in, never splits it, so the carry
    key is constant across epochs (the paper's shared-seed trick).
    ``probe`` is the block solver's warm-start carry — the previous epoch's
    converged (m, k) right singular block, replicated, handed to the next
    epoch's block power iteration at zero communication cost. For the rank1
    solver it is the empty pytree ``()``, so rank1 carries (and their v1
    checkpoints, which restore leaves by order) keep their exact leaf layout.
    """

    state: PyTree  # task sufficient-information state (per-worker shard)
    iterate: low_rank.FactoredIterate  # replicated factored W
    comm_state: PyTree  # reducer per-worker state; () when dense
    t: jax.Array  # () int32 epoch counter
    key: jax.Array  # replicated PRNG key
    probe: PyTree = ()  # block-solver warm-start (m, k) block; () for rank1


def init_carry(
    state: PyTree,
    iterate: low_rank.FactoredIterate,
    key: jax.Array,
    comm_state: PyTree = (),
    t: int = 0,
    probe: PyTree = (),
) -> EpochCarry:
    """Carry at epoch ``t`` (0 for a fresh run; a checkpoint's saved epoch
    counter when resuming), comm state defaulting to dense's ()."""
    return EpochCarry(
        state=state, iterate=iterate, comm_state=comm_state,
        t=jnp.full((), t, jnp.int32), key=key, probe=probe,
    )


# ---------------------------------------------------------------------------
# Solver tiers
# ---------------------------------------------------------------------------

#: Relative tolerance for the spectral-gap-adaptive block power iteration:
#: once no estimated singular value moved by more than ADAPT_RTOL relative to
#: the gap certificate's scale, further iterations cannot change the FW step
#: materially and the remaining K budget is skipped on device.
ADAPT_RTOL = 0.05


def solver_probe_shape(spec, m: int) -> Optional[tuple]:
    """Shape of the warm-start probe leaf carried in ``EpochCarry.probe`` for
    this solver, or ``None`` when the solver carries no probe (rank1)."""
    s = parse_solver(spec)
    return (m, s.k) if s.kind == "block" else None


def init_probe(spec, m: int, key: Optional[jax.Array] = None) -> PyTree:
    """Cold-start probe for a fresh run, ``()`` for rank1. For the block
    solver an orthonormal (m, k) block drawn from epoch 0's key
    ``fold_in(key, 0)``, as the rank-1 solver draws its epoch-0 vector, so
    a run's key picks its start; the key is replicated, so every worker
    agrees without communication. Without a key (shapes and tests), a fixed
    seed."""
    shape = solver_probe_shape(spec, m)
    if shape is None:
        return ()
    if key is None:
        key = jax.random.PRNGKey(0x5EED)
    return orthonormalize_block(jax.random.normal(jax.random.fold_in(key, 0), shape))


# ---------------------------------------------------------------------------
# K(t) schedules (paper Thm 2 + experimental settings §5)
# ---------------------------------------------------------------------------


def k_schedule(name: str) -> Callable[[int], int]:
    """Power-iteration schedules. Names mirror the paper's variants:

    - ``const:K``   DFW-TRACE-K (K(t) = K; paper uses 1 and 2)
    - ``log``       DFW-TRACE-log, K(t) = floor(1 + ln(t+1))
    - ``log_half``  K(t) = floor(1 + 0.5 ln(t+1))  (paper's logistic setting)
    - ``linear:c``  Thm 2 part 1 regime, K(t) = 1 + ceil(c (t+2))

    Every schedule must yield K(t) >= 1: zero power iterations returns the
    u=0, sigma=0 placeholder from ``power_iterations`` and silently corrupts
    both the FW update and the duality gap, so K=0 configurations are
    rejected here rather than failing downstream.
    """
    if name.startswith("const:"):
        k = int(name.split(":")[1])
        if k < 1:
            raise ValueError(
                f"K schedule {name!r}: K must be >= 1 (K=0 yields a zero LMO "
                "direction and a meaningless duality gap)"
            )
        return lambda t: k
    if name == "log":
        return lambda t: int(1 + math.log(t + 1))
    if name == "log_half":
        return lambda t: max(1, int(1 + 0.5 * math.log(t + 1)))
    if name.startswith("linear:"):
        c = float(name.split(":")[1])  # REP002-ok: parsing a schedule string
        if c <= 0:
            raise ValueError(
                f"K schedule {name!r}: slope c must be > 0 so K(t) >= 1"
            )
        return lambda t: 1 + int(math.ceil(c * (t + 2)))
    raise ValueError(f"unknown K schedule: {name!r}")


# ---------------------------------------------------------------------------
# One FW epoch
# ---------------------------------------------------------------------------


def make_epoch_step(
    task,
    mu: float,
    num_power_iters: int,
    *,
    step_size: str = "default",
    axis_name: AxisName = None,
    reducer=None,
    solver="rank1",
) -> Callable:
    """Returns ``epoch(carry, worker_weight=None) -> (carry, aux)``.

    ``num_power_iters`` is static (compile-time); the engine compiles one
    scan per distinct K(t) segment — a handful of compilations for the log
    schedule. ``worker_weight`` is the straggler mask (see power_method
    docstring); ``None`` means full participation.

    ``reducer`` (``repro.comm.Reducer``) selects the encoding of the power
    method's *vector* collectives; ``None`` means the exact f32 psum
    (``comm.DenseReducer``), whose per-worker state is the empty pytree — the
    carry structure is identical under every encoding. The scalar psums below
    — loss, <W, grad>, the line-search numerator/denominator — always stay
    exact: they are O(1) on the wire, and corrupting them would bias the step
    size and the duality-gap certificate rather than just the LMO direction.

    ``solver`` selects the LMO tier (see ``parse_solver``): ``"rank1"`` is
    the paper's single-atom power method; ``"block:K[:adapt][:cold]"`` is
    the BlockFW tier — a rank-K block power iteration whose k atoms are
    blended into one feasible direction and appended together, with the
    converged right block carried in ``EpochCarry.probe`` as next epoch's
    warm start.
    """
    if step_size not in ("default", "linesearch"):
        raise ValueError(step_size)
    if step_size == "linesearch" and not hasattr(task, "linesearch_terms"):
        raise ValueError(f"{type(task).__name__} has no closed-form line search")
    if num_power_iters < 1:
        raise ValueError(
            f"num_power_iters={num_power_iters}: at least one power iteration "
            "is required (K=0 would feed a zero singular direction to the LMO)"
        )
    sspec = parse_solver(solver)
    if reducer is None:
        from ..comm.base import DenseReducer  # leaf import; no cycle

        reducer = DenseReducer()
    # Per-node topologies (comm.GossipTopology) leave every worker with its
    # own inexact-consensus LMO direction, so sigma/gap become per-node
    # quantities. The aux stays replicated (engine out_specs demand it) by
    # taking the pmax: gap <= tol then certifies *every* node's iterate —
    # the conservative decentralized stopping rule.
    per_node = bool(getattr(reducer, "per_node", False))  # REP002-ok: host attribute
    if per_node and sspec.kind == "block":
        raise ValueError(
            "per-node topologies (gossip) support only the rank1 solver: the "
            "block tier orthonormalizes against a consensus block, which a "
            "master-less exchange cannot provide — use topology='flat' or "
            "'hier:g' with solver='block:k'"
        )

    def epoch(carry: EpochCarry, worker_weight: Optional[jax.Array] = None):
        state, it = carry.state, carry.iterate
        ti = jnp.asarray(carry.t, jnp.int32)
        t = ti.astype(jnp.float32)
        # All shards derive the same v0 from the replicated key (paper's
        # shared-seed trick: zero communication). The reducer key is a
        # distinct stream from v0's: fold the epoch index, then a tag.
        ekey = jax.random.fold_in(carry.key, ti)
        ckey = jax.random.fold_in(ekey, 0xC033)
        w = 1.0 if worker_weight is None else worker_weight
        # Named scopes mark each phase in the HLO metadata, so the device
        # trace splits an epoch's time into loss, power, exchange, line
        # search and update (docs/OBSERVABILITY.md).
        with jax.named_scope("dfw.loss"):
            loss = _psum(w * task.local_loss(state), axis_name)
            inner = _psum(w * task.inner_w_grad(state), axis_name)

        if sspec.kind == "block":
            k = sspec.k
            with jax.named_scope("dfw.power"):
                # Fresh random columns every epoch; the carried probe (when
                # warm) replaces them entirely. Mixing a small random
                # component back in would also work but breaks block:1 ==
                # rank1 equivalence.
                rand0 = jnp.stack(
                    [sphere_vector(jax.random.fold_in(ekey, 101 + j), task.m)
                     for j in range(k)],
                    axis=1,
                )
                if sspec.cold or not isinstance(carry.probe, jax.Array):
                    v0 = rand0
                else:
                    # Warm start from last epoch's converged right block; any
                    # numerically dead column (all-zero from init skeletons)
                    # falls back to its random column.
                    col_norm = jnp.linalg.norm(carry.probe, axis=0, keepdims=True)
                    v0 = jnp.where(col_norm > 1e-6, carry.probe, rand0)
                res, comm_state = block_power_iterations(
                    partial(task.matvec, state),
                    partial(task.rmatvec, state),
                    v0,
                    num_power_iters,
                    axis_name=axis_name,
                    worker_weight=worker_weight,
                    reducer=reducer,
                    comm_state=carry.comm_state,
                    key=ckey,
                    adapt_rtol=ADAPT_RTOL if sspec.adaptive else None,
                    # Scale for "did refinement stop mattering": the gap
                    # certificate is inner + mu*sigma_max, so changes small
                    # relative to |inner|/mu (or sigma itself) can't move it.
                    adapt_ref=jnp.abs(inner) / mu,
                    matmat=partial(task.matmat, state),
                    rmatmat=partial(task.rmatmat, state),
                )
                sigma_max = jnp.max(res.sigma)
                gap = duality_gap(inner, sigma_max, mu)
                # Blend the k atoms into one feasible direction
                # S = -mu sum_j c_j u_j v_j^T with c = sigma / sum(sigma):
                # the trace-ball-normalized top-k projection of -grad
                # (sum c = 1 keeps ||S||_* <= mu). Fold c into u's columns
                # so tasks see the same (u, v) signature as rank1.
                c = res.sigma / (jnp.sum(res.sigma) + 1e-30)
                u_c = res.u * c[None, :]
            with jax.named_scope("dfw.linesearch"):
                if step_size == "linesearch":
                    numer, denom = task.linesearch_terms(state, u_c, res.v, mu)
                    numer = _psum(w * numer, axis_name)
                    denom = _psum(w * denom, axis_name)
                    gamma = jnp.clip(numer / jnp.maximum(denom, 1e-30), 0.0, 1.0)
                else:
                    gamma = 2.0 / (t + 2.0)
            with jax.named_scope("dfw.update"):
                state = task.update(state, u_c, res.v, gamma, mu)
                it = low_rank.fw_update_block(it, res.u, res.v, c, gamma, mu)
            aux = EpochAux(
                loss=loss, gap=gap, sigma=sigma_max, gamma=gamma,
                piters=res.iters.astype(jnp.float32),
            )
            return EpochCarry(
                state=state, iterate=it, comm_state=comm_state,
                t=ti + 1, key=carry.key, probe=res.probe,
            ), aux

        with jax.named_scope("dfw.power"):
            v0 = sphere_vector(ekey, task.m)
            res, comm_state = power_iterations(
                partial(task.matvec, state),
                partial(task.rmatvec, state),
                v0,
                num_power_iters,
                axis_name=axis_name,
                worker_weight=worker_weight,
                reducer=reducer,
                comm_state=carry.comm_state,
                key=ckey,
            )

            gap = duality_gap(inner, res.sigma, mu)
            sigma = res.sigma
            if per_node:
                # inner/loss are exact global psums (already replicated);
                # only the gossip-estimated sigma — and hence the gap —
                # differs per node. pmax makes both replicated: the recorded
                # gap upper-bounds every node's certificate, so early stop
                # fires only when ALL nodes are within tol.
                gap = _pmax(gap, axis_name)
                sigma = _pmax(sigma, axis_name)

        with jax.named_scope("dfw.linesearch"):
            if step_size == "linesearch":
                numer, denom = task.linesearch_terms(state, res.u, res.v, mu)
                numer = _psum(w * numer, axis_name)
                denom = _psum(w * denom, axis_name)
                gamma = jnp.clip(numer / jnp.maximum(denom, 1e-30), 0.0, 1.0)
            else:
                gamma = 2.0 / (t + 2.0)

        with jax.named_scope("dfw.update"):
            state = task.update(state, res.u, res.v, gamma, mu)
            it = low_rank.fw_update(it, res.u, res.v, gamma, mu)
        aux = EpochAux(
            loss=loss, gap=gap, sigma=sigma, gamma=gamma,
            piters=jnp.full((), num_power_iters, jnp.float32),
        )
        return EpochCarry(
            state=state, iterate=it, comm_state=comm_state,
            t=ti + 1, key=carry.key, probe=carry.probe,
        ), aux

    return epoch


# ---------------------------------------------------------------------------
# Serial / single-process driver (tests, examples, benchmarks)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FitResult:
    """``history`` entries are *pre-update* measurements (see ``fit``);
    ``final_loss`` is F at the *returned* iterate — use it when reporting
    the quality of the fitted model. ``epochs_run`` < the requested epoch
    count when the gap certificate stopped the run early; histories are
    truncated to it. ``stats`` are the engine's dispatch/compile/host-sync
    counters (see ``core/engine.py``)."""

    iterate: low_rank.FactoredIterate
    state: PyTree
    history: Dict[str, list]
    final_loss: float = float("nan")
    epochs_run: int = 0
    stats: Dict[str, int] = dataclasses.field(default_factory=dict)


def fit(
    task,
    state: PyTree,
    *,
    mu: float,
    num_epochs: int,
    key: jax.Array,
    schedule: str = "const:2",
    step_size: str = "default",
    axis_name: AxisName = None,
    segment_wrapper: Optional[Callable[[Callable], Callable]] = None,
    callback: Optional[Callable[[int, EpochAux], None]] = None,
    reducer=None,
    max_rank: Optional[int] = None,
    gap_tol: Optional[float] = None,
    block_epochs: Optional[int] = None,
    mode: str = "scan",
    iterate: Optional[low_rank.FactoredIterate] = None,
    comm_state: Optional[PyTree] = None,
    start_t: int = 0,
    initial_history: Optional[Dict[str, list]] = None,
    checkpointer=None,
    telemetry=None,
    num_workers: int = 1,
    solver: str = "rank1",
    probe: PyTree = None,
) -> FitResult:
    """Run DFW-TRACE for up to ``num_epochs`` on the device-resident engine.

    **History contract.** ``history[key][t]`` records epoch t's measurements
    at W^t *before* that epoch's update — the loss/gap the power method and
    step size were computed against (matching the paper's per-epoch
    trajectories). The loss of the *returned* iterate never appears in
    ``history``; it is exposed as ``FitResult.final_loss`` (the psum'd
    ``task.local_loss`` of the returned state). Benchmarks that report
    "final loss" must use ``final_loss``, not ``history["loss"][-1]``
    (which is one epoch stale).

    ``max_rank`` sizes the factored-iterate store (one factor is appended
    per epoch, so it must be >= ``num_epochs``; default exactly
    ``num_epochs``) — the same capacity contract ``launch/dfw.DFWConfig``
    exposes.

    ``gap_tol`` stops the run once the psum'd duality-gap certificate
    satisfies ``gap <= gap_tol`` (paper Thm 2's stopping rule), checked on
    device every epoch and acted on at segment granularity;
    ``FitResult.epochs_run`` records how many epochs actually executed and
    all histories are truncated to it. ``block_epochs`` caps the scan
    segment length, bounding how many epochs can run past the certificate.

    ``callback(start_t, aux_block)`` fires once per **segment** (not per
    epoch): ``aux_block`` is an ``EpochAux`` of host numpy arrays covering
    epochs ``start_t .. start_t + len - 1``; rows after an early stop are
    NaN. Per-epoch callbacks would force a device->host sync every epoch —
    exactly the overhead the engine exists to remove. Each callback
    invocation does force one segment-boundary sync, so leave it ``None``
    on the hot path.

    ``segment_wrapper`` contract: ``wrap(seg_fn) -> seg_fn'`` applied to
    each segment function before ``jax.jit`` (one wrap per distinct
    (K, length) pair). The canonical non-trivial wrapper is shard_map over
    the data mesh — see ``engine.shard_map_segment_wrapper``, which
    ``core/dfw_head.sharded_fit`` and ``launch/dfw.fit`` install, paired
    with ``axis_name`` naming the mesh axes so the epoch's psums resolve.

    ``reducer`` routes the power method's vector collectives through a
    compressed encoding (``repro.comm``); serially this *simulates* the
    compression noise of a distributed run (axis_name=None sums one worker),
    which is what the convergence-vs-bits benchmarks sweep. ``None`` is the
    exact dense psum. ``mode="legacy"`` runs the pre-engine per-epoch
    dispatch loop (one jit call + four blocking scalar transfers per epoch)
    — kept as the equivalence/off-device-overhead baseline; ``"scan"`` is
    the production path.

    ``checkpointer`` (``repro.checkpoint.dfw.RunCheckpointer``) saves the
    full run carry asynchronously at segment boundaries; to resume, pass
    the restored carry fields back in — ``state``/``iterate``/
    ``comm_state``/``key`` from the checkpoint, ``start_t`` its epoch,
    ``initial_history`` its history — and the run continues bit-exactly
    (see ``core/engine.run_epochs`` and ``tests/test_checkpoint_resume``;
    ``launch/dfw.fit`` wires this end to end via ``DFWConfig.resume_from``).

    ``telemetry`` (``repro.obs.Telemetry``; inert default) is handed to the
    engine for its zero-sync span/metric stream and brackets the final-loss
    eval here; ``num_workers`` only scales the analytic comm byte
    accounting — it never changes the math.

    ``solver`` selects the LMO tier (``parse_solver`` grammar). For the
    block tier, ``probe`` optionally resumes the warm-start block from a
    checkpoint (``None`` cold-starts from the run's key); an epoch appends
    k factors, so ``max_rank`` defaults to ``num_epochs * k``.
    """
    from .engine import final_loss, run_epochs  # engine builds on this module
    from ..obs import Telemetry

    tel = telemetry if telemetry is not None else Telemetry.noop()
    eres = run_epochs(
        task,
        state,
        mu=mu,
        num_epochs=num_epochs,
        key=key,
        schedule=schedule,
        step_size=step_size,
        axis_name=axis_name,
        reducer=reducer,
        iterate=iterate,
        comm_state=comm_state,
        max_rank=max_rank,
        gap_tol=gap_tol,
        block_epochs=block_epochs,
        segment_wrapper=segment_wrapper,
        callback=callback,
        mode=mode,
        start_t=start_t,
        initial_history=initial_history,
        checkpointer=checkpointer,
        telemetry=tel,
        num_workers=num_workers,
        solver=solver,
        probe=probe,
    )
    if checkpointer is not None:
        # Join the last async write so its failure surfaces with the run,
        # not silently at interpreter exit.
        with tel.span("checkpoint.join", "checkpoint"):
            checkpointer.wait()
    # Loss at the *returned* iterate (cheap: one O(n_j) reduction outside the
    # epoch; on sharded state the plain sum is already the global loss).
    with tel.span("engine.final_loss", "engine"):
        loss, reused = final_loss(task, eres.carry.state, tel)
    eres.stats["dispatches"] += 1
    eres.stats["host_syncs"] += 1
    eres.stats["compilations"] += 1
    eres.stats["programs_reused"] += reused
    if tel.enabled:
        tel.registry.gauge("dfw.final_loss").set(loss)
    return FitResult(
        iterate=eres.carry.iterate,
        state=eres.carry.state,
        history=eres.history,
        final_loss=loss,
        epochs_run=eres.epochs_run,
        stats=eres.stats,
    )
