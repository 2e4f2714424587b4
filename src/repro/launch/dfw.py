"""Distributed DFW-Trace execution layer (paper Algorithm 2, end to end).

``core/frank_wolfe.py`` builds the *math* of one FW epoch and
``core/engine.py`` the device-resident execution engine (scan-compiled K(t)
segments, unified ``EpochCarry``, gap-based early stop); this module builds
the *machine* around them:

- a 1-D data mesh over the available devices (``launch/mesh.py``),
- row-wise sharding of the task state across workers (each worker owns a
  contiguous shard of the sample axis, exactly the paper's data partition),
- the BSP master realized as ``psum`` inside ``shard_map`` — per epoch only
  the O(d+m) power-iteration vectors cross the network, never a d x m
  gradient (paper Table 1),
- the paper's straggler/sampled-worker mode: a per-epoch Bernoulli schedule
  over workers precomputed as a (num_epochs, nw) weight array, indexed
  inside the engine's scan,
- an up-front check that the task's kernel products (its ``kernels``
  route: Pallas on TPU, the jnp reference elsewhere) agree with its f32
  maths (``verify_kernels``),
- matrix-completion data layout: ``shard_observations`` partitions the
  observed entries into row-block worker shards padded to equal sizes with
  zero-weight no-op entries (static shapes under shard_map).

The serial driver (``frank_wolfe.fit``) and this sharded driver execute the
same engine; they differ only in the ``segment_wrapper`` layer (shard_map
over the data mesh), so their loss/gap trajectories agree to
float-summation-order tolerance. A ``const:K`` run is a single jit dispatch
with O(1) device->host transfers; ``gap_tol`` stops runs on the duality-gap
certificate at segment granularity.

Typical use (8 simulated hosts; see ``examples/distributed_dfw.py``)::

    from repro.launch import dfw
    cfg = dfw.DFWConfig(mu=1.0, num_epochs=20, schedule="log",
                        step_size="linesearch", sample_prob=0.8,
                        gap_tol=1e-3)
    res = dfw.fit(task, x, y, cfg=cfg, key=key, num_workers=8)
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import comm as comm_lib, specs
from ..checkpoint import dfw as ckpt
from ..compat import shard_map_compat
from ..core import engine, frank_wolfe, low_rank
from ..core.frank_wolfe import EpochAux
from ..obs import Telemetry
from ..core.power_method import sphere_vector
from . import mesh as mesh_lib

PyTree = Any


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DFWConfig:
    """Knobs of one distributed DFW-Trace run.

    ``sample_prob`` < 1 enables the paper's sampled-worker/straggler mode:
    each epoch, every worker participates independently with this probability
    (at least one worker is always kept). ``reweight`` scales the survivors
    by num_workers/num_alive so psum'd aggregates (loss, gap, line-search
    terms) remain estimates of the full-data quantities.

    ``solver`` selects the LMO tier (``frank_wolfe.parse_solver`` grammar):
    ``"rank1"`` is the paper's single-atom power method;
    ``"block:K[:adapt][:cold]"`` the linear-convergence BlockFW tier — a
    rank-K block power iteration appending K atoms per epoch, warm-started
    from the previous epoch's converged right block (``:cold`` disables the
    warm start for ablations, ``:adapt`` stops power iterations early once
    they no longer move the gap certificate). ``max_rank`` then defaults to
    ``num_epochs * K``.

    ``comm`` selects the collective encoding for the power method's vector
    exchanges (``repro.comm``): "dense" (exact f32 psum), "int8"
    (stochastic-rounding s8 psum, ~4x fewer wire bytes), or "topk:r" (top-r
    sparsification with per-worker error feedback). Scalar aggregates stay
    exact under every setting. Applies to all three tasks — the reducer
    wraps the psum, not the task.

    ``topology`` selects the *graph* those exchanges flow over
    (``repro.comm.make_topology`` grammar): "flat" (one global all-reduce
    domain — bit-exact legacy behavior), "ring"/"gossip:k" (master-less
    neighbor averaging; every worker evolves its own iterate and the
    recorded gap is the pmax over the per-node certificates, so early stop
    fires only when all nodes are within ``gap_tol``; requires
    ``comm="dense"`` and ``solver="rank1"``), or "hier:g" (two-level
    reduce: exact psum inside each of g groups, ``comm``-encoded exchange
    across groups — bit-exact vs flat under "dense", and the composition
    point for int8/topk at scale). ``gossip_rounds`` overrides the number
    of mixing rounds per exchange (default: auto-sized from the gossip
    matrix's spectral gap to hit ~1% consensus error). The two axes are
    orthogonal; ``repro.specs.validate`` rejects the meaningless corners.

    ``gap_tol`` stops the run once the psum'd duality-gap certificate
    satisfies ``gap <= gap_tol`` (checked on device every epoch, acted on at
    segment granularity — see ``core/engine.py``); the result records
    ``epochs_run`` and truncates histories to it. Under a compressed
    ``comm`` the certificate inherits the sigma estimate's noise, so treat
    the stop as approximate there. ``block_epochs`` caps the scan segment
    length, bounding both the early-stop overshoot and the staleness of a
    progress ``callback``. ``engine`` selects the execution mode: "scan"
    (production: one dispatch per K(t) segment) or "legacy" (per-epoch
    dispatch + blocking scalar pulls; the overhead baseline).

    **Fault tolerance.** ``checkpoint_dir`` makes the run durable: at every
    ``checkpoint_every``-th segment boundary the full run carry (task
    state, factored iterate, per-worker comm state, epoch counter, PRNG
    key) plus history and the straggler-mask schedule are written
    asynchronously (``repro.checkpoint``), keeping the newest
    ``checkpoint_keep`` steps. The run *owns* the directory's timeline: a
    fresh run clears any previous run's steps, and a resume drops steps
    past its resume point, so the latest step is always this run's.
    ``resume_from`` (a checkpoint directory; ``resume_step`` picks an
    exact step, default latest) restarts a run from its last durable
    boundary:

    - **bit-exact** when the worker count and ``comm`` mode are unchanged —
      the resumed trajectory equals the uninterrupted one bit for bit;
    - **elastic** when the worker count differs — the row-blocked task
      state is re-sharded onto the new mesh, per-worker comm state is
      re-initialized, the mask schedule is re-drawn, and the run converges
      to the same solution (within float-summation-order noise);
    - **warm restart**: ``gap_tol``, ``schedule``, ``num_epochs``, and
      ``comm`` may all differ from the checkpointed run's — the new values
      apply from the resume point (a changed ``comm`` re-initializes
      reducer state, costing exactness but not correctness).

    Note ``block_epochs`` bounds the work a crash can lose: an unbroken
    ``const:K`` run is a single segment and only checkpoints at its end.

    **Telemetry.** ``telemetry`` (a ``repro.obs.Telemetry``; None = inert
    no-op) turns on the zero-sync observability spine: engine segment/
    dispatch spans, per-epoch loss/gap/sigma/gamma samples riding the
    existing boundary fetches, analytic + HLO comm byte accounting,
    checkpoint save/prune spans, and — when the handle's ``profiler_dir``
    is set — a ``jax.profiler`` XLA capture bracketing the epoch loop.
    Export with ``telemetry.write_jsonl(...)`` /
    ``telemetry.write_chrome_trace(...)`` after the run
    (docs/OBSERVABILITY.md).
    """

    mu: float
    num_epochs: int
    schedule: str = "const:2"  # K(t); see frank_wolfe.k_schedule
    step_size: str = "default"  # "default" (2/(t+2)) or "linesearch"
    solver: str = "rank1"  # LMO tier; see frank_wolfe.parse_solver
    comm: str = "dense"  # power-method collective encoding; see repro.comm
    topology: str = "flat"  # exchange graph; see repro.comm.make_topology
    gossip_rounds: Optional[int] = None  # mixing rounds/exchange (None = auto)
    data_axis: str = "data"
    sample_prob: float = 1.0
    reweight: bool = True
    verify_kernels: bool = True  # up-front kernel-vs-f32 agreement check
    max_rank: Optional[int] = None  # factored-iterate capacity (default epochs)
    gap_tol: Optional[float] = None  # duality-gap early-stop threshold
    block_epochs: Optional[int] = None  # max epochs per scan segment
    engine: str = "scan"  # "scan" (device-resident) or "legacy" (per-epoch)
    checkpoint_dir: Optional[str] = None  # enable segment-boundary checkpoints
    checkpoint_every: int = 1  # save every Nth segment boundary
    checkpoint_keep: Optional[int] = 2  # retained steps (None = all)
    resume_from: Optional[str] = None  # checkpoint dir to restore from
    resume_step: Optional[int] = None  # exact step (default: latest)
    telemetry: Optional[Any] = None  # repro.obs.Telemetry (None = no-op)


@dataclasses.dataclass
class DFWFitResult:
    iterate: low_rank.FactoredIterate
    state: PyTree
    history: Dict[str, list]  # loss/gap/sigma/gamma/k per epoch (pre-update)
    masks: Optional[jax.Array]  # (epochs_run, num_workers) worker weights
    final_loss: float = float("nan")  # F at the returned iterate (full data)
    epochs_run: int = 0  # < num_epochs when gap_tol stopped the run
    stats: Dict[str, int] = dataclasses.field(default_factory=dict)


# ---------------------------------------------------------------------------
# Mesh + row-wise state sharding
# ---------------------------------------------------------------------------


def data_mesh(num_workers: int, axis: str = "data") -> Mesh:
    """1-D mesh over the first ``num_workers`` devices (the paper's workers)."""
    devices = jax.devices()
    if num_workers > len(devices):
        hint = (
            "; on CPU set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "before the first jax import"
            if devices[0].platform == "cpu" else ""
        )
        raise ValueError(
            f"num_workers={num_workers} > visible {devices[0].platform} "
            f"devices={len(devices)}{hint}"
        )
    return mesh_lib.make_mesh((num_workers,), (axis,))


def row_specs(tree: PyTree, axis: str) -> PyTree:
    """PartitionSpec pytree sharding every leaf's leading (sample) dim."""
    return jax.tree.map(lambda _: P(axis), tree)


def replicated_specs(tree: PyTree) -> PyTree:
    return jax.tree.map(lambda _: P(), tree)


def shard_rowwise(mesh: Mesh, tree: PyTree, axis: str = "data") -> PyTree:
    """Place every leaf row-sharded over ``axis``; leading dims must divide.

    Host (numpy) leaves go straight to their shards, each device receiving
    only its own rows; they are never first assembled on one device."""
    nw = mesh.shape[axis]

    def place(x):
        if not isinstance(x, jax.Array):
            x = np.asarray(x)  # REP002-ok: host data, past the isinstance(jax.Array) guard
        if x.shape[0] % nw:
            raise ValueError(
                f"leading dim {x.shape[0]} not divisible by {nw} workers; "
                "pad or trim the sample axis before sharding"
            )
        return jax.device_put(x, NamedSharding(mesh, P(axis)))

    return jax.tree.map(place, tree)


def init_rowwise_state(task, xs: jax.Array, ys: jax.Array, axis: str = "data",
                       telemetry=None):
    """``task.init_state`` on row-sharded data, without copying the data.

    Leaves the task creates (e.g. the logistic Z) are built under jit with
    row-sharded outputs, so each is born on its workers' devices. Leaves
    that are the data itself are handed back as they are: a jit output
    would be a second copy of X. The jitted program is kept in the engine's
    program cache (``engine.cached_program``), keyed on the task, mesh and
    axis; ``telemetry`` counts the lookup."""
    mesh = xs.sharding.mesh

    def build():
        layout = {}  # the state's treedef and its leaves that are data

        def created(x, y):
            leaves, layout["treedef"] = jax.tree.flatten(task.init_state(x, y))
            layout["which"] = {i: j for i, leaf in enumerate(leaves)
                               for j, arg in enumerate((x, y)) if leaf is arg}
            return [leaf for i, leaf in enumerate(leaves)
                    if i not in layout["which"]]

        return jax.jit(created, out_shardings=NamedSharding(mesh, P(axis))), layout

    (made_fn, layout), _ = engine.cached_program(
        ("init_state", task, mesh, axis, engine.code_of(task)), build, telemetry
    )
    made = iter(made_fn(xs, ys))  # its first call traces and fills ``layout``
    data, which, treedef = (xs, ys), layout["which"], layout["treedef"]
    return jax.tree.unflatten(treedef, [
        data[which[i]] if i in which else next(made)
        for i in range(treedef.num_leaves)
    ])


def shard_observations(
    rows,
    cols,
    vals,
    num_workers: int,
    d: int,
    *,
    m: Optional[int] = None,
    weight=None,
):
    """Partition matrix-completion observations into row-block worker shards.

    Worker j owns the contiguous row block ``[j*ceil(d/nw), (j+1)*ceil(d/nw))``
    (the paper's data partition along the sample axis); each observed entry is
    routed to its row's owner. Shard sizes differ, and shard_map needs static
    equal shapes, so every shard is padded to the largest one with
    **zero-weight** entries at coordinate (0, 0) — exact no-ops in every
    reduction (``tasks.MCState`` pre-masks the residual).

    Returns ``(idx, yw)`` as produced by ``tasks.pack_observations``, laid out
    so ``shard_rowwise``'s contiguous split hands worker j exactly its block.
    Pass ``m`` to also range-check the column indices (recommended — the
    downstream gather/segment chains clip silently). Runs on host (numpy):
    this is one-time data layout, not epoch work.
    """
    # Explicit boundary (no-op on numpy inputs): a caller handing device
    # arrays gets one batched fetch, not four implicit pulls (REP002).
    rows, cols, vals, weight = jax.device_get((rows, cols, vals, weight))
    rows_np = np.asarray(rows, np.int64)
    cols_np = np.asarray(cols, np.int64)
    vals_np = np.asarray(vals, np.float32)
    w_np = (
        np.ones_like(vals_np)
        if weight is None
        else np.asarray(weight, np.float32)
    )
    if not (rows_np.shape == cols_np.shape == vals_np.shape == w_np.shape):
        raise ValueError("rows/cols/vals/weight must have identical shapes")
    if rows_np.size and (rows_np.min() < 0 or rows_np.max() >= d):
        raise ValueError(f"row indices must lie in [0, {d})")
    # Out-of-range columns would be silently clipped/dropped by the gather/
    # segment chains downstream — reject them here while shapes are concrete.
    if cols_np.size and cols_np.min() < 0:
        raise ValueError("column indices must be nonnegative")
    if m is not None and cols_np.size and cols_np.max() >= m:
        raise ValueError(f"column indices must lie in [0, {m})")

    block = -(-d // num_workers)  # ceil: worker j owns rows [j*block, (j+1)*block)
    owner = np.minimum(rows_np // block, num_workers - 1)
    sizes = np.bincount(owner, minlength=num_workers)
    p_max = max(int(sizes.max(initial=0)), 1)

    order = np.argsort(owner, kind="stable")
    owner_sorted = owner[order]
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    slot = owner_sorted * p_max + (np.arange(order.size) - starts[owner_sorted])

    idx = np.zeros((num_workers * p_max, 2), np.int32)
    yw = np.zeros((num_workers * p_max, 2), np.float32)  # weight-0 padding
    idx[slot, 0] = rows_np[order]
    idx[slot, 1] = cols_np[order]
    yw[slot, 0] = vals_np[order]
    yw[slot, 1] = w_np[order]
    return jnp.asarray(idx), jnp.asarray(yw)


# ---------------------------------------------------------------------------
# Kernel check
# ---------------------------------------------------------------------------


def verify_kernels(
    task,
    state: PyTree,
    key: jax.Array,
    *,
    tol: float = 1e-4,
    k: int = 1,
) -> float:
    """Assert the task's matvec/rmatvec match those of the same task with
    ``kernels="ref"`` (its products through the kernels' jnp references) on
    random unit probes. Returns the max relative error observed.

    For a block solver of width ``k`` > 1 it probes the route the block LMO
    takes instead: ``matmat``/``rmatmat`` on one (m, k) and one (d, k)
    block of unit columns.

    The oracle runs at ``Precision.HIGHEST``, so on TPU (whose default f32
    matmul rounds operands to bf16) the check compares the kernels with the
    f32 maths, not with another precision mode."""
    kv, ku = jax.random.split(key)
    if k > 1:
        v = jax.random.normal(kv, (task.m, k))
        u = jax.random.normal(ku, (task.d, k))
        v, u = v / jnp.linalg.norm(v, axis=0), u / jnp.linalg.norm(u, axis=0)
        fwd, bwd = "matmat", "rmatmat"
    else:
        v = sphere_vector(kv, task.m)
        u = sphere_vector(ku, task.d)
        fwd, bwd = "matvec", "rmatvec"
    oracle = dataclasses.replace(task, kernels="ref")
    with jax.default_matmul_precision("highest"):
        want_v = getattr(oracle, fwd)(state, v)
        want_u = getattr(oracle, bwd)(state, u)

    def rel_err(a, b):
        a, b = jnp.asarray(a), jnp.asarray(b)
        # Explicit device_get: this runs inside drivers whose callers may
        # guard against implicit device->host transfers.
        return float(jax.device_get(
            jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)
        ))

    err = max(
        rel_err(getattr(task, fwd)(state, v), want_v),
        rel_err(getattr(task, bwd)(state, u), want_u),
    )
    if err > tol:
        raise AssertionError(
            f"kernel products diverge from the f32 reference: rel err {err:.3e} "
            f"> tol {tol:.1e} (task={type(task).__name__})"
        )
    return err


# ---------------------------------------------------------------------------
# Sampled-worker (straggler) schedule
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("num_epochs", "num_workers", "reweight"))
def worker_schedule(
    key: jax.Array,
    num_epochs: int,
    num_workers: int,
    sample_prob: float,
    *,
    reweight: bool = True,
) -> jax.Array:
    """(num_epochs, num_workers) per-epoch worker weights.

    Each worker participates independently with ``sample_prob`` (the paper's
    sampled-worker/straggler-timeout model); if a draw kills every worker one
    uniformly-chosen survivor is forced so the LMO stays well-defined. With
    ``reweight`` the survivors are scaled by num_workers/num_alive, making
    the psum'd loss/gap/line-search aggregates unbiased estimates of their
    full-data values under equal shard sizes.
    """

    def one_epoch(k):
        alive = jax.random.bernoulli(k, sample_prob, (num_workers,))
        force = jax.random.randint(jax.random.fold_in(k, 1), (), 0, num_workers)
        alive = jnp.where(jnp.any(alive), alive, alive.at[force].set(True))
        w = alive.astype(jnp.float32)
        if reweight:
            w = w * (num_workers / jnp.sum(w))
        return w

    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        key, jnp.arange(num_epochs)
    )
    return jax.vmap(one_epoch)(keys)


# ---------------------------------------------------------------------------
# Sharded epoch construction (single-epoch unit; the engine wraps segments)
# ---------------------------------------------------------------------------


def make_sharded_epoch(
    task,
    cfg: DFWConfig,
    mesh: Mesh,
    num_power_iters: int,
    state_example: PyTree,
    reducer: Optional[comm_lib.Reducer] = None,
) -> Callable:
    """shard_map-wrapped single epoch: ``(carry, mask) -> (carry, aux)``.

    The unified-carry analogue of one engine scan step, exposed for tests
    and the HLO-analysis benchmarks that need exactly one epoch's compiled
    collectives. ``carry.state`` is row-sharded over ``cfg.data_axis``;
    iterate, scalars and the PRNG key are replicated; ``mask`` is the
    (num_workers,) worker-weight vector of which each worker consumes its
    own entry; every ``carry.comm_state`` leaf carries a leading worker axis
    sharded over ``cfg.data_axis`` (leaf (nw, d) outside, (1, d) per worker
    inside — the error-feedback residuals live with the worker that owns
    them, exactly like the task state rows; ``()`` for dense).

    ``cfg.topology`` other than "flat" routes the exchanges through a
    ``comm.Topology`` built for this mesh (a passed ``reducer`` is then
    ignored — the topology builds its own inner reducer at the right
    width); gossip topologies additionally give the factored iterate the
    leading worker axis (see ``engine.sharded_carry_spec``).
    """
    axis = cfg.data_axis
    tspec = specs.parse_topology(cfg.topology)
    if tspec.kind == "flat":
        if reducer is None:
            reducer = comm_lib.DenseReducer()
        comm_obj = reducer
    else:
        comm_obj = comm_lib.make_topology(
            cfg.topology, num_workers=mesh.shape[axis], comm=cfg.comm,
            rounds=cfg.gossip_rounds,
        )
        reducer = comm_obj.reducer
    per_node = bool(getattr(comm_obj, "per_node", False))  # REP002-ok: host attribute
    sspec = frank_wolfe.parse_solver(cfg.solver)
    k_block = sspec.k if sspec.kind == "block" else 1
    ep = frank_wolfe.make_epoch_step(
        task, cfg.mu, num_power_iters, step_size=cfg.step_size, axis_name=axis,
        reducer=comm_obj, solver=sspec,
    )

    carry_spec = engine.sharded_carry_spec(
        axis,
        row_specs(state_example, axis),
        reducer.init_state(task.d * k_block, task.m * k_block),
        frank_wolfe.init_probe(sspec, task.m),
        per_node_iterate=per_node,
    )
    aux_spec = EpochAux(P(), P(), P(), P(), P())

    def step(carry, mask):
        carry, aux = ep(
            engine.strip_worker_axis(carry, per_node_iterate=per_node),
            worker_weight=mask[0],
        )
        return (
            engine.restore_worker_axis(carry, per_node_iterate=per_node),
            aux,
        )

    return shard_map_compat(
        step,
        mesh,
        in_specs=(carry_spec, P(axis)),
        out_specs=(carry_spec, aux_spec),
    )


# ---------------------------------------------------------------------------
# Checkpoint / resume plumbing shared by the drivers
# ---------------------------------------------------------------------------


def _check_snapshot(snap: ckpt.RunSnapshot, task, cfg: DFWConfig) -> None:
    """A checkpoint is only resumable onto the problem it was saved from:
    same task type and dimensions (worker count / comm / schedule MAY
    change — that's elastic / warm restart). Mismatches here mean the
    caller pointed resume_from at the wrong run."""
    ext = snap.extra
    want = (type(task).__name__, int(task.d), int(task.m))
    got = (ext.get("task"), int(ext.get("d", -1)), int(ext.get("m", -1)))
    if want != got:
        raise ValueError(
            f"checkpoint was saved by task {got} but resume targets {want}; "
            "resume_from must point at a checkpoint of the same problem"
        )
    if snap.t > cfg.num_epochs:
        raise ValueError(
            f"checkpoint is at epoch {snap.t} but num_epochs={cfg.num_epochs}; "
            "extend num_epochs to resume past it"
        )


def _resume_complete(snap: ckpt.RunSnapshot, cfg: DFWConfig) -> bool:
    """Does the checkpoint already satisfy the *current* config? True when
    the epoch budget is spent, or when the saved early stop still stands
    under cfg's gap_tol. A warm restart that extends num_epochs or loosens/
    removes gap_tol re-enters the engine instead of returning the stopped
    run verbatim — the saved ``done`` flag records the OLD certificate, not
    this one."""
    if snap.t >= cfg.num_epochs:
        return True
    if not snap.done:
        return False
    gaps = snap.history.get("gap", [])
    return bool(gaps) and cfg.gap_tol is not None and gaps[-1] <= cfg.gap_tol


def _make_checkpointer(
    task, cfg: DFWConfig, nw: int, comm_spec: str, telemetry=None,
    *, per_node_iterate: bool = False,
) -> Optional[ckpt.RunCheckpointer]:
    if cfg.checkpoint_dir is None:
        return None
    return ckpt.RunCheckpointer(
        cfg.checkpoint_dir,
        save_every=cfg.checkpoint_every,
        keep_last=cfg.checkpoint_keep,
        telemetry=telemetry,
        per_node_iterate=per_node_iterate,
        extra=ckpt.run_extra(
            task,
            num_workers=nw,
            comm=comm_spec,
            num_epochs=cfg.num_epochs,
            schedule=cfg.schedule,
            mu=cfg.mu,
            step_size=cfg.step_size,
            sample_prob=cfg.sample_prob,
            reweight=cfg.reweight,
            solver=cfg.solver,
            topology=cfg.topology,
        ),
    )


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------


def fit(
    task,
    x: jax.Array,
    y: jax.Array,
    *,
    cfg: DFWConfig,
    key: jax.Array,
    mesh: Optional[Mesh] = None,
    num_workers: Optional[int] = None,
    callback: Optional[Callable[[int, EpochAux], None]] = None,
) -> DFWFitResult:
    """Run distributed DFW-Trace on data ``(x, y)`` sharded over workers.

    Provide either a prebuilt 1-D ``mesh`` (axis ``cfg.data_axis``) or a
    ``num_workers`` count (a mesh over the first N devices is built). The
    sample axis of ``x``/``y`` must divide the worker count. The returned
    history matches ``frank_wolfe.fit``'s, plus the per-epoch worker masks.

    Execution goes through ``core/engine.run_epochs``: maximal constant-K(t)
    segments each compiled as one ``lax.scan`` inside ``shard_map``, epochs
    advancing entirely on device. ``callback(start_t, aux_block)`` fires per
    segment (see ``frank_wolfe.fit``), not per epoch.

    With ``cfg.telemetry`` the call is one job (``Telemetry.job``), bracketed
    whole by ``Telemetry.profiler``. Its top-level spans cover it:
    ``engine.setup`` (entry to the first segment dispatch; children
    ``engine.verify_kernels`` and ``engine.place``), then the engine's
    ``engine.dispatch``, ``engine.fetch`` and ``engine.history``, then
    ``engine.final_loss``.

    The job's jitted programs (the segment scans, the state init, the final
    loss) come from the engine's process-wide program cache
    (``engine.cached_program``): a later ``fit`` with equal settings, on data
    of the same shapes and placement, runs them without tracing, lowering
    or compiling again.
    """
    tel = cfg.telemetry if cfg.telemetry is not None else Telemetry.noop()
    # The body stays inline under this ``with``. A version that ran it in a
    # helper called from here lowered the segment's scan 70-90 ms slower
    # on a v5e host (+7-9 % time to gap for the ImageNet head, with the
    # telemetry off, when every job lowered its scan). A plain pass-through
    # frame did not reproduce it; the cause is open (PERF.md, Findings).
    with tel.job(), tel.profiler(), contextlib.ExitStack() as setup:
        setup.enter_context(tel.span("engine.setup", "engine"))
        if mesh is None:
            if num_workers is None:
                raise ValueError("pass mesh= or num_workers=")
            mesh = data_mesh(num_workers, cfg.data_axis)
        elif num_workers is not None and mesh.shape[cfg.data_axis] != num_workers:
            raise ValueError(
                f"mesh has {mesh.shape[cfg.data_axis]} workers on "
                f"{cfg.data_axis!r} but num_workers={num_workers}; pass one or "
                "make them agree"
            )
        nw = mesh.shape[cfg.data_axis]
        sspec, _, tspec = specs.validate(
            solver=cfg.solver, comm=cfg.comm, topology=cfg.topology
        )
        k_block = sspec.k if sspec.kind == "block" else 1
        max_rank = engine.resolve_max_rank(cfg.max_rank, cfg.num_epochs, k_block)
        tel.event("run.start", "run", driver="launch.dfw.fit",
                  task=type(task).__name__, d=int(task.d), m=int(task.m),
                  num_workers=nw, comm=cfg.comm, topology=cfg.topology,
                  schedule=cfg.schedule,
                  num_epochs=cfg.num_epochs, solver=cfg.solver)

        # The comm stack: a Topology (exchange graph) wrapping a Reducer (wire
        # encoding). "flat" hands the bare reducer to the engine — the exact
        # legacy psum path, bit for bit — while ring/gossip/hier pass the
        # topology itself (it quacks like a Reducer: same ``exchange``
        # signature, so nothing downstream changes shape).
        topo = comm_lib.make_topology(
            cfg.topology, num_workers=nw, comm=cfg.comm,
            rounds=cfg.gossip_rounds,
        )
        reducer = topo.reducer
        comm_obj = reducer if tspec.kind == "flat" else topo
        per_node = bool(getattr(comm_obj, "per_node", False))  # REP002-ok: host attribute

        with tel.span("engine.verify_kernels", "engine"):
            if cfg.verify_kernels:
                # Probe on a small host-local slice before committing to the run.
                probe_rows = min(x.shape[0], max(nw, 64))
                probe = task.init_state(x[:probe_rows], y[:probe_rows])
                verify_kernels(task, probe, jax.random.fold_in(key, 0x5EED),
                               k=k_block)
            if isinstance(reducer, comm_lib.Int8Reducer) and cfg.verify_kernels:
                comm_lib.verify_quantize_kernels(
                    jax.random.fold_in(key, 0x17F8), num_workers=nw,
                )

        with tel.span("engine.place", "engine"):
            xs, ys = shard_rowwise(mesh, (x, y), cfg.data_axis)
            state = init_rowwise_state(task, xs, ys, cfg.data_axis, tel)
            it = low_rank.init(max_rank, task.d, task.m)

            # Per-worker reducer state: every worker starts from the reducer's own
            # init_state values (not zeros — the contract allows nonzero
            # initialization), stacked along a leading worker axis sharded like the
            # data rows. Dense's () has no leaves, so this is a no-op there. The
            # block solver flattens (d,k)/(m,k) blocks through the reducer, so
            # stateful encodings are sized for the flattened payload.
            comm_example = reducer.init_state(task.d * k_block, task.m * k_block)
            comm_state = jax.tree.map(
                lambda leaf: jax.device_put(
                    jnp.broadcast_to(leaf, (nw,) + leaf.shape),
                    NamedSharding(mesh, P(cfg.data_axis)),
                ),
                comm_example,
            )

            # Block-solver warm-start probe: replicated (m, k) block, cold-started
            # from the run's key (() for rank1 — zero extra carry leaves).
            probe_blk = frank_wolfe.init_probe(sspec, task.m, key)
            if sspec.kind == "block":
                probe_blk = jax.device_put(probe_blk, NamedSharding(mesh, P()))

            sampling = cfg.sample_prob < 1.0
            if sampling:
                masks = worker_schedule(
                    jax.random.fold_in(key, 0x1A5C),
                    cfg.num_epochs,
                    nw,
                    cfg.sample_prob,
                    reweight=cfg.reweight,
                )
            else:
                masks = jnp.ones((cfg.num_epochs, nw), jnp.float32)

        start_t, initial_history = 0, None
        if cfg.resume_from is not None:
            # `state` (freshly built above) supplies the treedef skeleton; its
            # values are then replaced wholesale by the checkpointed ones.
            snap = ckpt.restore_run(
                cfg.resume_from, state_like=state, step=cfg.resume_step
            )
            _check_snapshot(snap, task, cfg)
            state = shard_rowwise(mesh, snap.carry.state, cfg.data_axis)
            it = snap.unpack_iterate(max_rank)
            key = jnp.asarray(snap.carry.key)
            start_t, initial_history = snap.t, snap.history
            snap_probe = getattr(snap.carry, "probe", ())
            if (
                sspec.kind == "block"
                and hasattr(snap_probe, "shape")
                and tuple(snap_probe.shape) == (task.m, sspec.k)
            ):
                # v2 checkpoint with a matching block width: resume the warm
                # start bit-exactly. v1 payloads (or a changed k) keep the cold
                # probe initialized above — convergence is preserved, warmth
                # is not.
                probe_blk = jax.device_put(
                    jnp.asarray(snap_probe), NamedSharding(mesh, P())
                )
            same_mesh = int(snap.extra.get("num_workers", -1)) == nw
            same_topo = snap.extra.get("topology", "flat") == cfg.topology
            if same_mesh and same_topo and snap.extra.get("comm") == reducer.spec:
                # Bit-exact path: per-worker reducer state (e.g. top-k
                # error-feedback residuals) resumes exactly where it stopped.
                comm_state = jax.tree.map(
                    lambda leaf: jax.device_put(
                        jnp.asarray(leaf), NamedSharding(mesh, P(cfg.data_axis))
                    ),
                    snap.carry.comm_state,
                )
            # else: keep the freshly initialized comm_state — an elastic remesh
            # (or a warm comm-mode change) re-derives per-worker state.
            same_sampling = (
                float(snap.extra.get("sample_prob", -1.0)) == cfg.sample_prob  # REP002-ok: host
                and bool(snap.extra.get("reweight", not cfg.reweight))  # REP002-ok: host
                == cfg.reweight
            )
            if (
                same_mesh
                and same_sampling
                and snap.masks is not None
                and snap.masks.shape == (cfg.num_epochs, nw)
            ):
                masks = jnp.asarray(snap.masks)
            # else: the regenerated schedule above stands — a new worker count
            # or extended num_epochs re-draws it, and a warm restart that
            # changes sample_prob/reweight must get the schedule it asked for,
            # not the checkpointed run's.
            if _resume_complete(snap, cfg):
                # Nothing left to run: the checkpoint already holds the final
                # carry (epoch budget spent, or its gap certificate still
                # stands under this config's gap_tol).
                loss, reused = engine.final_loss(task, state, tel)
                return DFWFitResult(
                    iterate=it, state=state, history=snap.history,
                    masks=masks[: snap.t] if sampling else None,
                    final_loss=loss, epochs_run=snap.t,
                    stats={"segments_planned": 0, "segments_run": 0,
                           "dispatches": 1, "compilations": 1,
                           "programs_reused": int(reused), "host_syncs": 1},
                )

        checkpointer = _make_checkpointer(
            task, cfg, nw, reducer.spec, tel, per_node_iterate=per_node
        )
        if checkpointer is not None:
            # checkpoint_dir belongs to THIS run's timeline from here on: a
            # fresh run clears any previous run's steps, a resume keeps its
            # prefix and drops the abandoned tail. Either way, steps past
            # start_t would shadow this run's history on the next default
            # (latest-step) resume.
            checkpointer.store.discard_after(start_t)

        if per_node:
            # Gossip: every worker evolves its own inexact-consensus iterate, so
            # the (possibly resumed node-0) iterate is stacked along a leading
            # worker axis sharded like the data rows — the exact treatment the
            # per-worker reducer state already gets. A gossip resume is elastic
            # here by construction: checkpoints store the node-0 slice and this
            # broadcast re-seeds every node with it (the optimization dynamics
            # themselves resume bit-exactly — they read only the task state).
            it = jax.tree.map(
                lambda leaf: jax.device_put(
                    jnp.broadcast_to(leaf, (nw,) + leaf.shape),
                    NamedSharding(mesh, P(cfg.data_axis)),
                ),
                it,
            )

        wrapper = engine.shard_map_segment_wrapper(
            mesh,
            cfg.data_axis,
            row_specs(state, cfg.data_axis),
            comm_state_example=comm_example,
            probe_example=probe_blk,
            has_masks=True,
            per_node_iterate=per_node,
        )
        eres = engine.run_epochs(
            task,
            state,
            mu=cfg.mu,
            num_epochs=cfg.num_epochs,
            key=key,
            schedule=cfg.schedule,
            step_size=cfg.step_size,
            axis_name=cfg.data_axis,
            reducer=comm_obj,
            comm_state=comm_state,
            iterate=it,
            masks=masks,
            gap_tol=cfg.gap_tol,
            block_epochs=cfg.block_epochs,
            segment_wrapper=wrapper,
            callback=callback,
            mode=cfg.engine,
            start_t=start_t,
            initial_history=initial_history,
            checkpointer=checkpointer,
            telemetry=tel,
            num_workers=nw,
            solver=sspec,
            probe=probe_blk if sspec.kind == "block" else None,
            end_setup=setup.close,
        )
        if checkpointer is not None:
            # Surface the last in-flight write's failure here, not silently at
            # interpreter exit — the run result should not claim durability the
            # store never achieved.
            with tel.span("checkpoint.join", "checkpoint"):
                checkpointer.wait()
        # Loss at the returned iterate (history is pre-update; see frank_wolfe.fit).
        # The plain sum over the row-sharded state is already the global loss, and
        # straggler weights never apply here: this is the true full-data F.
        with tel.span("engine.final_loss", "engine"):
            loss, reused = engine.final_loss(task, eres.carry.state, tel)
        eres.stats["dispatches"] += 1
        eres.stats["host_syncs"] += 1
        eres.stats["compilations"] += 1
        eres.stats["programs_reused"] += reused
        it_out = eres.carry.iterate
        if per_node:
            # Report node 0's iterate — the same convention gossip checkpoints
            # use. All nodes agree to consensus tolerance; the caller's
            # final_loss above is the exact full-data F of the *states*.
            it_out = jax.tree.map(lambda a: a[0], it_out)
        return DFWFitResult(
            iterate=it_out,
            state=eres.carry.state,
            history=eres.history,
            masks=masks[: eres.epochs_run] if sampling else None,
            final_loss=loss,
            epochs_run=eres.epochs_run,
            stats=eres.stats,
        )


def fit_serial(
    task,
    x: jax.Array,
    y: jax.Array,
    *,
    cfg: DFWConfig,
    key: jax.Array,
    callback: Optional[Callable[[int, EpochAux], None]] = None,
) -> DFWFitResult:
    """Single-device reference run with the *same* config (and the same
    kernel products) as ``fit`` — the baseline every sharded run is
    compared against in tests and benchmarks.

    ``cfg.comm`` is honored with a one-worker reducer: the serial run
    *simulates* the compressed encoding (int8 at full 127-level budget,
    top-k with one worker's error feedback), which is what the
    convergence-vs-bits sweeps compare against. ``cfg.topology`` is honored
    the same way: a one-worker gossip exchange is the identity (a node
    averaging with itself) and a one-worker ``hier:g`` applies the reducer
    encoding at group width g — the serial baselines the topology tests and
    sweeps compare their sharded runs against.

    ``cfg.sample_prob`` < 1 is rejected: the straggler model samples
    *workers*, and a serial run has exactly one — silently ignoring the
    setting (the old behavior) made a "straggler" benchmark measure nothing.
    """
    if cfg.sample_prob < 1.0:
        raise ValueError(
            f"sample_prob={cfg.sample_prob} needs multiple workers to sample "
            "from; fit_serial runs exactly one. Use fit(..., num_workers=N) "
            "for the straggler mode, or set sample_prob=1.0"
        )
    sspec, _, tspec = specs.validate(
        solver=cfg.solver, comm=cfg.comm, topology=cfg.topology
    )
    topo = comm_lib.make_topology(
        cfg.topology, num_workers=1, comm=cfg.comm,
        rounds=cfg.gossip_rounds,
    )
    reducer = topo.reducer
    comm_obj = reducer if tspec.kind == "flat" else topo
    k_block = sspec.k if sspec.kind == "block" else 1
    state = task.init_state(jnp.asarray(x), jnp.asarray(y))
    iterate, comm_state, start_t, initial_history = None, None, 0, None
    probe = None
    if cfg.resume_from is not None:
        snap = ckpt.restore_run(
            cfg.resume_from, state_like=state, step=cfg.resume_step
        )
        _check_snapshot(snap, task, cfg)
        state = jax.tree.map(jnp.asarray, snap.carry.state)
        iterate = snap.unpack_iterate(
            engine.resolve_max_rank(cfg.max_rank, cfg.num_epochs, k_block)
        )
        key = jnp.asarray(snap.carry.key)
        start_t, initial_history = snap.t, snap.history
        snap_probe = getattr(snap.carry, "probe", ())
        if (
            sspec.kind == "block"
            and hasattr(snap_probe, "shape")
            and tuple(snap_probe.shape) == (task.m, sspec.k)
        ):
            # v2 payload with matching block width resumes the warm start;
            # v1 (or a changed k) cold-starts via the engine default.
            probe = jnp.asarray(snap_probe)
        if (
            int(snap.extra.get("num_workers", -1)) == 1
            and snap.extra.get("comm") == reducer.spec
        ):
            comm_state = jax.tree.map(jnp.asarray, snap.carry.comm_state)
        # else: default (fresh) reducer state — a sharded checkpoint's
        # per-worker residuals don't transfer to the one-worker run, and a
        # warm comm change starts its new encoding from scratch.
        if _resume_complete(snap, cfg):
            loss, reused = engine.final_loss(task, state, cfg.telemetry)
            return DFWFitResult(
                iterate=iterate, state=state, history=snap.history,
                masks=None, final_loss=loss, epochs_run=snap.t,
                stats={"segments_planned": 0, "segments_run": 0,
                       "dispatches": 1, "compilations": 1,
                       "programs_reused": int(reused), "host_syncs": 1},
            )
    checkpointer = _make_checkpointer(task, cfg, 1, reducer.spec, cfg.telemetry)
    if checkpointer is not None:
        # As in `fit`: the dir is this run's timeline — drop steps past
        # start_t (all of them, for a fresh run).
        checkpointer.store.discard_after(start_t)
    res = frank_wolfe.fit(
        task,
        state,
        mu=cfg.mu,
        num_epochs=cfg.num_epochs,
        key=key,
        schedule=cfg.schedule,
        step_size=cfg.step_size,
        callback=callback,
        reducer=comm_obj,
        max_rank=cfg.max_rank,
        gap_tol=cfg.gap_tol,
        block_epochs=cfg.block_epochs,
        mode=cfg.engine,
        iterate=iterate,
        comm_state=comm_state,
        start_t=start_t,
        initial_history=initial_history,
        checkpointer=checkpointer,
        telemetry=cfg.telemetry,
        solver=sspec,
        probe=probe,
    )
    return DFWFitResult(
        iterate=res.iterate, state=res.state, history=res.history, masks=None,
        final_loss=res.final_loss, epochs_run=res.epochs_run, stats=res.stats,
    )
