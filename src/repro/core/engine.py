"""Device-resident epoch engine: scan-compiled K(t) segments.

The DFW-Trace loop is hundreds to thousands of *cheap* O(d+m) epochs, so a
driver that pays one jit dispatch and four blocking ``float(...)`` transfers
per epoch is dominated by Python and PCIe, not by the algorithm. This engine
keeps whole runs on device:

1. **Segment plan.** ``plan_segments`` partitions the K(t) schedule into
   maximal constant-K runs (optionally capped at ``block_epochs``). A
   ``const:K`` schedule is one segment; ``log`` is O(log T) segments.
2. **Scan compilation.** Each segment executes as a single ``jax.lax.scan``
   over the unified ``EpochCarry`` — one dispatch per segment, with the
   per-epoch ``EpochAux`` rows written into the scan's preallocated
   on-device output buffers. Worker straggler masks are precomputed as a
   ``(num_epochs, nw)`` array and indexed by the carried epoch counter
   inside the scan. Segments sharing a (K, length) shape share one
   executable.
3. **Gap-certificate early stop.** The psum'd duality gap rides the scan
   carry as a ``done`` flag: once ``gap <= gap_tol`` every remaining epoch
   in the segment is a ``lax.cond`` no-op (static shapes preserved, compute
   skipped), and the host stops launching segments at the next boundary.
   ``epochs_run`` counts the epochs that actually executed.
4. **Program reuse.** The jitted segment programs are kept in a bounded,
   process-wide cache keyed on the values they close over (task, μ,
   (K, length), step rule, reducer, solver, wrapper, gap tolerance) and the
   arguments' shapes and shardings, so a later run with equal settings
   dispatches the executables an earlier run built: no trace, no lowering,
   no persistent-cache load. ``launch/dfw.py`` keeps its per-job programs
   in the same cache (``cached_program``).

Host transfers happen only at segment boundaries (and only when early
stopping or a callback needs them) plus one final history fetch — all via
explicit ``jax.device_get``, so a run under
``jax.transfer_guard_device_to_host("disallow")`` proves the loop is
device-resident (regression-pinned in ``tests/test_engine.py``).

``mode="legacy"`` reproduces the pre-engine driver — one dispatch per epoch
and four blocking scalar pulls — on the same unified carry; it exists as the
trajectory-equivalence oracle and the baseline ``benchmarks/engine_bench.py``
measures against.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import threading
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..compat import shard_map_compat
from ..obs import Telemetry
from . import low_rank
from .frank_wolfe import (
    EpochAux,
    EpochCarry,
    has_block_products,
    init_carry,
    init_probe,
    k_schedule,
    make_epoch_step,
    parse_solver,
)
from .power_method import AxisName

PyTree = Any


class Segment(NamedTuple):
    """A maximal run of epochs sharing one (static) power-iteration count."""

    start: int  # first epoch index
    length: int  # number of epochs
    k: int  # K(t) throughout the segment


def plan_segments(
    schedule: str,
    num_epochs: int,
    block_epochs: Optional[int] = None,
    *,
    start: int = 0,
) -> List[Segment]:
    """Partition ``[start, num_epochs)`` into maximal constant-K segments.

    ``block_epochs`` caps segment length: early stopping acts at segment
    granularity, so the cap bounds how many epochs a converged run can
    execute past its certificate (and how stale a progress callback gets).
    Equal-length blocks of the same K share one compiled executable, so
    chopping a long ``const:K`` run costs extra dispatches, not compiles.

    ``start`` > 0 is the resume case: checkpoints are written at segment
    boundaries, and because maximality (and the block cap) are computed
    from each segment's own start, planning from a boundary of the full
    plan reproduces exactly that plan's remaining segments — a resumed run
    dispatches the same (K, length) executables the uninterrupted run
    would have, which is what makes bit-exact resume possible.
    """
    if num_epochs < 1:
        raise ValueError(f"num_epochs={num_epochs}: need at least one epoch")
    if block_epochs is not None and block_epochs < 1:
        raise ValueError(f"block_epochs={block_epochs}: must be >= 1")
    if not 0 <= start < num_epochs:
        raise ValueError(
            f"start={start}: must lie in [0, num_epochs={num_epochs})"
        )
    sched = k_schedule(schedule)
    segments: List[Segment] = []
    t = start
    while t < num_epochs:
        k = sched(t)
        end = t + 1
        while (
            end < num_epochs
            and sched(end) == k
            and (block_epochs is None or end - t < block_epochs)
        ):
            end += 1
        segments.append(Segment(start=t, length=end - t, k=k))
        t = end
    return segments


def resolve_max_rank(
    max_rank: Optional[int], num_epochs: int, atoms_per_epoch: int = 1
) -> int:
    """Factored-iterate capacity. ``atoms_per_epoch`` factors are appended
    per epoch (1 for rank1, k for ``block:k``) and ``low_rank.fw_update``
    clamps out-of-range writes silently, so undersizing would corrupt the
    returned iterate — reject it up front. (Shared by the serial and sharded
    drivers: one capacity contract.)"""
    need = num_epochs * atoms_per_epoch
    if max_rank is None:
        return need
    if max_rank < need:
        raise ValueError(
            f"max_rank={max_rank} < num_epochs*atoms={need}: every "
            f"epoch appends {atoms_per_epoch} factor(s), so the iterate "
            "store would overflow"
        )
    return max_rank


def dispatch_contract(
    *,
    segments: int = 1,
    max_compilations: Optional[int] = 2,
    name: Optional[str] = None,
):
    """The engine's reason to exist, declared as a checkable contract: a run
    over ``segments`` planned segments costs at most ``segments + 1`` jitted
    dispatches (one scan per segment + the driver's final-loss eval), at
    most O(1) explicit host syncs, and — under ``contract.guard()`` — zero
    implicit device->host transfers. ``max_compilations`` bounds the
    distinct executables the run dispatches, whether built in this run or
    reused from the program cache (``cached_program``); it defaults to 2
    (the single-segment ``const:K`` case: one scan executable + the final
    loss eval); pass ``None`` for schedules whose distinct (K, length)
    signature count isn't pinned. Consumed by ``tests/test_engine.py`` (the
    serial, log-schedule, and 8-way pins) and ``tools/repro_contracts.py``
    against ``FitResult.stats``."""
    from ..analysis.contracts import Contract  # lazy: analysis is tooling

    return Contract(
        name=name or f"engine.dispatch[segments={segments}]",
        max_dispatches=segments + 1,
        max_compilations=max_compilations,
        max_host_syncs=2,
        no_host_transfers=True,
    )


@dataclasses.dataclass
class EngineResult:
    """``history`` lists are truncated to ``epochs_run``. ``stats`` counts
    the engine's interactions with the runtime — the quantities the
    dispatch/sync regression tests pin:

    - ``segments_planned`` / ``segments_run``: plan size vs segments
      actually launched (early stop skips the tail),
    - ``dispatches``: jitted calls issued,
    - ``compilations``: distinct executables the run dispatches, built or
      reused (segments sharing a (K, length) shape share one),
    - ``programs_reused``: those of them found in the process's program
      cache (``cached_program``), built by an earlier run,
    - ``host_syncs``: explicit ``jax.device_get`` round-trips (legacy mode
      counts its four blocking per-epoch scalar pulls here).
    """

    carry: EpochCarry
    history: Dict[str, list]
    epochs_run: int
    stats: Dict[str, int]


# Jitted programs kept across runs. A key holds the values a program closes
# over, never the identity of an object rebuilt per job, so a run with equal
# settings gets the jax.jit object an earlier run built and JAX runs its
# compiled executable without tracing or lowering again. Entries hold code
# only: no cached callable captures a run's state, data or iterate.
_PROGRAM_CACHE_SIZE = 32
_programs: "collections.OrderedDict[Any, Any]" = collections.OrderedDict()
_programs_lock = threading.Lock()  # builds run outside it


def cached_program(key, build: Callable[[], Any],
                   telemetry: Optional[Telemetry] = None) -> tuple:
    """``(program, hit)``: the program cached under ``key``, or ``build()``
    stored there (least recently used entries past 32 are dropped). A key
    that cannot be hashed is never stored: its program is built every time.
    Each lookup adds one to the enabled ``telemetry``'s
    ``engine.program_cache.hits`` or ``engine.program_cache.misses``."""
    try:
        with _programs_lock:
            program = _programs.get(key)
            if program is not None:
                _programs.move_to_end(key)
    except TypeError:
        key = program = None
    hit = program is not None
    if not hit:
        program = build()
        if key is not None:
            with _programs_lock:
                _programs[key] = program
                while len(_programs) > _PROGRAM_CACHE_SIZE:
                    _programs.popitem(last=False)
    if telemetry is not None and telemetry.enabled:
        name = "hits" if hit else "misses"
        telemetry.registry.counter(f"engine.program_cache.{name}").inc()
    return program, hit


def clear_programs() -> None:
    """Drop every cached program: the next run builds its own."""
    with _programs_lock:
        _programs.clear()


def code_of(task, reducer=None) -> tuple:
    """What the classes of ``task`` and ``reducer`` define that can be
    called (and of what they wrap: a kernelized task's ``base``, a
    topology's ``reducer``). A program key holds it beside the objects'
    values, so a method patched on a class (as the benchmark's fault
    harness patches ``update``) gives a program traced with the new code."""
    objs = (task, getattr(task, "base", None),
            reducer, getattr(reducer, "reducer", None))
    return tuple(v for obj in objs for cls in type(obj).__mro__[:-1]
                 for v in vars(cls).values() if callable(v))


def final_loss(task, state: PyTree, telemetry: Optional[Telemetry] = None) -> tuple:
    """``(F, reused)``: ``task.local_loss`` of ``state`` (on row-sharded
    state the plain sum is already the global loss), by the jitted loss kept
    in the program cache, and whether that program was reused."""
    loss_fn, reused = cached_program(
        ("final_loss", task, code_of(task)), lambda: jax.jit(task.local_loss),
        telemetry,
    )
    return float(jax.device_get(loss_fn(state))), reused


def _arg_signature(args: tuple) -> tuple:
    """What a compiled executable is specialised to: the arguments' tree,
    types and shardings (no array is held)."""
    leaves, treedef = jax.tree.flatten(args)
    return treedef, tuple(
        (jax.typeof(x), getattr(x, "sharding", None)) for x in leaves
    )


def _segment_step(
    task,
    mu: float,
    k: int,
    length: int,
    *,
    step_size: str,
    axis_name: AxisName,
    reducer,
    gap_tol: Optional[float],
    has_masks: bool,
    solver="rank1",
) -> Callable:
    """One segment as a pure function: ``length`` epochs under ``lax.scan``.

    Signature (before any shard_map wrapping):
    ``seg(carry, done, epochs_run[, masks]) -> (carry, done, epochs_run, aux)``
    where ``aux`` leaves are ``(length,)`` — the scan's preallocated
    on-device history rows — and ``masks`` is the full ``(num_epochs, nw)``
    straggler-weight array, indexed at ``[carry.t, 0]`` inside the scan
    (inside shard_map every worker holds its own ``(num_epochs, 1)`` column).
    Epochs after the gap certificate fires are ``lax.cond`` no-ops emitting
    NaN aux rows (truncated away by the host).
    """
    epoch = make_epoch_step(
        task, mu, k, step_size=step_size, axis_name=axis_name,
        reducer=reducer, solver=solver,
    )
    tol = jnp.float32(-jnp.inf if gap_tol is None else gap_tol)

    def segment(carry, done, epochs_run, masks=None):
        def body(c, _):
            def live(c):
                carry, done, epochs_run = c
                w = masks[carry.t, 0] if has_masks else None
                carry, aux = epoch(carry, worker_weight=w)
                return (carry, done | (aux.gap <= tol), epochs_run + 1), aux

            def skip(c):
                nan = jnp.float32(jnp.nan)
                return c, EpochAux(
                    loss=nan, gap=nan, sigma=nan, gamma=nan, piters=nan
                )

            done = c[1]
            return jax.lax.cond(done, skip, live, c)

        (carry, done, epochs_run), aux = jax.lax.scan(
            body, (carry, done, epochs_run), None, length=length
        )
        return carry, done, epochs_run, aux

    return segment


def sharded_carry_spec(
    axis_or_axes,
    state_spec: PyTree,
    comm_state_example: PyTree = (),
    probe_example: PyTree = (),
    *,
    per_node_iterate: bool = False,
):
    """shard_map PartitionSpecs for an ``EpochCarry``: task state rows
    sharded over the data axes, iterate/counter/key replicated, and every
    reducer-state leaf carried with a *leading worker axis* sharded like the
    data rows (dense's ``()`` has no leaves — encoding-agnostic). The block
    solver's warm-start probe is replicated like the iterate (``()`` for
    rank1 — zero extra leaves).

    ``per_node_iterate=True`` (gossip topologies) gives the factored iterate
    the same leading-worker-axis treatment as the reducer state: every
    worker evolves its *own* inexact-consensus iterate, so the driver stacks
    the leaves to ``(nw, ...)`` and shard_map hands each worker its slice.

    ``comm_state_example`` is one worker's (unstacked) reducer state;
    ``probe_example`` the replicated probe block (or ``()``)."""
    from jax.sharding import PartitionSpec as P

    ax = axis_or_axes
    it_spec = P(ax) if per_node_iterate else P()
    return EpochCarry(
        state=state_spec,
        iterate=low_rank.FactoredIterate(
            u=it_spec, s=it_spec, v=it_spec, alpha=it_spec, count=it_spec
        ),
        comm_state=jax.tree.map(lambda _: P(ax), comm_state_example),
        t=P(),
        key=P(),
        probe=jax.tree.map(lambda _: P(), probe_example),
    )


def strip_worker_axis(
    carry: EpochCarry, *, per_node_iterate: bool = False
) -> EpochCarry:
    """Inside a shard_map region: drop the leading worker axis off the comm
    leaves — a worker owns its (1, ...) slice of the stacked reducer state.
    With ``per_node_iterate`` the factored-iterate leaves are stacked the
    same way and stripped too."""
    carry = carry._replace(
        comm_state=jax.tree.map(lambda a: a[0], carry.comm_state)
    )
    if per_node_iterate:
        carry = carry._replace(
            iterate=jax.tree.map(lambda a: a[0], carry.iterate)
        )
    return carry


def restore_worker_axis(
    carry: EpochCarry, *, per_node_iterate: bool = False
) -> EpochCarry:
    carry = carry._replace(
        comm_state=jax.tree.map(lambda a: a[None], carry.comm_state)
    )
    if per_node_iterate:
        carry = carry._replace(
            iterate=jax.tree.map(lambda a: a[None], carry.iterate)
        )
    return carry


def shard_map_segment_wrapper(
    mesh,
    axis_or_axes,
    state_spec: PyTree,
    *,
    comm_state_example: PyTree = (),
    probe_example: PyTree = (),
    has_masks: bool = False,
    per_node_iterate: bool = False,
) -> "ShardMapSegmentWrapper":
    """Build the canonical ``segment_wrapper``: shard_map with the task
    state row-sharded, iterate/scalars/key/probe replicated, straggler masks
    column-sharded, and reducer state carried with a leading worker axis
    (sharded like the data rows) that is stripped inside the region.
    ``per_node_iterate`` extends that leading-axis treatment to the factored
    iterate (gossip topologies; see ``sharded_carry_spec``).
    """
    carry_spec = sharded_carry_spec(
        axis_or_axes, state_spec, comm_state_example, probe_example,
        per_node_iterate=per_node_iterate,
    )
    leaves, treedef = jax.tree.flatten(carry_spec)
    return ShardMapSegmentWrapper(
        mesh, axis_or_axes, treedef, tuple(leaves), has_masks, per_node_iterate
    )


@dataclasses.dataclass(frozen=True)
class ShardMapSegmentWrapper:
    """``shard_map_segment_wrapper``'s result: a value (equal wrappers wrap
    alike), so the program cache can key on it. The carry's PartitionSpecs
    are held flattened, which keeps them hashable."""

    mesh: Any
    axes: Any
    carry_tree: Any
    carry_leaves: tuple
    has_masks: bool = False
    per_node_iterate: bool = False

    def __call__(self, seg_fn: Callable) -> Callable:
        from jax.sharding import PartitionSpec as P

        per_node = self.per_node_iterate
        carry_spec = jax.tree.unflatten(self.carry_tree, self.carry_leaves)
        aux_spec = EpochAux(P(), P(), P(), P(), P())

        def step(carry, done, epochs_run, *masks):
            carry, done, epochs_run, aux = seg_fn(
                strip_worker_axis(carry, per_node_iterate=per_node),
                done, epochs_run, *masks
            )
            return (
                restore_worker_axis(carry, per_node_iterate=per_node),
                done, epochs_run, aux,
            )

        mask_specs = (P(None, self.axes),) if self.has_masks else ()
        return shard_map_compat(
            step,
            self.mesh,
            in_specs=(carry_spec, P(), P()) + mask_specs,
            out_specs=(carry_spec, P(), P(), aux_spec),
        )


_HISTORY_KEYS = ("loss", "gap", "sigma", "gamma")


def _assemble_history(
    prefix: Dict[str, list], aux_blocks: List[tuple], upto: int
) -> Dict[str, list]:
    """Prefix history + every fetched aux block, truncated to ``upto``
    executed epochs (rows past an early stop are NaN no-op fillers). All
    blocks must carry their host copy — callers fetch before assembling."""
    hist = {name: list(prefix[name]) for name in (*_HISTORY_KEYS, "k")}
    for seg, host_aux, _ in aux_blocks:
        for name, col in zip(_HISTORY_KEYS, host_aux):
            hist[name].extend(float(v) for v in col)
        hist["k"].extend([seg.k] * seg.length)
    for name in hist:
        del hist[name][upto:]
    return hist


def run_epochs(
    task,
    state: PyTree,
    *,
    mu: float,
    num_epochs: int,
    key: jax.Array,
    schedule: str = "const:2",
    step_size: str = "default",
    axis_name: AxisName = None,
    reducer=None,
    comm_state: Optional[PyTree] = None,
    iterate: Optional[low_rank.FactoredIterate] = None,
    max_rank: Optional[int] = None,
    masks: Optional[jax.Array] = None,
    gap_tol: Optional[float] = None,
    block_epochs: Optional[int] = None,
    segment_wrapper: Optional[Callable[[Callable], Callable]] = None,
    callback: Optional[Callable[[int, EpochAux], None]] = None,
    mode: str = "scan",
    start_t: int = 0,
    initial_history: Optional[Dict[str, list]] = None,
    checkpointer=None,
    telemetry: Optional[Telemetry] = None,
    num_workers: int = 1,
    solver="rank1",
    probe: Optional[PyTree] = None,
    end_setup: Optional[Callable[[], None]] = None,
) -> EngineResult:
    """Run up to ``num_epochs`` DFW-Trace epochs, device-resident.

    ``comm_state`` defaults to ``reducer.init_state(task.d, task.m)`` (one
    worker's state); a sharded driver passes its worker-stacked version,
    matching whatever its ``segment_wrapper`` strips/restores. ``iterate``
    defaults to a fresh ``low_rank.init`` with ``max_rank`` capacity
    (validated >= num_epochs). ``masks`` is the full ``(num_epochs, nw)``
    straggler-weight schedule or ``None`` for unweighted epochs.

    ``mode="scan"`` (production): one dispatch per segment, host transfers
    at boundaries only. ``mode="legacy"``: the pre-engine loop — per-epoch
    dispatch plus four blocking scalar pulls — same math, same carry, kept
    as the equivalence oracle and overhead baseline.

    **Checkpointing.** ``checkpointer`` (``repro.checkpoint.dfw.
    RunCheckpointer`` or anything duck-compatible) makes the run durable:
    on the segment boundaries the checkpointer *wants*, the engine fetches
    the carry + the not-yet-fetched aux history with ONE explicit batched
    ``device_get`` and hands them over for an async write — dispatch counts
    are unchanged, boundaries it doesn't want stay sync-free (unless
    ``gap_tol``/callback already sync there), and the hot path never blocks
    on disk (the D2H snapshot is the only added cost). The epoch-t
    checkpoint holds everything the remaining epochs read, so a later run
    can resume from it.

    **Resume.** ``start_t`` (a segment boundary reached by a previous run —
    any checkpoint step qualifies) starts the carry at epoch ``start_t``
    instead of 0; ``state``/``iterate``/``comm_state``/``key`` must then be
    the restored carry fields, ``initial_history`` the restored per-epoch
    history (length ``start_t``), and ``masks``/``num_epochs``/``schedule``
    the full-run values — the plan is recomputed from ``start_t`` and the
    same executables re-dispatch, reproducing the uninterrupted trajectory
    bit-for-bit (pinned in ``tests/test_checkpoint_resume.py``).

    ``end_setup`` is called once, just before the first segment dispatch
    (``launch/dfw.fit`` closes its ``engine.setup`` span with it).

    **Telemetry.** ``telemetry`` (``repro.obs.Telemetry``; inert no-op when
    None) records compile/dispatch/segment spans, per-executable comm cost
    (analytic ``Reducer.wire_bytes`` vs dense logical bytes, scaled by
    ``num_workers``, plus an HLO walk once per compile when the handle
    wants it), per-epoch loss/gap/sigma/gamma counter samples, and the
    early-stop instant. Every scalar rides a ``device_get`` the engine
    already performs — enabling telemetry adds zero host syncs and zero
    dispatches, which the contract pins in ``tests/test_engine.py`` verify
    with an enabled handle under the transfer guard.
    """
    if mode not in ("scan", "legacy"):
        raise ValueError(f"mode={mode!r}: expected 'scan' or 'legacy'")
    if not 0 <= start_t < num_epochs:
        raise ValueError(
            f"start_t={start_t}: must lie in [0, num_epochs={num_epochs}) — "
            "a run checkpointed at or past num_epochs has nothing left to do"
        )
    if initial_history is not None:
        for name, vals in initial_history.items():
            if len(vals) != start_t:
                raise ValueError(
                    f"initial_history[{name!r}] has {len(vals)} entries for "
                    f"start_t={start_t}; pass the restored prefix unmodified"
                )
    sspec = parse_solver(solver)
    if sspec.kind == "block" and sspec.k > min(task.d, task.m):
        raise ValueError(
            f"solver block:{sspec.k}: block width exceeds "
            f"min(d={task.d}, m={task.m})"
        )
    k_block = sspec.k if sspec.kind == "block" else 1
    if reducer is None:
        from ..comm.base import DenseReducer

        reducer = DenseReducer()
    if comm_state is None:
        # Block mode flattens (d,k)/(m,k) blocks through the reducer, so
        # stateful encodings (topk residuals) must be sized for the
        # flattened payload.
        comm_state = reducer.init_state(task.d * k_block, task.m * k_block)
    if iterate is None:
        iterate = low_rank.init(
            resolve_max_rank(max_rank, num_epochs, k_block), task.d, task.m
        )
    if sspec.kind == "block":
        if probe is None:
            probe = init_probe(sspec, task.m, key)
    else:
        probe = () if probe is None else probe
    if masks is not None:
        if masks.shape[0] != num_epochs:
            raise ValueError(
                f"masks has {masks.shape[0]} rows for {num_epochs} epochs"
            )
        if masks.shape[1] > 1 and segment_wrapper is None:
            # The scan body reads masks[t, 0]: each worker's own column after
            # shard_map slices the (num_epochs, nw) array. Without a wrapper
            # there is one "worker", and silently using column 0 would make a
            # multi-worker mask schedule measure nothing.
            raise ValueError(
                f"masks has {masks.shape[1]} worker columns but no "
                "segment_wrapper shards them; pass a shard_map wrapper "
                "(engine.shard_map_segment_wrapper) or a single-column mask"
            )

    segments = plan_segments(
        schedule, num_epochs, 1 if mode == "legacy" else block_epochs,
        start=start_t,
    )
    stats = {
        "segments_planned": len(segments),
        "segments_run": 0,
        "dispatches": 0,
        "compilations": 0,
        "programs_reused": 0,
        "host_syncs": 0,
    }
    has_masks = masks is not None
    wrapper = segment_wrapper if segment_wrapper is not None else (lambda f: f)
    tel = telemetry if telemetry is not None else Telemetry.noop()
    # Everything a segment program closes over, but its (K, length).
    program_key = ("segment", task, mu, step_size, axis_name, reducer, gap_tol,
                   has_masks, sspec, segment_wrapper, tel.wants_hlo,
                   code_of(task, reducer))

    compiled: Dict[tuple, Callable] = {}

    def get_compiled(seg: Segment, args: tuple) -> tuple:
        """``(program, built)``: the run's program for ``seg``'s (K, length)
        and whether this lookup built it (a miss of the program cache)."""
        sig = (seg.k, seg.length)
        if sig in compiled:
            return compiled[sig], False

        def build():
            fn = _segment_step(
                task, mu, seg.k, seg.length,
                step_size=step_size, axis_name=axis_name, reducer=reducer,
                gap_tol=gap_tol, has_masks=has_masks, solver=sspec,
            )
            jitted = jax.jit(wrapper(fn))
            if not tel.wants_hlo:
                return jitted
            # Ahead-of-time compile so the post-SPMD HLO is in hand for the
            # one-time comm walk; the executable itself dispatches, so the
            # compile is still counted (and paid) exactly once. jax.jit is
            # kept on the non-HLO path because its call cache is independent
            # of lower().compile() — mixing them would compile twice.
            with tel.span("engine.compile", "engine", k=seg.k,
                          length=seg.length):
                exe = jitted.lower(*args).compile()
            _emit_executable_cost(seg, exe)
            return exe

        program, hit = cached_program(
            program_key + (sig, _arg_signature(args)), build, tel
        )
        compiled[sig] = program
        stats["compilations"] += 1
        stats["programs_reused"] += hit
        if sspec.kind == "block" and tel.enabled:
            # How the program's block LMO forms its products (built or
            # reused alike), so a job's counters show the route it ran.
            route = "kwide" if has_block_products(task) else "vmap"
            tel.registry.counter(f"power.block_programs.{route}").inc()
        return program, not hit

    def _emit_executable_cost(seg: Segment, exe) -> None:
        """One HLO walk per executable (never per step): wire-level
        collective bytes/counts straight from the compiled module."""
        try:
            from ..analysis import hlo as hlo_lib

            text = exe.as_text()
            info = hlo_lib.analyze(text)
        except Exception:  # pragma: no cover - HLO text formats drift
            return
        tel.event(
            "comm.executable", "comm", k=seg.k, length=seg.length,
            hlo_collective_bytes=info["collective_bytes_total"],
            hlo_collective_count={k: v for k, v in info["collective_count"].items()},
            hlo_flops=info["flops"],
            tpu_kernel_calls=hlo_lib.tpu_kernel_calls(text),
        )

    # Analytic per-segment comm cost: 2*K *exchanges* per epoch (K for
    # d-vectors + K for m-vectors), wire bytes from the reducer's own
    # accounting, logical bytes at the dense-f32 convention. The block
    # solver keeps the exchange count and widens each payload by k
    # (flattened (d,k)/(m,k) blocks through the same reducer). A topology
    # (``comm.Topology`` quacks like a Reducer here) may spend several
    # collective rounds per exchange (gossip's R mixing rounds), which
    # ``rounds_per_exchange`` scales into the round count.
    def _comm_cost(seg: Segment) -> Dict[str, float]:
        rpe = int(getattr(reducer, "rounds_per_exchange", 1))
        rounds = 2 * seg.k * seg.length * rpe
        logical = 8.0 * (task.d + task.m) * k_block * seg.k * seg.length
        wire = float(
            seg.k * seg.length * (
                reducer.wire_bytes(task.d * k_block, num_workers)
                + reducer.wire_bytes(task.m * k_block, num_workers)
            )
        )
        return {"rounds": rounds, "logical_bytes": logical, "wire_bytes": wire}

    # Per-hop byte split (topologies only: hier's intra/inter, gossip's
    # neighbor links, flat's single global hop). Empty for plain reducers.
    def _hop_cost(seg: Segment) -> Dict[str, float]:
        hop_fn = getattr(reducer, "hop_wire_bytes", None)
        if hop_fn is None:
            return {}
        out: Dict[str, float] = {}
        for dim in (task.d * k_block, task.m * k_block):
            for hop, nbytes in hop_fn(dim).items():
                out[hop] = out.get(hop, 0.0) + float(seg.k * seg.length * nbytes)
        return out

    carry = init_carry(state, iterate, key, comm_state, t=start_t, probe=probe)
    done = jnp.zeros((), jnp.bool_)
    nrun = jnp.full((), start_t, jnp.int32)
    history: Dict[str, list] = {
        k: list(initial_history[k]) if initial_history is not None else []
        for k in (*_HISTORY_KEYS, "k")
    }

    # Lazy one-time host copy of the mask schedule for checkpoint payloads.
    host_masks_cache: List[Any] = []

    def _host_masks():
        if masks is None:
            return None
        if not host_masks_cache:
            with tel.span("engine.fetch", "engine", kind="masks"):
                host_masks_cache.append(jax.device_get(masks))
            stats["host_syncs"] += 1
            # Straggler accounting rides this one-time fetch (checkpoint
            # payloads are the only consumer that forces it — when nothing
            # fetches the masks, the counts are deliberately not observed
            # rather than paying a sync for them).
            hm = host_masks_cache[0]
            if tel.enabled:
                alive_hist = tel.registry.histogram("engine.alive_workers")
                alive = [int((row > 0).sum()) for row in hm]
                for a in alive:
                    alive_hist.observe(a)
                tel.event(
                    "engine.straggler_masks", "engine",
                    epochs=len(alive), num_workers=int(hm.shape[1]),
                    min_alive=min(alive) if alive else None,
                    mean_alive=sum(alive) / len(alive) if alive else None,
                )
        return host_masks_cache[0]

    if mode == "legacy":
        if end_setup is not None:
            end_setup()
        # Pre-engine behavior: one dispatch + four blocking float() pulls
        # per epoch (each an implicit device->host transfer, like the old
        # driver's `float(aux.loss)` lines). Boundaries are every epoch, so
        # a checkpointer here saves (at most) once per epoch.
        epochs_run = start_t
        for i, seg in enumerate(segments):
            args = (carry, done, nrun) + ((masks,) if has_masks else ())
            t_disp = tel.now_us()
            carry, done, nrun, aux = get_compiled(seg, args)[0](*args)
            stats["dispatches"] += 1
            stats["segments_run"] += 1
            row = [float(aux.loss[0]), float(aux.gap[0]),
                   float(aux.sigma[0]), float(aux.gamma[0])]
            stats["host_syncs"] += 4
            t_end = tel.now_us()
            tel.complete("engine.segment", "engine", t_disp, t_end - t_disp,
                         start=seg.start, length=seg.length, k=seg.k)
            for name, val in zip(_HISTORY_KEYS, row):
                tel.counter_sample(f"dfw.{name}", val, ts_us=t_end)
            for name, val in zip(_HISTORY_KEYS, row):
                history[name].append(val)
            history["k"].append(seg.k)
            epochs_run += 1
            if callback is not None:
                callback(seg.start, jax.device_get(aux))
                stats["host_syncs"] += 1
            stop = gap_tol is not None and row[1] <= gap_tol
            if checkpointer is not None:
                last = stop or i == len(segments) - 1
                if checkpointer.want(i, last):
                    host_carry = jax.device_get(carry)
                    stats["host_syncs"] += 1
                    checkpointer.save_segment(
                        t=epochs_run, carry=host_carry, history=history,
                        masks=_host_masks(), done=stop,
                    )
            if stop:
                tel.event("engine.early_stop", "engine", epoch=epochs_run,
                          gap=row[1], gap_tol=gap_tol)
                break
        return EngineResult(
            carry=carry, history=history, epochs_run=epochs_run, stats=stats
        )

    # (Segment, host EpochAux | None, device EpochAux) per segment run; the
    # host slot is filled when a callback or checkpoint already fetched the
    # block, so the final history assembly never transfers the same rows
    # twice. ``seg_ts`` is the parallel dispatch-time list (us) and
    # ``recorded`` the blocks whose telemetry has been emitted — both ride
    # alongside rather than inside the tuples so ``_assemble_history``'s
    # 3-tuple unpacking stays untouched.
    aux_blocks: List[tuple] = []
    seg_ts: List[float] = []
    recorded: set = set()

    def _record_block(idx: int, t_end_us: float) -> None:
        """Telemetry for a block whose host aux just landed: the segment
        span (dispatch -> data on host), the analytic comm byte counters,
        and per-epoch scalar samples timestamped by linear interpolation
        across the span. Pure bookkeeping on already-fetched host values —
        no device access."""
        if idx in recorded or not tel.enabled:
            return
        recorded.add(idx)
        seg, host_aux, _ = aux_blocks[idx]
        t0 = seg_ts[idx]
        dur = max(t_end_us - t0, 0.0)
        tel.complete("engine.segment", "engine", t0, dur,
                     start=seg.start, length=seg.length, k=seg.k)
        cost = _comm_cost(seg)
        reg = tel.registry
        reg.counter("comm.rounds").inc(cost["rounds"])
        reg.counter("comm.logical_bytes").inc(cost["logical_bytes"])
        reg.counter("comm.wire_bytes").inc(cost["wire_bytes"])
        # Topology-mode accounting: a per-hop byte counter split
        # (comm.hop_bytes.intra/inter/...).
        for h, b in _hop_cost(seg).items():
            reg.counter(f"comm.hop_bytes.{h}").inc(b)
        if sspec.kind == "block":
            reg.gauge("dfw.block.k").set(k_block)
        for j in range(seg.length):
            vals = [float(col[j]) for col in host_aux]
            if math.isnan(vals[0]):  # lax.cond no-op filler past early stop
                continue
            ts = t0 + dur * (j + 1) / seg.length
            for name, val in zip(_HISTORY_KEYS, vals):
                tel.counter_sample(f"dfw.{name}", val, ts_us=ts)
                reg.gauge(f"dfw.{name}").set(val)
            if sspec.kind == "block":
                # Executed block power iterations (host aux's piters column
                # — rides the fetch the engine already performs, zero added
                # syncs; < K per epoch when the adaptive stop fired).
                reg.counter("dfw.block.power_iters").inc(
                    float(host_aux.piters[j])
                )
            reg.counter("engine.epochs").inc()

    for i, seg in enumerate(segments):
        args = (carry, done, nrun) + ((masks,) if has_masks else ())
        # Without HLO capture a program built in this process is traced, and
        # compiled or loaded from the persistent cache, inside its first call.
        exe, built = get_compiled(seg, args)
        compiles = built and not tel.wants_hlo
        if i == 0 and end_setup is not None:
            end_setup()
        t_disp = tel.now_us()
        with tel.span("engine.dispatch", "engine", t0=t_disp, start=seg.start,
                      length=seg.length, k=seg.k):
            with (tel.span("engine.compile", "engine", k=seg.k, length=seg.length)
                  if compiles else contextlib.nullcontext()):
                carry, done, nrun, aux = exe(*args)
        stats["dispatches"] += 1
        stats["segments_run"] += 1
        host_aux = None
        host_done = None
        if callback is not None or (checkpointer is not None and gap_tol is not None):
            # The light boundary fetch: aux rows + the two scalars — it
            # serves the callback and the early-stop check in one sync.
            # Without a callback or gap_tol, boundaries the checkpointer
            # does NOT want stay sync-free, preserving the dispatch
            # pipelining and the batched end-of-run aux fetch.
            with tel.span("engine.fetch", "engine", kind="boundary"):
                host_aux, host_done, host_nrun = jax.device_get((aux, done, nrun))
            stats["host_syncs"] += 1
            host_done = bool(host_done)
            if callback is not None:
                callback(seg.start, host_aux)
        aux_blocks.append((seg, host_aux, aux))
        seg_ts.append(t_disp)
        if host_aux is not None:
            _record_block(i, tel.now_us())
        if checkpointer is not None:
            last = bool(host_done) or i == len(segments) - 1
            if checkpointer.want(i, last):
                # One batched sync: the carry (the payload) plus every aux
                # block not yet on host (skipped boundaries included) plus
                # the scalars — the checkpoint needs the full history-so-far
                # anyway, and the blocks are reused by the final assembly.
                pending_idx = [
                    j for j, (_, h, _) in enumerate(aux_blocks) if h is None
                ]
                with tel.span("engine.fetch", "engine", kind="checkpoint"):
                    host_carry, pend, host_done, host_nrun = jax.device_get(
                        (carry, [aux_blocks[j][2] for j in pending_idx], done, nrun)
                    )
                stats["host_syncs"] += 1
                host_done = bool(host_done)
                for j, h in zip(pending_idx, pend):
                    aux_blocks[j] = (aux_blocks[j][0], h, aux_blocks[j][2])
                t_fetch = tel.now_us()
                for j in pending_idx:
                    _record_block(j, t_fetch)
                t_now = int(host_nrun)
                checkpointer.save_segment(
                    t=t_now, carry=host_carry,
                    history=_assemble_history(history, aux_blocks, t_now),
                    masks=_host_masks(), done=host_done,
                )
        if gap_tol is not None:
            if host_done is None:
                # The only mid-run sync: one scalar at the segment boundary,
                # deciding whether to launch the next segment.
                stats["host_syncs"] += 1
                with tel.span("engine.fetch", "engine", kind="done-flag"):
                    host_done = bool(jax.device_get(done))
            if host_done:
                break

    pending = [a for _, h, a in aux_blocks if h is None]
    with tel.span("engine.fetch", "engine", kind="final"):
        fetched, epochs_run = jax.device_get((pending, nrun))
    stats["host_syncs"] += 1
    t_final = tel.now_us()
    # Host work on the fetched rows: the history, and the telemetry's
    # per-epoch samples, which are most of it when it is on.
    with tel.span("engine.history", "engine"):
        epochs_run = int(epochs_run)
        it = iter(fetched)
        aux_blocks = [
            (seg, h if h is not None else next(it), a) for seg, h, a in aux_blocks
        ]
        for j in range(len(aux_blocks)):
            _record_block(j, t_final)
        history = _assemble_history(history, aux_blocks, epochs_run)
        if gap_tol is not None and epochs_run < num_epochs:
            gap_hist = history.get("gap") or [float("nan")]
            tel.event("engine.early_stop", "engine", epoch=epochs_run,
                      gap=gap_hist[-1], gap_tol=gap_tol)
    return EngineResult(
        carry=carry, history=history, epochs_run=epochs_run, stats=stats
    )
