"""Declarative HLO/dispatch contracts — the executable form of the paper's
cost model.

DFW-Trace's performance claims are statements about *compiled artifacts and
runtime counters*, not about Python: an epoch with K power iterations costs
exactly 2K collective rounds (paper Alg. 2 + the carried-sigma fix), a
``const:K`` run is one scan dispatch, serving never materializes the d x m
matrix, and nothing crosses device->host implicitly. A ``Contract`` states
those bounds once, next to the code that owns them (``core/power_method.
collective_rounds_contract``, ``core/engine.dispatch_contract``,
``serve.ServingEngine.contract``), and the test suites + ``make analyze``
check the *same* declaration — replacing the copy-pasted HLO walks and
stats asserts that used to live in each test file.

Checking has three independent surfaces, used as the clause mix demands:

- ``check_hlo(fn_or_compiled, *args)`` lowers/compiles (or takes an already
  compiled executable / raw HLO text), walks the post-SPMD module via
  ``analysis.hlo``, and asserts the collective-count and forbidden-shape
  clauses against what XLA actually emitted.
- ``check_stats(stats)`` asserts the dispatch/compile/host-sync caps against
  the runtime counters the engine/serving layers maintain.
- ``guard()`` is the transfer-discipline context: inside it, any implicit
  device->host transfer raises (``jax.transfer_guard_device_to_host``).

All violations raise ``ContractViolation`` (an ``AssertionError``) naming
the contract, the clause, and observed-vs-allowed.

``python tools/repro_contracts.py`` (the ``make analyze`` tier 2) verifies
every declared contract at probe scale on 8 fake CPU devices.
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

from . import hlo


class ContractViolation(AssertionError):
    """A compiled artifact or runtime counter broke a declared invariant."""


def _as_hlo_text(target: Any, *args, **kwargs) -> str:
    """HLO text of ``target``: raw text, a compiled executable (anything
    with ``as_text``), or a callable to ``jit(...).lower(*args).compile()``
    (args may be concrete arrays or ``jax.ShapeDtypeStruct``s)."""
    if isinstance(target, str):
        return target
    if hasattr(target, "as_text"):
        return target.as_text()
    if callable(target):
        import jax

        return jax.jit(target).lower(*args, **kwargs).compile().as_text()
    raise TypeError(
        f"cannot extract HLO from {type(target).__name__}; pass HLO text, a "
        "compiled executable, or a callable + example args"
    )


def measure(target: Any, *args, **kwargs) -> Dict:
    """``analysis.hlo.analyze`` of ``target``'s post-SPMD module — the
    measurement half of a contract check, exposed for relational tests
    (e.g. dense-vs-int8 wire-byte ratios) that compare two measurements
    rather than assert one bound."""
    return hlo.analyze(_as_hlo_text(target, *args, **kwargs))


def _shape_pattern(dims: Sequence[int]) -> re.Pattern:
    # f32[40,28]{1,0} / bf16[40,28] — any dtype, optional layout suffix.
    body = ",".join(str(int(d)) for d in dims)
    return re.compile(r"\b\w+\[" + body + r"\]")


@dataclasses.dataclass(frozen=True)
class Contract:
    """One layer's declared cost/discipline invariants.

    HLO clauses (checked by ``check_hlo``):

    - ``collective_counts``: the executed (trip-multiplied) per-type
      collective counts must equal this mapping exactly — e.g. the power
      method's ``{"all-reduce": 2K}``.
    - ``max_collective_rounds``: total executed collectives <= bound (use
      when the mix is flexible but the round budget is not).
    - ``forbid_shapes``: no op in the compiled module may produce a tensor
      of any of these shapes — e.g. ``((d, m), (m, d))`` pins factor-form
      serving to never densify the iterate.

    Counter clauses (checked by ``check_stats`` against the engine/serving
    ``stats`` dicts): ``max_dispatches``, ``max_compilations``,
    ``max_host_syncs``.

    ``no_host_transfers`` is the transfer-guard discipline: run the
    workload under ``with contract.guard():`` and any implicit
    device->host pull raises at the offending line.

    Telemetry clauses (checked by ``check_telemetry`` against a
    ``repro.obs.Telemetry`` handle): ``max_noop_span_us`` caps the
    amortized cost of entering+exiting one ``span()`` on the handle, and
    ``max_events`` caps its recorded event count — together they pin the
    disabled default to "free and silent" (``repro.obs.noop_contract``).
    """

    name: str
    collective_counts: Optional[Mapping[str, float]] = None
    max_collective_rounds: Optional[float] = None
    forbid_shapes: Tuple[Tuple[int, ...], ...] = ()
    max_dispatches: Optional[int] = None
    max_compilations: Optional[int] = None
    max_host_syncs: Optional[int] = None
    no_host_transfers: bool = False
    max_noop_span_us: Optional[float] = None
    max_events: Optional[int] = None

    # ------------------------------------------------------------- helpers
    def _fail(self, clause: str, detail: str):
        raise ContractViolation(f"contract {self.name!r}: {clause}: {detail}")

    # ----------------------------------------------------------------- hlo
    def check_hlo(self, target: Any, *args, **kwargs) -> Dict:
        """Assert the HLO clauses against ``target``'s compiled module;
        returns the ``analysis.hlo.analyze`` dict for further inspection."""
        text = _as_hlo_text(target, *args, **kwargs)
        analysis = hlo.analyze(text)
        counts = analysis["collective_count"]
        if self.collective_counts is not None:
            want = {k: float(v) for k, v in self.collective_counts.items()}
            if counts != want:
                self._fail(
                    "collective_counts",
                    f"compiled module executes {counts or '{}'}, declared {want}",
                )
        if self.max_collective_rounds is not None:
            total = sum(counts.values())
            if total > self.max_collective_rounds:
                self._fail(
                    "max_collective_rounds",
                    f"{total} executed collectives > {self.max_collective_rounds}"
                    f" (by type: {counts})",
                )
        for dims in self.forbid_shapes:
            pat = _shape_pattern(dims)
            for line in text.splitlines():
                stripped = line.strip()
                m = pat.search(stripped)
                # Only op *results* count (lines defining a value); operand
                # mentions repeat the defining op's shape anyway.
                if m and "=" in stripped:
                    self._fail(
                        "forbid_shapes",
                        f"shape {tuple(dims)} materialized by: "
                        f"{stripped[:160]}",
                    )
        return analysis

    # --------------------------------------------------------------- stats
    def check_stats(self, stats: Mapping[str, int]) -> None:
        """Assert the runtime-counter caps against an engine/serving
        ``stats`` dict (only the declared caps are checked)."""
        for key, cap in (
            ("dispatches", self.max_dispatches),
            ("compilations", self.max_compilations),
            ("host_syncs", self.max_host_syncs),
        ):
            if cap is None:
                continue
            if key not in stats:
                self._fail(key, f"stats dict has no {key!r} counter: {dict(stats)}")
            if stats[key] > cap:
                self._fail(key, f"{stats[key]} > declared max {cap} ({dict(stats)})")

    # ----------------------------------------------------------- telemetry
    def check_telemetry(self, telemetry, iters: int = 2000) -> None:
        """Assert the telemetry clauses against a ``repro.obs.Telemetry``
        handle: time ``iters`` empty ``span()`` entries/exits (amortized
        per-span cost vs ``max_noop_span_us``), then cap the handle's
        recorded event count at ``max_events``. An *enabled* handle run
        against the no-op contract fails the event clause — that is the
        point: the inert default must record nothing."""
        import time

        if self.max_noop_span_us is not None:
            t0 = time.perf_counter()
            for _ in range(iters):
                with telemetry.span("contract.noop_probe"):
                    pass
            per_span_us = (time.perf_counter() - t0) * 1e6 / iters
            if per_span_us > self.max_noop_span_us:
                self._fail(
                    "max_noop_span_us",
                    f"{per_span_us:.2f}us per span() > declared "
                    f"{self.max_noop_span_us}us",
                )
        if self.max_events is not None:
            n = telemetry.event_count()
            if n > self.max_events:
                self._fail(
                    "max_events",
                    f"handle recorded {n} events > declared {self.max_events}"
                    f" (enabled={telemetry.enabled})",
                )

    # --------------------------------------------------------------- guard
    def guard(self):
        """Context manager enforcing ``no_host_transfers`` (no-op when the
        contract doesn't declare it)."""
        if not self.no_host_transfers:
            return contextlib.nullcontext()
        import jax

        return jax.transfer_guard_device_to_host("disallow")


# ---------------------------------------------------------------------------
# Declared-contract verification (tier 2 of `make analyze`)
# ---------------------------------------------------------------------------


def verify_declared(verbose: bool = True) -> int:
    """Build and check every layer-declared contract at probe scale.

    Requires >= 8 devices for the collective-round contracts —
    ``tools/repro_contracts.py`` sets ``XLA_FLAGS`` fake-device count
    before jax initializes. Returns a process exit code.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..compat import shard_map_compat
    from ..core import engine, frank_wolfe, power_method, tasks
    from ..serve import ServeConfig, ServingEngine
    from ..core import low_rank

    failures = 0

    def report(contract: Contract, err: Optional[Exception], note: str):
        nonlocal failures
        if err is None:
            if verbose:
                print(f"contract {contract.name}: OK ({note})")
        else:
            failures += 1
            print(f"contract {contract.name}: FAIL\n  {err}")

    # 1. Power method: an epoch's K iterations cost exactly 2K collective
    # rounds (the carried-sigma invariant), on an 8-way data mesh.
    K, n, m = 3, 512, 48
    c = power_method.collective_rounds_contract(K)
    try:
        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))

        def run(a, v0):
            return power_method.power_iterations(
                lambda v: a @ v, lambda u: a.T @ u, v0, K, axis_name="data"
            )

        wrapped = shard_map_compat(
            run,
            mesh,
            in_specs=(P("data"), P()),
            out_specs=power_method.PowerResult(u=P(), v=P(), sigma=P()),
        )
        a = jax.ShapeDtypeStruct((n, m), jnp.float32)
        v0 = jax.ShapeDtypeStruct((m,), jnp.float32)
        c.check_hlo(wrapped, a, v0)
        report(c, None, f"8-way, K={K}: all-reduce == {2 * K}")
    except Exception as e:  # noqa: BLE001 — every failure must be reported
        report(c, e, "")

    # 1b. Block power method: K block iterations still cost exactly 2K
    # all-reduce rounds — the (k,k) Gram orthogonalization runs on the
    # replicated reduced block, adding zero rounds at any block width.
    Kb, kb = 3, 4
    c = power_method.block_collective_rounds_contract(Kb, kb)
    try:
        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))

        def run_block(a, v0):
            return power_method.block_power_iterations(
                lambda v: a @ v, lambda u: a.T @ u, v0, Kb, axis_name="data"
            )

        bspec = power_method.BlockPowerResult(
            u=P(), v=P(), sigma=P(), probe=P(), iters=P()
        )
        wrapped = shard_map_compat(
            run_block,
            mesh,
            in_specs=(P("data"), P()),
            out_specs=(bspec, ()),
        )
        a = jax.ShapeDtypeStruct((n, m), jnp.float32)
        v0 = jax.ShapeDtypeStruct((m, kb), jnp.float32)
        c.check_hlo(wrapped, a, v0)
        report(c, None, f"8-way, K={Kb}, k={kb}: all-reduce == {2 * Kb}")
    except Exception as e:  # noqa: BLE001
        report(c, e, "")

    # 2. Engine: a const:K run is one scan dispatch (+ final loss eval),
    # device-resident under the transfer guard.
    c = engine.dispatch_contract()
    try:
        key = jax.random.PRNGKey(0)
        kx, kw = jax.random.split(key)
        w = jax.random.normal(kw, (24, 18))
        x = jax.random.normal(kx, (400, 24))
        task = tasks.MultiTaskLeastSquares(d=24, m=18)
        state = task.init_state(x, x @ w)
        with c.guard():
            res = frank_wolfe.fit(
                task, state, mu=1.0, num_epochs=30, key=jax.random.PRNGKey(1),
                step_size="linesearch",
            )
        c.check_stats(res.stats)
        report(c, None, f"30-epoch const:2 stats {res.stats}")
    except Exception as e:  # noqa: BLE001
        report(c, e, "")

    # 2b. Engine dispatch pins hold with the block solver enabled: same
    # segment plan, same dispatch/sync/transfer budget — the block tier
    # changes the per-epoch math, never the execution discipline.
    c = engine.dispatch_contract(name="engine.dispatch[solver=block:4:adapt]")
    try:
        key = jax.random.PRNGKey(0)
        kx, kw = jax.random.split(key)
        w = jax.random.normal(kw, (24, 18))
        x = jax.random.normal(kx, (400, 24))
        task = tasks.MultiTaskLeastSquares(d=24, m=18)
        state = task.init_state(x, x @ w)
        with c.guard():
            res = frank_wolfe.fit(
                task, state, mu=1.0, num_epochs=30, key=jax.random.PRNGKey(1),
                step_size="linesearch", solver="block:4:adapt",
            )
        c.check_stats(res.stats)
        report(c, None, f"30-epoch const:2 block:4:adapt stats {res.stats}")
    except Exception as e:  # noqa: BLE001
        report(c, e, "")

    # 3. Serving: no compiled scoring executable materializes the d x m
    # (or m x d) matrix, and dispatch+swap run transfer-guarded.
    d_s, m_s = 48, 36
    eng = ServingEngine(
        d_s, m_s, ServeConfig(max_batch=8, rank_block=8, verify_kernels=False)
    )
    c = eng.contract(max_compilations=1)
    try:
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        it = low_rank.FactoredIterate(
            u=jax.random.normal(ks[0], (5, d_s)),
            s=jax.random.normal(ks[1], (5,)),
            v=jax.random.normal(ks[2], (5, m_s)),
            alpha=jnp.asarray(0.9, jnp.float32),
            count=jnp.asarray(5, jnp.int32),
        )
        with c.guard():
            eng.load(low_rank.pack_live(it))
            pending = eng.score_async(jnp.ones((3, d_s)))
        pending.block()
        eng.check_contract(c)
        report(c, None, f"rank-5 load + dispatch, stats {eng.stats}")
    except Exception as e:  # noqa: BLE001
        report(c, e, "")

    # 4. Observability: the no-op Telemetry default is free (sub-contract
    # per-span overhead) and silent (zero recorded events) — the guarantee
    # that lets every layer accept a handle unconditionally.
    from ..obs import Telemetry, noop_contract

    c = noop_contract()
    try:
        c.check_telemetry(Telemetry.noop())
        report(c, None, "no-op handle: spans free, event stream empty")
    except Exception as e:  # noqa: BLE001
        report(c, e, "")

    if failures:
        print(f"{failures} contract(s) FAILED")
    elif verbose:
        print("all declared contracts OK")
    return 1 if failures else 0
