"""Factor-form low-rank serving engine.

The traffic-facing consumer of a DFW-Trace iterate. Training keeps the model
as the factor triple ``W = alpha * U^T diag(s) V`` with rank <= T (paper
§2.2); this engine scores requests directly against those factors —
``x @ W`` is ``alpha * ((x @ U^T) * s) @ V`` — so the scoring path is
O(batch * rank * (d + m)) FLOPs and O(rank * (d + m)) memory and the dense
d x m matrix is never materialized (`kernels/factor_matvec` is the fused
Pallas hot path; Yun et al.'s streaming completion serving, arXiv:1107.0789,
is the same never-densify discipline at cluster scale).

Three serving-specific contracts, all about *static shapes*:

* **Padded micro-batches.** Every scoring call is padded to the engine's
  ``max_batch`` rows, so ONE ahead-of-time compiled executable serves every
  batch size 1..max_batch — request traffic never triggers a recompile, and
  latency is flat in the batch fill. Padding rows are zeros; callers get
  exactly their rows back.
* **Live-rank bucket packing.** Models load via ``low_rank.pack_live``: a
  t-epoch iterate ships t factors, padded up to the next ``rank_block``
  multiple (zero ``s`` rows — exact no-ops in the kernel). Per-request
  FLOPs therefore track the model's *actual* rank at rank_block
  granularity, not the training run's ``max_rank`` capacity.
* **Hot-swap without recompiles or drops.** ``load`` stages the new model's
  factors onto device, then atomically republishes the engine's model
  reference. Executables are keyed by rank bucket: a swap inside the same
  bucket reuses the compiled scorer (``stats["compilations"]`` is the pin —
  ahead-of-time compilation means a shape change *raises* rather than
  silently recompiling). In-flight batches hold references to the old
  factor arrays — jax arrays are immutable, so they complete against
  exactly the model they were dispatched with; nothing blocks, nothing is
  dropped.

Scoring never pulls device->host implicitly: ``score_async`` returns a
``PendingScores`` handle whose ``block()`` performs the one explicit
``device_get`` (the same transfer-guard discipline as ``core/engine``,
pinned in ``tests/test_serve.py``).
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Any, Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import dfw as ckpt
from ..checkpoint.store import CheckpointStore
from ..core import low_rank
from ..kernels.factor_matvec import ops as fm_ops
from ..obs import MetricsRegistry, Telemetry

ModelSource = Union[
    low_rank.FactoredIterate, Dict[str, Any], CheckpointStore, str, Path
]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Knobs of one serving engine.

    ``max_batch`` is the padded static batch capacity — the one executable
    per rank bucket scores exactly this many rows per dispatch.
    ``rank_block`` is the live-rank bucket granularity: models whose live
    ranks land in the same bucket share an executable, so routine
    checkpoint-to-checkpoint hot-swaps (rank grows by one per epoch) only
    compile when the rank crosses a bucket boundary. ``transpose=False``
    scores ``x @ W`` (requests are d-vectors of features, scores are
    m-vectors over tasks/classes — the ``dfw_head``/MTLS convention);
    ``transpose=True`` scores ``x @ W^T`` (m -> d, the paper's
    ``U (s ⊙ V^T x)`` direction). ``use_pallas``/``interpret`` route the
    fused kernel exactly like ``launch/dfw.DFWConfig``.

    ``telemetry`` (a ``repro.obs.Telemetry``; None = inert no-op) backs the
    engine's ``stats`` counters with the handle's registry and records
    per-dispatch latency histograms plus load/hot-swap/compile events — the
    no-op default's overhead is contract-pinned (<2% p50, measured by
    ``benchmarks/serving_latency.py``).
    """

    max_batch: int = 64
    rank_block: int = 32
    transpose: bool = False
    use_pallas: Optional[bool] = None
    interpret: bool = False
    verify_kernels: bool = True
    block_o: int = 256
    telemetry: Optional[Telemetry] = None

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError(f"max_batch={self.max_batch}: must be >= 1")
        if self.rank_block < 1:
            raise ValueError(f"rank_block={self.rank_block}: must be >= 1")


class Model:
    """One loaded model version: capacity-padded device factors + metadata.

    Immutable by convention (and jax arrays by construction): a swap builds
    a new ``Model``; anything already scoring against this one is safe.
    """

    __slots__ = ("u", "s", "v", "alpha", "live_rank", "capacity", "version", "step")

    def __init__(self, *, u, s, v, alpha, live_rank, capacity, version, step):
        self.u = u  # (capacity, d) device
        self.s = s  # (capacity,) device; rows >= live_rank are 0
        self.v = v  # (capacity, m) device
        self.alpha = alpha  # () device
        self.live_rank = int(live_rank)
        self.capacity = int(capacity)
        self.version = int(version)
        self.step = step  # checkpoint step or None


class PendingScores:
    """A dispatched scoring batch: device-resident until ``block()``.

    ``raw`` is the full (max_batch, n_out) device array; ``block()`` does
    the single explicit device->host transfer and returns the caller's
    ``n`` rows (cached — blocking twice transfers once). ``version``/
    ``step`` stamp which model scored the batch, so hot-swap tests can
    prove in-flight batches completed against the model they were
    dispatched with.
    """

    __slots__ = ("raw", "n", "version", "step", "_host", "_tel", "_t0", "_hist")

    def __init__(self, raw: jax.Array, n: int, version: int, step,
                 telemetry: Optional[Telemetry] = None, t0_us: float = 0.0,
                 latency_hist=None):
        self.raw = raw
        self.n = n
        self.version = version
        self.step = step
        self._host: Optional[np.ndarray] = None
        self._tel = telemetry
        self._t0 = t0_us
        # Pre-bound by the engine: a registry lookup per fetch costs real
        # microseconds on this path (cold caches after an XLA dispatch).
        self._hist = latency_hist

    def block(self) -> np.ndarray:
        if self._host is None:
            self._host = np.asarray(jax.device_get(self.raw))[: self.n]
            # Dispatch->host latency, stamped exactly once per batch on the
            # transfer the caller already pays for (zero added syncs).
            tel = self._tel
            if tel is not None and tel.enabled:
                dur = tel.now_us() - self._t0
                tel.complete("serve.dispatch", "serve", self._t0, dur,
                             n=self.n, version=self.version)
                self._hist.observe(dur)
        return self._host


def rank_bucket(live_rank: int, rank_block: int) -> int:
    """Smallest ``rank_block`` multiple >= max(live_rank, 1): the executable
    capacity serving this live rank. Rank 0 (an untrained iterate) shares
    the first bucket — its ``s`` rows are all zero, so it scores exactly 0
    through the same executable rather than needing a degenerate one."""
    return rank_block * max(1, -(-live_rank // rank_block))


def _as_packed(source: ModelSource, step: Optional[int]):
    """Normalize a model source to (packed_dict, step, extra)."""
    if isinstance(source, low_rank.FactoredIterate):
        return low_rank.pack_live(source), None, {}
    if isinstance(source, dict):
        missing = [k for k in low_rank.packed_like() if k not in source]
        if missing:
            raise ValueError(f"packed iterate dict is missing {missing}")
        return source, None, {}
    if isinstance(source, (CheckpointStore, str, Path)):
        step, packed, extra = ckpt.read_iterate_packed(source, step)
        return packed, step, extra
    raise TypeError(
        f"cannot load a model from {type(source).__name__}; pass a "
        "FactoredIterate, a pack_live dict, or a checkpoint store/directory"
    )


class ServingEngine:
    """Score request batches against a hot-swappable factored model.

    Built for a fixed problem shape ``(d, m)``; every loaded model must
    match it. ``load`` is both first load and hot-swap. ``score`` /
    ``score_async`` accept 1..max_batch requests of dimension ``n_in``
    (= d, or m when ``transpose``) and return ``n_out`` scores per request.

    ``stats`` counters mirror ``core/engine``'s pins: ``compilations``
    (ahead-of-time executable builds — the hot-swap regression pin),
    ``dispatches`` (scoring calls), ``loads`` (models published),
    ``requests`` (caller rows scored, excluding padding). They are backed
    by ``repro.obs`` registry counters (``serve.*``) — on the telemetry
    handle's registry when one is configured, else a private registry —
    and ``stats`` is a read-only snapshot; ``check_contract()``'s pins are
    unchanged by the migration.
    """

    def __init__(self, d: int, m: int, cfg: ServeConfig = ServeConfig()):
        self.d, self.m = int(d), int(m)
        self.cfg = cfg
        self.n_in = self.m if cfg.transpose else self.d
        self.n_out = self.d if cfg.transpose else self.m
        self._model: Optional[Model] = None
        self._compiled: Dict[int, Any] = {}  # rank capacity -> executable
        self._verified = not cfg.verify_kernels
        self.telemetry = (
            cfg.telemetry if cfg.telemetry is not None else Telemetry.noop()
        )
        # A disabled handle's registry is the shared no-op singleton's —
        # counting there would alias every un-instrumented engine in the
        # process onto one set of counters, so each gets its own registry.
        reg = (
            self.telemetry.registry if self.telemetry.enabled
            else MetricsRegistry()
        )
        self._counters = {
            k: reg.counter(f"serve.{k}")
            for k in ("compilations", "dispatches", "loads", "requests")
        }
        self._latency_hist = reg.histogram("serve.latency_us")

    @property
    def stats(self) -> Dict[str, int]:
        """Registry-backed counter snapshot (same keys as before the obs
        migration; see ``check_contract``)."""
        return {k: int(c.value) for k, c in self._counters.items()}

    # ------------------------------------------------------------ compile
    def _scorer(self):
        cfg = self.cfg
        kw = dict(
            use_pallas=cfg.use_pallas, interpret=cfg.interpret,
            block_b=min(128, _ceil_to(cfg.max_batch, 8)), block_o=cfg.block_o,
        )

        def score(u, s, v, alpha, x):
            if cfg.transpose:
                return fm_ops.factor_matvec(x, v, s, u, alpha=alpha, **kw)
            return fm_ops.factor_matvec(x, u, s, v, alpha=alpha, **kw)

        return score

    def _executable(self, capacity: int):
        """The ahead-of-time compiled scorer for one rank bucket. AOT (not
        plain jit) is the no-recompile guarantee: the executable admits
        exactly the (capacity, max_batch) shapes it was built for, and any
        drift raises instead of silently compiling on the request path."""
        if capacity not in self._compiled:
            f32 = jnp.float32
            sd = jax.ShapeDtypeStruct
            args = (
                sd((capacity, self.d), f32),
                sd((capacity,), f32),
                sd((capacity, self.m), f32),
                sd((), f32),
                sd((self.cfg.max_batch, self.n_in), f32),
            )
            t0 = self.telemetry.now_us()
            exe = jax.jit(self._scorer()).lower(*args).compile()
            self._compiled[capacity] = exe
            self._counters["compilations"].inc()
            self.telemetry.complete(
                "serve.compile", "serve", t0, self.telemetry.now_us() - t0,
                capacity=capacity, max_batch=self.cfg.max_batch,
            )
            if self.telemetry.wants_hlo:
                # One HLO walk per executable, mirroring the engine's
                # compile-time comm accounting (never on the request path).
                try:
                    from ..analysis import hlo as hlo_lib

                    text = exe.as_text()
                    info = hlo_lib.analyze(text)
                    self.telemetry.event(
                        "serve.executable", "serve", capacity=capacity,
                        hlo_flops=info["flops"],
                        hlo_dot_bytes=info["dot_bytes"],
                        tpu_kernel_calls=hlo_lib.tpu_kernel_calls(text),
                    )
                except Exception:  # pragma: no cover - HLO formats drift
                    pass
        return self._compiled[capacity]

    # --------------------------------------------------------------- load
    def load(self, source: ModelSource, *, step: Optional[int] = None) -> Model:
        """Publish a model (first load or hot-swap) from an in-memory
        iterate, a ``pack_live`` dict, or a run-checkpoint directory/store
        (``step=None`` means its latest step).

        The new model's factors are staged to device and its rank bucket's
        executable ensured *before* the engine reference flips, so there is
        no window where scoring sees a half-loaded model; batches already
        dispatched keep their (immutable) old factor arrays.
        """
        t0 = self.telemetry.now_us()
        packed, ck_step, extra = _as_packed(source, step)
        if extra:
            got = (int(extra.get("d", -1)), int(extra.get("m", -1)))
            if got != (self.d, self.m):
                raise ValueError(
                    f"checkpoint model is {got[0]}x{got[1]} but this engine "
                    f"serves {self.d}x{self.m}"
                )
        # `packed` leaves may still live on device (a dict handed over from
        # a training process): one explicit batched device_get is the load
        # path's only transfer — int()/np.asarray on the leaves would each
        # block on an implicit pull (lint rule REP002).
        packed = jax.device_get(packed)
        live = int(packed["count"])
        capacity = rank_bucket(live, self.cfg.rank_block)
        padded = low_rank.unpack_live(packed, capacity)
        if padded.u.shape[1] != self.d or padded.v.shape[1] != self.m:
            raise ValueError(
                f"model factors are {padded.u.shape[1]}x{padded.v.shape[1]} "
                f"but this engine serves {self.d}x{self.m}"
            )
        model = Model(
            u=jnp.asarray(padded.u, jnp.float32),
            s=jnp.asarray(padded.s, jnp.float32),
            v=jnp.asarray(padded.v, jnp.float32),
            alpha=jnp.asarray(packed["alpha"], jnp.float32),
            live_rank=live,
            capacity=capacity,
            version=(self._model.version + 1) if self._model else 0,
            step=ck_step,
        )
        self._verify_once(model)
        self._executable(capacity)  # compile (or reuse) before publishing
        self._model = model
        self._counters["loads"].inc()
        self.telemetry.complete(
            "serve.load", "serve", t0, self.telemetry.now_us() - t0,
            version=model.version, step=model.step, live_rank=live,
            capacity=capacity,
        )
        if model.version > 0:
            self.telemetry.event("serve.hot_swap", "serve",
                                 version=model.version, step=model.step,
                                 live_rank=live, capacity=capacity)
        return model

    @classmethod
    def from_checkpoint(
        cls,
        store: Union[CheckpointStore, str, Path],
        cfg: ServeConfig = ServeConfig(),
        *,
        step: Optional[int] = None,
    ) -> "ServingEngine":
        """Build an engine sized from a run checkpoint's manifest and load
        that checkpoint — the one-call serving bootstrap."""
        _, extra = ckpt.read_run_extra(store, step)
        eng = cls(int(extra["d"]), int(extra["m"]), cfg)
        eng.load(store, step=step)
        return eng

    # -------------------------------------------------------------- score
    @property
    def model(self) -> Model:
        if self._model is None:
            raise RuntimeError("no model loaded; call load() first")
        return self._model

    def score_async(self, x) -> PendingScores:
        """Dispatch one padded scoring batch; returns without blocking.

        ``x`` is (b, n_in) with 1 <= b <= max_batch (or a single (n_in,)
        request). The result handle is pinned to the model version at
        dispatch time — a concurrent ``load`` cannot retarget it.
        """
        model = self.model
        xh = np.asarray(x, np.float32)  # REP002-ok: host request ingress
        if xh.ndim == 1:
            xh = xh[None, :]
        b, n_in = xh.shape
        if n_in != self.n_in:
            raise ValueError(
                f"requests have dim {n_in}; this engine scores "
                f"{'m' if self.cfg.transpose else 'd'}={self.n_in}-vectors"
            )
        if not 1 <= b <= self.cfg.max_batch:
            raise ValueError(
                f"batch of {b} exceeds max_batch={self.cfg.max_batch}; "
                "split it (serve.MicroBatcher does this)"
            )
        pad = np.zeros((self.cfg.max_batch, self.n_in), np.float32)
        pad[:b] = xh
        exe = self._executable(model.capacity)
        t0 = self.telemetry.now_us()
        raw = exe(model.u, model.s, model.v, model.alpha, jnp.asarray(pad))
        self._counters["dispatches"].inc()
        self._counters["requests"].inc(b)
        return PendingScores(raw, b, model.version, model.step,
                             telemetry=self.telemetry, t0_us=t0,
                             latency_hist=self._latency_hist)

    def score(self, x) -> np.ndarray:
        """Blocking convenience: ``score_async(x).block()``."""
        return self.score_async(x).block()

    # ----------------------------------------------------------- contract
    def contract(self, *, max_compilations: Optional[int] = None):
        """The serving layer's declarative invariant (see
        ``repro.analysis.contracts``): no compiled scorer may materialize a
        d x m (or m x d) intermediate — scoring is strictly factored,
        O(t(d+m)) per request — and the request path performs no implicit
        device->host transfer. ``max_compilations`` optionally pins the AOT
        no-recompile guarantee on top."""
        from ..analysis.contracts import Contract

        return Contract(
            name=f"serve.never_materialize[{self.d}x{self.m}]",
            forbid_shapes=((self.d, self.m), (self.m, self.d)),
            max_compilations=max_compilations,
            no_host_transfers=True,
        )

    def check_contract(self, contract=None) -> "Contract":
        """Assert ``contract`` (default: ``self.contract()``) against every
        compiled executable's HLO and the engine's runtime counters. Raises
        ``ContractViolation`` with the offending HLO line on failure."""
        c = contract if contract is not None else self.contract()
        for exe in self._compiled.values():
            c.check_hlo(exe)
        c.check_stats(self.stats)
        return c

    # ------------------------------------------------------------- verify
    def _verify_once(self, model: Model) -> None:
        """First-load startup check (same role as ``launch/dfw.
        verify_kernelized``): the configured kernel path must agree with
        the dense materialized oracle before any traffic is scored."""
        if self._verified:
            return
        verify_factor_kernels(
            jax.random.PRNGKey(0x5E12),
            d=self.d,
            m=self.m,
            use_pallas=self.cfg.use_pallas,
            interpret=self.cfg.interpret,
        )
        self._verified = True


def _ceil_to(n: int, mult: int) -> int:
    return mult * (-(-n // mult))


def verify_factor_kernels(
    key: jax.Array,
    *,
    d: int,
    m: int,
    rank: int = 6,
    batch: int = 4,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    tol: float = 1e-4,
) -> float:
    """Assert the fused factor-matvec path matches the dense materialized
    product on a random triple, in both scoring directions. Returns the max
    relative error observed; raises AssertionError past ``tol``.

    The dense oracle runs at ``Precision.HIGHEST`` (TPU's default f32 matmul
    rounds operands to bf16), so the check compares the kernel with the f32
    maths."""
    from ..kernels.factor_matvec import ref as fm_ref

    ks = jax.random.split(key, 5)
    dd, mm = min(d, 96), min(m, 96)  # probe scale: the check is structural
    a = jax.random.normal(ks[0], (rank, dd))
    s = jax.random.normal(ks[1], (rank,))
    b = jax.random.normal(ks[2], (rank, mm))
    kw = dict(use_pallas=use_pallas, interpret=interpret)

    def rel_err(got, want):
        got, want = jnp.asarray(got), jnp.asarray(want)
        return float(jax.device_get(
            jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-30)
        ))

    x_d = jax.random.normal(ks[3], (batch, dd))
    x_m = jax.random.normal(ks[4], (batch, mm))
    with jax.default_matmul_precision("highest"):
        want_d = fm_ref.dense_matvec(x_d, a, s, b)
        want_m = fm_ref.dense_matvec(x_m, b, s, a)
    err = max(
        rel_err(fm_ops.factor_matvec(x_d, a, s, b, **kw), want_d),
        rel_err(fm_ops.factor_matvec(x_m, b, s, a, **kw), want_m),
    )
    if err > tol:
        raise AssertionError(
            f"factor_matvec kernels diverge from the dense oracle: rel err "
            f"{err:.3e} > tol {tol:.1e}"
        )
    return err
