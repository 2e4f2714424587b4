"""Span tracing + event stream with JSONL and Chrome-trace sinks.

A :class:`Telemetry` handle is the single object threaded through
``DFWConfig``, ``frank_wolfe.fit`` and ``ServeConfig``. It owns

* a :class:`~repro.obs.registry.MetricsRegistry` (aggregates),
* a bounded in-memory event stream (the timeline), and
* export sinks: ``write_jsonl`` (one JSON object per line) and
  ``write_chrome_trace`` (a ``chrome://tracing`` / Perfetto-loadable
  trace), plus an optional ``jax.profiler`` hook for XLA-level capture.

Zero-sync discipline: nothing in this module touches a device value.
Instrumentation sites hand in host scalars they already have — engine
epoch scalars ride the existing segment-boundary ``device_get``, comm
bytes are computed analytically / from HLO once per executable, and
checkpoint latency is stamped on the writer thread. The no-op handle
(``Telemetry.noop()``) records nothing and allocates nothing per call;
its overhead is pinned by ``analysis/contracts.py`` via
:func:`noop_contract`.

Events are stored in Chrome trace-event form (ph "X" complete spans,
"i" instants, "C" counter samples) so both sinks serialize the same
dicts; timestamps are microseconds from the handle's creation
(``time.perf_counter`` based — monotonic, sub-us resolution).

Causality: every span and instant records in ``args`` the name of the
innermost context span open on its thread (``parent``; absent at the top
level) and the id of the job it belongs to (``job``; one per
``launch/dfw.fit`` call, see :meth:`Telemetry.job`). A layer's self time is
its span minus the part its children cover.

One clock with the device trace: when ``profiler_dir`` is set, every
context span is also written to the profiler's host plane as a
``jax.profiler.TraceAnnotation`` of the same name. The offset from this
handle's clock to the profiler's is the profiler start of a job's
``engine.setup`` annotation minus that span's ``ts``; adding it places the
retroactive events (``engine.segment``, ``jit.*``) on the device timeline.
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

from .registry import MetricsRegistry

__all__ = ["Telemetry", "noop_contract"]


class _NullSpan:
    """Shared do-nothing span returned by a disabled handle."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """Context manager emitting one ph="X" complete event on exit. While
    open it is the ``parent`` of what its thread records, and with a
    ``profiler_dir`` it is also a profiler annotation."""

    __slots__ = ("_tel", "_name", "_cat", "_t0", "_args", "_parent", "_anno")

    def __init__(self, tel: "Telemetry", name: str, cat: str,
                 t0: Optional[float], args: Dict[str, Any]):
        self._tel = tel
        self._name = name
        self._cat = cat
        self._t0 = t0
        self._args = args
        self._anno = None

    def __enter__(self):
        tel = self._tel
        if tel.profiler_dir:
            import jax

            self._anno = jax.profiler.TraceAnnotation(self._name)
            self._anno.__enter__()
        stack = tel._stack()
        self._parent = stack[-1]._name if stack else None
        stack.append(self)
        if self._t0 is None:
            self._t0 = tel.now_us()
        return self

    def __exit__(self, exc_type, exc, tb):
        tel = self._tel
        t1 = tel.now_us()
        tel._stack().remove(self)
        tel._record_x(self._name, self._cat, self._t0, t1 - self._t0,
                      self._parent, self._args)
        if self._anno is not None:
            self._anno.__exit__(exc_type, exc, tb)
        return False


# jax.monitoring events a job listens to (``Telemetry.job``). Each timed
# compile phase becomes a retroactive span and a seconds counter; a backend
# compile (an executable built by XLA or loaded from the persistent cache)
# also counts one ``jit.compiles``.
_JIT_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": ("jit.trace", "jit.trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("jit.lower", "jit.lower_s"),
    "/jax/core/compile/backend_compile_duration": ("jit.compile", "jit.compile_s"),
}
_JIT_COUNTS = {
    "/jax/compilation_cache/cache_hits": "jit.cache_hits",
}
_JIT_COUNTERS = ("jit.compiles", *_JIT_COUNTS.values(),
                *(c for _, c in _JIT_PHASES.values()))


class Telemetry:
    """Run-wide telemetry handle (metrics registry + event stream + sinks).

    Parameters
    ----------
    enabled:
        When False the handle is inert: every record call is a cheap
        no-op, ``span()`` returns a shared null context manager, and the
        event stream stays empty. ``Telemetry.noop()`` returns a module
        singleton built this way.
    capture_hlo:
        Allow instrumentation sites to take the ahead-of-time compile
        path and run ``analysis/hlo.py`` over each executable (once per
        compile, never per step). Off by default only on the noop handle.
    max_events:
        Hard cap on the in-memory stream; past it events are counted as
        dropped rather than appended, so a runaway loop cannot exhaust
        host memory.
    profiler_dir:
        When set, ``profiler()`` brackets the run with
        ``jax.profiler.start_trace/stop_trace`` writing XLA-level data
        there, and every context span is also a profiler annotation; when
        None the hook is a no-op.
    """

    def __init__(self, enabled: bool = True, *, capture_hlo: bool = True,
                 max_events: int = 200_000,
                 profiler_dir: Optional[str] = None):
        self.enabled = bool(enabled)
        self.capture_hlo = bool(capture_hlo)
        self.max_events = int(max_events)
        self.profiler_dir = profiler_dir
        self.registry = MetricsRegistry()
        self._events: List[Dict[str, Any]] = []
        self._dropped = 0
        self._lock = threading.Lock()  # checkpoint writer thread emits too
        self._pid = os.getpid()
        self._t0_perf = time.perf_counter()
        self._t0_unix = time.time()
        self._local = threading.local()  # per-thread stack of open spans
        self._job: Optional[int] = None
        self._jobs = 0
        self._jit_depth = 0  # compile phases open (``job``)

    # -- time ---------------------------------------------------------------

    def now_us(self) -> float:
        """Microseconds since this handle was created (monotonic)."""
        return (time.perf_counter() - self._t0_perf) * 1e6

    # -- recording ----------------------------------------------------------

    @property
    def wants_hlo(self) -> bool:
        return self.enabled and self.capture_hlo

    def _append(self, ev: Dict[str, Any]) -> None:
        # Lock-free on the common path: list.append is atomic under the
        # GIL, which is all the concurrent checkpoint-writer thread needs.
        # The cap check races benignly — a burst can overshoot max_events
        # by at most one event per appending thread. Measured in situ this
        # halves the per-event cost on the serving fetch path.
        if len(self._events) < self.max_events:
            self._events.append(ev)
        else:
            with self._lock:
                self._dropped += 1

    def _stack(self) -> List[_Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _tagged(self, args: Dict[str, Any], parent: Optional[str]) -> Dict[str, Any]:
        if parent is not None:
            args["parent"] = parent
        if self._job is not None:
            args["job"] = self._job
        return args

    def _current_parent(self) -> Optional[str]:
        stack = self._stack()
        return stack[-1]._name if stack else None

    def _record_x(self, name: str, cat: str, ts_us: float, dur_us: float,
                  parent: Optional[str], args: Dict[str, Any]) -> None:
        self._append({"name": name, "cat": cat, "ph": "X",
                      "ts": round(ts_us, 3), "dur": round(max(dur_us, 0.0), 3),
                      "pid": self._pid, "tid": threading.get_ident(),
                      "args": self._tagged(args, parent)})

    def span(self, name: str, cat: str = "run",
             t0: Optional[float] = None, **args: Any):
        """Context manager producing a complete ("X") event on exit.

        ``t0`` (microseconds, from :meth:`now_us`) backdates the span
        start — used when the enclosing work began before the handle
        could be consulted (e.g. a dispatch whose wall time is only
        known at the blocking fetch).
        """
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, cat, t0, args)

    def complete(self, name: str, cat: str, ts_us: float, dur_us: float,
                 **args: Any) -> None:
        """Record a retroactive complete span [ts_us, ts_us + dur_us]. Its
        ``parent`` is the context span open on this thread now. It never
        reaches the profiler's trace (see the module docstring for the
        offset that places it there)."""
        if not self.enabled:
            return
        self._record_x(name, cat, ts_us, dur_us, self._current_parent(), args)

    def event(self, name: str, cat: str = "run",
              ts_us: Optional[float] = None, **args: Any) -> None:
        """Record an instant ("i") event, e.g. early_stop or hot_swap."""
        if not self.enabled:
            return
        self._append({"name": name, "cat": cat, "ph": "i", "s": "t",
                      "ts": round(self.now_us() if ts_us is None else ts_us, 3),
                      "pid": self._pid, "tid": threading.get_ident(),
                      "args": self._tagged(args, self._current_parent())})

    def counter_sample(self, name: str, value: float, cat: str = "metrics",
                       ts_us: Optional[float] = None) -> None:
        """Record a ph="C" counter sample (renders as a track in Perfetto)."""
        if not self.enabled:
            return
        self._append({"name": name, "cat": cat, "ph": "C",
                      "ts": round(self.now_us() if ts_us is None else ts_us, 3),
                      "pid": self._pid, "tid": 0,
                      "args": {"value": value}})

    def event_count(self) -> int:
        with self._lock:
            return len(self._events)

    def events(self) -> List[Dict[str, Any]]:
        """Copy of the event stream (Chrome trace-event dicts)."""
        with self._lock:
            return list(self._events)

    # -- jobs and JAX's compile events --------------------------------------

    def job(self):
        """Context manager around one job (a ``launch/dfw.fit`` call): the
        events recorded inside carry its id as ``job``, and JAX's compile
        events are listened to through ``jax.monitoring`` until it exits.

        They become the counters ``jit.compiles`` (executables built by XLA
        or loaded from the persistent cache), ``jit.cache_hits``,
        ``jit.trace_s``, ``jit.lower_s`` and ``jit.compile_s``, and the
        retroactive spans ``jit.trace``, ``jit.lower`` and ``jit.compile``
        (with ``fun_name``). Only a phase that starts while no other is open is
        counted and recorded: the jitted helpers traced inside a trace or a
        lowering are part of it. The listeners are process-wide, so a
        compile on another thread during the job counts too. A disabled
        handle registers nothing."""
        if not self.enabled:
            return _NULL_SPAN
        return self._job_scope()

    @contextmanager
    def _job_scope(self):
        from jax import monitoring

        self._jobs += 1
        outer_job, self._job = self._job, self._jobs
        outer_depth, self._jit_depth = self._jit_depth, 0
        for name in _JIT_COUNTERS:  # present, at 0, in every job's snapshot
            self.registry.counter(name)
        listeners = (
            # JAX records a phase's start as a scalar and its end as a time
            # span: the pair gives the depth of nested phases.
            (monitoring.register_scalar_listener,
             monitoring.unregister_scalar_listener, self._on_jit_start),
            (monitoring.register_event_time_span_listener,
             monitoring.unregister_event_time_span_listener, self._on_jit_span),
            (monitoring.register_event_listener,
             monitoring.unregister_event_listener, self._on_jit_event),
        )
        for register, _, cb in listeners:
            register(cb)
        try:
            yield self._job
        finally:
            for _, unregister, cb in listeners:
                unregister(cb)
            self._job, self._jit_depth = outer_job, outer_depth

    def _on_jit_start(self, event: str, value: float, **kw) -> None:
        if event in _JIT_PHASES:
            self._jit_depth += 1

    def _on_jit_span(self, event: str, start: float, end: float, **kw) -> None:
        names = _JIT_PHASES.get(event)
        if names is None:
            return
        self._jit_depth = max(self._jit_depth - 1, 0)
        if self._jit_depth:  # inside an outer phase, which counts it
            return
        span_name, seconds = names
        # start/end are time.time() readings: convert at this instant, so
        # that the wall clock's drift from perf_counter does not enter.
        ts = self.now_us() - (time.time() - start) * 1e6
        self.complete(span_name, "jit", ts, (end - start) * 1e6,
                      fun_name=kw.get("fun_name"))
        self.registry.counter(seconds).inc(end - start)
        if span_name == "jit.compile":
            self.registry.counter("jit.compiles").inc()

    def _on_jit_event(self, event: str, **kw) -> None:
        name = _JIT_COUNTS.get(event)
        if name is not None:
            self.registry.counter(name).inc()

    # -- jax.profiler hook --------------------------------------------------

    @contextmanager
    def profiler(self):
        """Bracket a region with XLA-level capture when profiler_dir is set."""
        if not (self.enabled and self.profiler_dir):
            yield
            return
        import jax

        jax.profiler.start_trace(self.profiler_dir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    # -- sinks --------------------------------------------------------------

    def _meta(self) -> Dict[str, Any]:
        return {"type": "meta", "t0_unix": self._t0_unix, "pid": self._pid,
                "clock": "us_since_start", "dropped_events": self._dropped,
                "max_events": self.max_events}

    def write_jsonl(self, path) -> None:
        """One JSON object per line: meta, then events, then a final
        ``{"type": "metrics", ...}`` registry snapshot."""
        events = self.events()
        with open(path, "w") as fh:
            fh.write(json.dumps(self._meta()) + "\n")
            for ev in events:
                fh.write(json.dumps(ev) + "\n")
            fh.write(json.dumps({"type": "metrics",
                                 "data": self.registry.snapshot()}) + "\n")

    def write_chrome_trace(self, path) -> None:
        """Chrome trace JSON (open in Perfetto / chrome://tracing)."""
        doc = {
            "traceEvents": self.events(),
            "displayTimeUnit": "ms",
            "otherData": {"meta": self._meta(),
                          "metrics": self.registry.snapshot()},
        }
        with open(path, "w") as fh:
            json.dump(doc, fh)

    # -- no-op singleton ----------------------------------------------------

    _NOOP: Optional["Telemetry"] = None

    @classmethod
    def noop(cls) -> "Telemetry":
        """Shared inert handle — the default everywhere a Telemetry is
        accepted. Records nothing; its per-span overhead is contract-pinned."""
        if cls._NOOP is None:
            cls._NOOP = cls(enabled=False, capture_hlo=False, max_events=0)
        return cls._NOOP


def noop_contract():
    """Contract pinning the disabled handle: sub-50us span entry/exit and
    a permanently empty event stream. Checked by ``make analyze`` probe 4."""
    from repro.analysis.contracts import Contract

    return Contract(name="obs.noop_overhead", max_noop_span_us=50.0,
                    max_events=0)
