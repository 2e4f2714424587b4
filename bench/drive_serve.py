"""Serving traffic: open-loop arrivals of feature-row batches.

Requests arrive as a Poisson process at the traffic file's fixed
``rate_per_s``; each is a batch of 1 to ``rows_max`` feature rows, its size
drawn from a geometric distribution of mean ``rows_mean`` truncated at
``rows_max`` (a client scoring a page or an album of images). Arrival times,
sizes and rows all come from the seed. Each request is dispatched with
``ServingEngine.score_async`` when it is due, never held back to be batched
with others, and collected in order with ``block()``. A request's latency
runs from the time it was *due* to its scores being on the host, so a stall
delays every request behind it.

Set-up draws the model -- factors of live rank ``live_rank`` -- and a pool
of request rows on the device from the seed, loads the model into the
engine (which compiles its one scorer) and scores a few requests to warm up.
After the window, the reference scores a sample of the requests drawn from
the seed, with the largest request in it, and compares.

The traffic file's keys: ``live_rank``, ``max_batch``, ``rank_block``,
``rate_per_s``, ``rows_mean``, ``rows_max``, ``pool_rows``, ``check_sample``,
``trace_requests`` (how many requests a traced run records) and ``limits``.
"""
from __future__ import annotations

import collections
import time
from functools import partial
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, trace_reduce


def schedule(seed: int, rate: float, seconds: float, rows_mean: float,
             rows_max: int, pool_rows: int):
    """Due times (s from the window's start), sizes and pool offsets of the
    requests due inside ``seconds``."""
    rng = np.random.default_rng([int(seed) % (1 << 64), 0x5E12])
    n = int(rate * seconds * 1.5) + 64
    due = np.cumsum(rng.exponential(1.0 / rate, n))
    sizes = np.minimum(rng.geometric(1.0 / rows_mean, n), rows_max)
    offsets = rng.integers(0, pool_rows - rows_max + 1, n)
    keep = due < seconds
    return due[keep], sizes[keep].astype(int), offsets[keep].astype(int)


def wait_until(deadline: float, clock=time.perf_counter) -> None:
    """Sleep to within a millisecond of ``deadline``, then spin to it."""
    left = deadline - clock()
    if left > 2e-3:
        time.sleep(left - 1e-3)
    while clock() < deadline:
        pass


def open_loop(due: Sequence[float], dispatch: Callable, collect: Callable,
              clock: Callable[[], float] = time.perf_counter,
              wait: Callable[[float], None] = wait_until, hooks=None):
    """Dispatch every request that is due, then collect the oldest in
    flight; when nothing is in flight, wait for the next due time.

    Returns the start time and, per request, the dispatch and completion
    times relative to it."""
    n = len(due)
    sent, done = np.zeros(n), np.zeros(n)
    pending: collections.deque = collections.deque()
    t0 = clock()
    i = 0
    while i < n or pending:
        while i < n and t0 + due[i] <= clock():
            if hooks:
                hooks.before_dispatch(i)
            sent[i] = clock() - t0
            pending.append((i, dispatch(i)))
            i += 1
        if pending:
            j, handle = pending.popleft()
            collect(j, handle)
            done[j] = clock() - t0
            if hooks:
                hooks.after_collect(j)
        elif i < n:
            wait(t0 + due[i])
    return t0, sent, done


def quantile_ms(values_s, q: float) -> float:
    """Nearest-rank quantile of seconds, in milliseconds."""
    return float(np.quantile(np.asarray(values_s), q, method="inverted_cdf") * 1e3)


class _TraceHook:
    """Trace the requests dispatched from ``first`` until ``last`` is
    collected."""

    def __init__(self, first: int, last: int):
        self.first, self.last = first, last
        self.out: dict = {}
        self.traced: List[int] = []
        self._cm = None

    def before_dispatch(self, i):
        if i == self.first:
            self._cm = trace_reduce.capture(self.out)
            self._cm.__enter__()
            self._ann = harness.annotate(trace_reduce.WINDOW, True)
            self._ann.__enter__()
        if self._cm is not None:
            self.traced.append(i)

    def after_collect(self, j):
        if j == self.last and self._cm is not None:
            self._ann.__exit__(None, None, None)
            self._cm.__exit__(None, None, None)
            self._cm = None


@partial(jax.jit, static_argnames=("rank", "d", "m", "pool_rows"))
def _draw(key, *, rank, d, m, pool_rows):
    ku, kv, ks, kx = jax.random.split(key, 4)
    u = jax.random.normal(ku, (rank, d), jnp.float32)
    v = jax.random.normal(kv, (rank, m), jnp.float32)
    u = u / jnp.linalg.norm(u, axis=1, keepdims=True)
    v = v / jnp.linalg.norm(v, axis=1, keepdims=True)
    s = jax.random.uniform(ks, (rank,), jnp.float32, 0.5, 1.5)
    pool = jax.random.normal(kx, (pool_rows, d), jnp.float32)
    return u, s, v, pool


def reference_scores(u, s, v, alpha, x, dtype=None):
    """x @ (alpha * U.T diag(s) V), in float32 at HIGHEST precision or in
    ``dtype``."""
    dtype = jnp.float32 if dtype is None else dtype
    prec = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    w = jnp.dot((u.T * (alpha * s)).astype(dtype), v.astype(dtype), precision=prec)
    return jnp.dot(jnp.asarray(x, dtype), w, precision=prec).astype(jnp.float32)


def score_error(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def check_sample(seed: int, sizes: np.ndarray, k: int) -> List[int]:
    rng = np.random.default_rng([int(seed) % (1 << 64), 0xC4EC])
    pick = set(rng.choice(len(sizes), size=min(k, len(sizes)), replace=False).tolist())
    pick.add(int(np.argmax(sizes)))
    return sorted(pick)


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, engine_factory=None) -> dict:
    from repro.serve import ServeConfig, ServingEngine

    cfg, tr = cell.config, cell.traffic
    d, m, rank = cfg["d"], cfg["m"], tr["live_rank"]
    u, s, v, pool = _draw(harness.seed_key(seed), rank=rank, d=d, m=m,
                          pool_rows=tr["pool_rows"])
    packed = jax.device_get({"u": u, "s": s, "v": v,
                             "alpha": np.float32(1.0), "count": np.int32(rank)})
    pool = np.asarray(jax.device_get(pool))
    tel = harness.annotating_telemetry() if trace else None
    scfg = ServeConfig(max_batch=tr["max_batch"], rank_block=tr["rank_block"],
                       telemetry=tel)
    eng = (engine_factory or ServingEngine)(d, m, scfg)
    eng.load(packed)
    for b in (1, tr["rows_max"], 8, 3, 64 if tr["rows_max"] >= 64 else 2):
        eng.score_async(pool[:b]).block()

    due, sizes, offsets = schedule(seed, tr["rate_per_s"], seconds,
                                   tr["rows_mean"], tr["rows_max"], tr["pool_rows"])
    sample = set(check_sample(seed, sizes, tr["check_sample"]))
    kept, errors = {}, []

    def dispatch(i):
        try:
            return eng.score_async(pool[offsets[i]:offsets[i] + sizes[i]])
        except Exception as e:
            errors.append(i)
            return e

    def collect(j, handle):
        if isinstance(handle, Exception):
            return
        try:
            out = handle.block()
        except Exception:
            errors.append(j)
            return
        if j in sample:
            kept[j] = np.array(out)

    hook = None
    if trace:
        first = max(0, len(due) // 2 - tr["trace_requests"] // 2)
        hook = _TraceHook(first, min(len(due) - 1, first + tr["trace_requests"] - 1))
    t_open = time.perf_counter()
    t0, sent, done = open_loop(due, dispatch, collect, hooks=hook)
    t_close = time.perf_counter()
    memory_peak = harness.memory_peak_bytes(1)

    ok = np.ones(len(due), bool)
    ok[errors] = False
    worst = 0.0
    for j in sorted(sample):
        x = pool[offsets[j]:offsets[j] + sizes[j]]
        want = np.asarray(jax.device_get(reference_scores(u, s, v, 1.0, x)))
        got = kept.get(j)
        e = float("inf") if got is None or got.shape != want.shape else score_error(got, want)
        worst = max(worst, e)
    checks = [{"name": "score_err", "value": worst,
               "limit": tr["limits"]["score_err"]}]
    lat = done - due
    e2e = {"setup_s": t_open - t_start,
           "serve_p50_ms": quantile_ms(lat, 0.5),
           "serve_p99_ms": quantile_ms(lat, 0.99)}
    ctx = {
        "cell": cell, "device_kind": jax.devices()[0].device_kind,
        "d": d, "m": m, "rank": rank, "sizes": sizes,
        "latency_s": lat, "queue_s": sent - due, "service_s": done - sent,
        "window_s": t_close - t_open,
        "trace": trace_reduce.reduce(hook.out.get("trace")) if hook else None,
        "traced_requests": hook.traced if hook else [],
    }
    return {"attempted": len(due), "failed": int((~ok).sum()), "e2e": e2e,
            "checks": checks, "ctx": ctx, "memory_peak": memory_peak}
