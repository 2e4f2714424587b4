"""Telemetry spine (repro/obs): registry semantics, event stream, sinks,
and the zero-sync instrumentation riding the engine / checkpoint / serving
layers. Multi-device coverage runs in a subprocess with 8 fake CPU devices
(same pattern as tests/test_engine.py — device count locks at first jax
init in the main pytest process).
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import frank_wolfe, tasks
from repro.obs import Histogram, MetricsRegistry, Telemetry, noop_contract

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _run(script: str, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr[-4000:]}"
    return res.stdout


def _mtls(key, n=400, d=24, m=18):
    kx, kw = jax.random.split(key)
    w = jax.random.normal(kw, (d, m))
    w = w / jnp.linalg.norm(w, ord="nuc")
    x = jax.random.normal(kx, (n, d))
    return x, x @ w


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------


def test_registry_get_or_create_returns_same_instrument():
    reg = MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    assert reg.gauge("g") is reg.gauge("g")
    assert reg.histogram("h") is reg.histogram("h")
    reg.counter("a").inc(3)
    assert reg.snapshot()["counters"]["a"] == 3.0


def test_registry_reset_zeroes_in_place_keeping_handles():
    reg = MetricsRegistry()
    c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h")
    c.inc(5)
    g.set(1.5)
    h.observe(100.0)
    reg.reset()
    assert c.value == 0.0 and g.value is None and h.count == 0
    c.inc()  # the old handle still feeds the registry
    assert reg.snapshot()["counters"]["c"] == 1.0


def test_histogram_log2_buckets_and_summary():
    h = Histogram("lat")
    for v in (0.5, 1.0, 3.0, 1000.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4
    assert snap["min"] == 0.5 and snap["max"] == 1000.0
    assert snap["mean"] == pytest.approx((0.5 + 1 + 3 + 1000) / 4)
    # 0.5 -> bucket 0; 1.0 -> [1,2) bucket 1; 3.0 -> [2,4) bucket 2;
    # 1000 -> [512,1024) bucket 10
    assert snap["buckets"] == {"0": 1, "1": 1, "2": 1, "10": 1}


# ---------------------------------------------------------------------------
# Telemetry handle: events, bounds, no-op
# ---------------------------------------------------------------------------


def test_span_and_event_forms():
    tel = Telemetry()
    with tel.span("work", "test", detail=7):
        pass
    tel.event("marker", "test", note="x")
    tel.counter_sample("metric", 3.0)
    phs = [ev["ph"] for ev in tel.events()]
    assert phs == ["X", "i", "C"]
    span = tel.events()[0]
    assert span["name"] == "work" and span["args"] == {"detail": 7}
    assert span["dur"] >= 0.0


def test_event_stream_is_bounded_and_counts_drops():
    tel = Telemetry(max_events=3)
    for i in range(5):
        tel.event(f"e{i}")
    assert tel.event_count() == 3
    assert tel._meta()["dropped_events"] == 2


def test_noop_is_a_singleton_and_records_nothing():
    tel = Telemetry.noop()
    assert tel is Telemetry.noop()
    assert not tel.enabled and not tel.wants_hlo
    with tel.span("x"):
        pass
    tel.event("y")
    tel.complete("z", "c", 0.0, 1.0)
    assert tel.event_count() == 0
    # the declared contract agrees: spans free, stream empty
    noop_contract().check_telemetry(tel)


def test_noop_contract_rejects_an_enabled_handle():
    with pytest.raises(AssertionError):
        noop_contract().check_telemetry(Telemetry())


# ---------------------------------------------------------------------------
# Sinks: JSONL + Chrome trace from a real instrumented fit
# ---------------------------------------------------------------------------


def _instrumented_fit(tel, num_epochs=12, gap_tol=None, block_epochs=None):
    x, y = _mtls(jax.random.PRNGKey(3))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    return frank_wolfe.fit(
        task, task.init_state(x, y), mu=1.0, num_epochs=num_epochs,
        key=jax.random.PRNGKey(1), step_size="linesearch",
        gap_tol=gap_tol, block_epochs=block_epochs, telemetry=tel,
    )


def test_fit_emits_engine_and_comm_events_and_metrics():
    tel = Telemetry()
    res = _instrumented_fit(tel, num_epochs=12)
    names = {ev["name"] for ev in tel.events()}
    assert {"engine.compile", "engine.dispatch", "engine.segment",
            "engine.fetch", "engine.final_loss"} <= names
    # the exchange is accounted in counters, not in a span of the segment's
    # duration; on the device it is the comm.exchange name scope
    assert not names & {"comm.exchange", "comm.topology"}
    # per-epoch scalars ride the boundary fetch: one sample per epoch
    loss_samples = [ev for ev in tel.events() if ev["name"] == "dfw.loss"]
    assert len(loss_samples) == res.epochs_run == 12
    snap = tel.registry.snapshot()
    assert snap["counters"]["engine.epochs"] == 12
    assert snap["counters"]["comm.rounds"] == 2 * 2 * 12  # 2K rounds an epoch
    assert snap["counters"]["comm.wire_bytes"] > 0
    assert snap["gauges"]["dfw.final_loss"] == pytest.approx(
        res.final_loss, rel=1e-5)


def test_jsonl_and_chrome_trace_sinks_are_valid(tmp_path):
    tel = Telemetry()
    _instrumented_fit(tel, num_epochs=8)
    jl = tmp_path / "run.jsonl"
    ct = tmp_path / "run.trace.json"
    tel.write_jsonl(jl)
    tel.write_chrome_trace(ct)

    lines = [json.loads(s) for s in jl.read_text().splitlines()]
    assert lines[0]["type"] == "meta" and lines[-1]["type"] == "metrics"
    assert len(lines) - 2 == tel.event_count()

    doc = json.loads(ct.read_text())
    evs = doc["traceEvents"]
    assert len(evs) == tel.event_count()
    assert {ev["ph"] for ev in evs} <= {"X", "i", "C"}
    for ev in evs:  # Perfetto's minimum: name/ph/ts/pid on every event
        assert {"name", "ph", "ts", "pid"} <= set(ev)
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0


# ---------------------------------------------------------------------------
# Early stop: event epoch == epochs_run == truncated history (serial + 8-way)
# ---------------------------------------------------------------------------


def test_early_stop_event_matches_truncated_history_serial():
    # a tolerance that certifiably fires mid-run: 40% of the starting gap
    full = _instrumented_fit(Telemetry.noop(), num_epochs=40)
    tol = float(full.history["gap"][0]) * 0.4
    tel = Telemetry()
    res = _instrumented_fit(tel, num_epochs=40, gap_tol=tol, block_epochs=5)
    assert res.epochs_run < 40
    stops = [ev for ev in tel.events() if ev["name"] == "engine.early_stop"]
    assert len(stops) == 1
    assert stops[0]["args"]["epoch"] == res.epochs_run
    assert len(res.history["loss"]) == res.epochs_run
    # and no telemetry rows for the cond-skipped NaN epochs past the stop
    loss_samples = [ev for ev in tel.events() if ev["name"] == "dfw.loss"]
    assert len(loss_samples) == res.epochs_run


def test_early_stop_event_matches_truncated_history_8way():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import tasks
        from repro.launch import dfw
        from repro.obs import Telemetry

        n, d, m = 1600, 40, 30
        key = jax.random.PRNGKey(0)
        kx, kw = jax.random.split(key)
        W = jax.random.normal(kw, (d, m)); W = W / jnp.linalg.norm(W, ord="nuc")
        X = jax.random.normal(kx, (n, d)); Y = X @ W
        task = tasks.MultiTaskLeastSquares(d=d, m=m)
        full = dfw.fit(task, X, Y,
                       cfg=dfw.DFWConfig(mu=1.0, num_epochs=40,
                                         schedule="const:2",
                                         step_size="linesearch"),
                       key=jax.random.PRNGKey(1), num_workers=8)
        tol = float(full.history["gap"][0]) * 0.4
        tel = Telemetry()
        cfg = dfw.DFWConfig(mu=1.0, num_epochs=40, schedule="const:2",
                            step_size="linesearch", gap_tol=tol,
                            block_epochs=5, telemetry=tel)
        res = dfw.fit(task, X, Y, cfg=cfg, key=jax.random.PRNGKey(1),
                      num_workers=8)
        assert res.epochs_run < 40
        stops = [ev for ev in tel.events() if ev["name"] == "engine.early_stop"]
        assert len(stops) == 1, [ev["name"] for ev in tel.events()]
        assert stops[0]["args"]["epoch"] == res.epochs_run
        assert len(res.history["loss"]) == res.epochs_run
        losses = [ev for ev in tel.events() if ev["name"] == "dfw.loss"]
        assert len(losses) == res.epochs_run
        print("8-way early-stop telemetry OK", res.epochs_run)
    """)
    assert "OK" in out


# ---------------------------------------------------------------------------
# Checkpoint + serving instrumentation
# ---------------------------------------------------------------------------


def test_checkpoint_store_stamps_writes_and_prunes(tmp_path):
    from repro.checkpoint.store import CheckpointStore

    tel = Telemetry()
    store = CheckpointStore(tmp_path / "ck", keep_last=1, telemetry=tel)
    tree = {"w": np.ones((8, 8), np.float32)}
    store.save(0, tree)
    store.save_async(1, tree)
    store.wait()
    writes = [ev for ev in tel.events() if ev["name"] == "checkpoint.write"]
    assert [w["args"]["step"] for w in writes] == [0, 1]
    assert all(w["args"]["bytes"] == 8 * 8 * 4 for w in writes)
    prunes = [ev for ev in tel.events() if ev["name"] == "checkpoint.prune"]
    assert len(prunes) == 1 and prunes[0]["args"]["steps"] == [0]
    snap = tel.registry.snapshot()
    assert snap["counters"]["checkpoint.saves"] == 2
    assert snap["histograms"]["checkpoint.write_us"]["count"] == 2


def test_serving_latency_histogram_and_hot_swap_event():
    from repro import serve
    from repro.core import low_rank

    d, m, rank = 32, 24, 4
    tel = Telemetry()
    eng = serve.ServingEngine(
        d, m, serve.ServeConfig(max_batch=8, rank_block=4,
                                verify_kernels=False, telemetry=tel))
    key = jax.random.PRNGKey(0)
    it = low_rank.FactoredIterate(
        u=jax.random.normal(key, (rank, d)),
        s=jnp.ones((rank,)),
        v=jax.random.normal(key, (rank, m)),
        alpha=jnp.asarray(1.0),
        count=jnp.asarray(rank, jnp.int32),
    )
    eng.load(it)
    for _ in range(3):
        eng.score(np.ones((8, d), np.float32))
    eng.load(it._replace(s=it.s * 0.5))  # hot swap

    hist = tel.registry.snapshot()["histograms"]["serve.latency_us"]
    assert hist["count"] == 3
    names = [ev["name"] for ev in tel.events()]
    assert names.count("serve.dispatch") == 3
    assert "serve.compile" in names and "serve.hot_swap" in names
    assert eng.stats["dispatches"] == 3 and eng.stats["loads"] == 2
    # registry and stats views agree — stats is the registry now
    assert tel.registry.snapshot()["counters"]["serve.dispatches"] == 3


def test_disabled_engines_do_not_share_counters():
    """Two telemetry-off engines must not alias each other's stats through
    the shared no-op singleton's registry."""
    from repro import serve

    a = serve.ServingEngine(16, 12, serve.ServeConfig(max_batch=4,
                                                      verify_kernels=False))
    b = serve.ServingEngine(16, 12, serve.ServeConfig(max_batch=4,
                                                      verify_kernels=False))
    a._counters["dispatches"].inc()
    assert b.stats["dispatches"] == 0
    assert Telemetry.noop().registry.snapshot()["counters"] == {}


# ---------------------------------------------------------------------------
# Jobs: set-up/compile/dispatch spans, parents, JAX compile counters, and
# the offset onto the profiler's clock
# ---------------------------------------------------------------------------


def _dfw_job(tel, num_epochs=12):
    import time

    from repro.launch import dfw

    x, y = _mtls(jax.random.PRNGKey(4))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    cfg = dfw.DFWConfig(mu=1.0, num_epochs=num_epochs, schedule="const:2",
                        step_size="linesearch", telemetry=tel)
    t0 = time.perf_counter()
    res = dfw.fit(task, x, y, cfg=cfg, key=jax.random.PRNGKey(1), num_workers=1)
    return res, time.perf_counter() - t0


def test_top_level_spans_of_a_fit_job_cover_its_wall_time():
    tel = Telemetry(capture_hlo=False)
    _dfw_job(tel)  # the first job also compiles what every job shares
    t_us = tel.now_us()
    _, wall = _dfw_job(tel)
    job = [ev for ev in tel.events() if ev["ts"] >= t_us and ev["ph"] == "X"]
    assert {ev["args"]["job"] for ev in job} == {2}
    top = [ev for ev in job if "parent" not in ev["args"]]
    assert {ev["name"] for ev in top} >= {"engine.setup", "engine.dispatch",
                                          "engine.fetch", "engine.final_loss"}
    covered, reach = 0.0, float("-inf")
    for s, e in sorted((ev["ts"], ev["ts"] + ev["dur"]) for ev in top):
        covered += max(0.0, e - max(s, reach))
        reach = max(reach, e)
    assert covered / 1e6 >= 0.95 * wall
    parents = {ev["name"]: ev["args"].get("parent") for ev in job}
    assert parents["engine.verify_kernels"] == "engine.setup"
    assert parents["engine.place"] == "engine.setup"
    # each job retraces its fresh jax.jit inside the first dispatch
    assert parents["engine.compile"] == "engine.dispatch"
    assert {ev["args"]["parent"] for ev in job
            if ev["name"] == "jit.trace"} <= {"engine.compile", "engine.dispatch",
                                              "engine.place", "engine.final_loss"}


def test_complete_span_maps_onto_a_profiler_capture_by_the_offset_rule(tmp_path):
    import time

    from jax.profiler import ProfileData

    tel = Telemetry(profiler_dir=str(tmp_path))
    with tel.job(), tel.profiler():
        with tel.span("engine.setup", "engine"):
            time.sleep(0.005)
        t0 = tel.now_us()
        with jax.profiler.TraceAnnotation("probe.region"):
            time.sleep(0.03)
        tel.complete("probe.region", "test", t0, tel.now_us() - t0)
    host = {}
    for path in tmp_path.rglob("*.xplane.pb"):
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("engine.setup", "probe.region"):
                        host[e.name] = (e.start_ns, e.end_ns)
    ev = {e["name"]: e for e in tel.events() if e["ph"] == "X"}
    offset_ns = host["engine.setup"][0] - ev["engine.setup"]["ts"] * 1e3
    start = ev["probe.region"]["ts"] * 1e3 + offset_ns
    end = start + ev["probe.region"]["dur"] * 1e3
    assert abs(start - host["probe.region"][0]) < 1e6
    assert abs(end - host["probe.region"][1]) < 1e6


def test_a_second_identical_fit_reports_its_compiles_and_cache_hits(tmp_path):
    out = _run(f"""
        import jax, jax.numpy as jnp
        jax.config.update("jax_compilation_cache_dir", {str(tmp_path)!r})
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        from repro.core import tasks
        from repro.launch import dfw
        from repro.obs import Telemetry

        n, d, m = 400, 24, 18
        kx, kw = jax.random.split(jax.random.PRNGKey(0))
        W = jax.random.normal(kw, (d, m)); W = W / jnp.linalg.norm(W, ord="nuc")
        X = jax.random.normal(kx, (n, d)); Y = X @ W
        task = tasks.MultiTaskLeastSquares(d=d, m=m)
        tel = Telemetry(capture_hlo=False)
        cfg = dfw.DFWConfig(mu=1.0, num_epochs=10, schedule="const:2",
                            step_size="linesearch", telemetry=tel)
        jobs = []
        for _ in range(2):
            before = dict(tel.registry.snapshot()["counters"])
            dfw.fit(task, X, Y, cfg=cfg, key=jax.random.PRNGKey(1), num_workers=2)
            after = tel.registry.snapshot()["counters"]
            jobs.append({{k: v - before.get(k, 0.0) for k, v in after.items()
                          if k.startswith("jit.")}})
        first, second = jobs
        # a fresh cache: the first job compiles what it cannot load
        assert first["jit.cache_hits"] < first["jit.compiles"], first
        # the fresh jax.jit objects of the second job retrace and load from
        # the persistent cache: every executable is a hit, none is written
        assert second["jit.compiles"] >= 2, second
        assert second["jit.cache_hits"] == second["jit.compiles"], second
        assert second["jit.trace_s"] > 0 and second["jit.compile_s"] > 0, second
        names = {{e["args"]["fun_name"] for e in tel.events()
                  if e["name"] == "jit.compile" and e["args"].get("job") == 2}}
        assert "jit(local_loss)" in names, names
        print("compile counters OK", second)
    """)
    assert "OK" in out


def _listener_count():
    from jax._src import monitoring

    return (len(monitoring.get_event_listeners()),
            len(monitoring.get_event_duration_listeners()),
            len(monitoring.get_event_time_span_listeners()),
            len(monitoring.get_scalar_listeners()))


def test_noop_handle_registers_no_listener_and_records_nothing():
    base = _listener_count()
    during = []
    from repro.launch import dfw

    x, y = _mtls(jax.random.PRNGKey(4))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    cfg = dfw.DFWConfig(mu=1.0, num_epochs=6, schedule="const:2",
                        step_size="linesearch")
    dfw.fit(task, x, y, cfg=cfg, key=jax.random.PRNGKey(1), num_workers=1,
            callback=lambda t, aux: during.append(_listener_count()))
    assert during and all(c == base for c in during)
    assert Telemetry.noop().event_count() == 0
    assert Telemetry.noop().registry.snapshot()["counters"] == {}
    # an enabled handle listens only inside its job
    tel = Telemetry()
    with tel.job():  # event, time-span and scalar listeners; no duration
        assert _listener_count() == tuple(
            c + (i != 1) for i, c in enumerate(base))
    assert _listener_count() == base
