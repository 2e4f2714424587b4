"""Device-resident epoch engine (core/engine.py).

Covers the segment plan, scan-vs-legacy trajectory equivalence, gap-based
early stopping, the dispatch/host-sync regression pins, and the segment
granularity of the progress callback. Multi-device coverage runs in
subprocesses with 8 fake CPU devices (the device count locks at the first
jax init in the main pytest process), matching tests/test_dfw_launch.py.
"""
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm
from repro.core import engine, frank_wolfe, tasks

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


# ---------------------------------------------------------------------------
# Segment plan
# ---------------------------------------------------------------------------


def test_plan_segments_const_is_one_segment():
    (seg,) = engine.plan_segments("const:2", 30)
    assert seg == engine.Segment(start=0, length=30, k=2)


def test_plan_segments_log_is_maximal_constant_runs():
    segs = engine.plan_segments("log", 50)
    sched = frank_wolfe.k_schedule("log")
    # contiguous, exhaustive, constant-K inside, maximal at the boundaries
    t = 0
    for seg in segs:
        assert seg.start == t
        for e in range(seg.start, seg.start + seg.length):
            assert sched(e) == seg.k
        t = seg.start + seg.length
    assert t == 50
    for a, b in zip(segs, segs[1:]):
        assert a.k != b.k  # maximality: adjacent segments differ in K
    assert len(segs) <= int(np.log(50)) + 2  # O(log T) dispatches


def test_plan_segments_block_epochs_caps_length():
    segs = engine.plan_segments("const:1", 25, block_epochs=10)
    assert [s.length for s in segs] == [10, 10, 5]
    assert all(s.k == 1 for s in segs)
    with pytest.raises(ValueError, match="block_epochs"):
        engine.plan_segments("const:1", 5, block_epochs=0)
    with pytest.raises(ValueError, match="num_epochs"):
        engine.plan_segments("const:1", 0)


def test_resolve_max_rank_contract():
    assert engine.resolve_max_rank(None, 7) == 7
    assert engine.resolve_max_rank(12, 7) == 12
    with pytest.raises(ValueError, match="max_rank"):
        engine.resolve_max_rank(6, 7)


# ---------------------------------------------------------------------------
# Scan-vs-legacy trajectory equivalence (serial; the 8-way variant is below)
# ---------------------------------------------------------------------------


def _mtls(key, n=400, d=24, m=18):
    kx, kw = jax.random.split(key)
    w = jax.random.normal(kw, (d, m))
    w = w / jnp.linalg.norm(w, ord="nuc")
    x = jax.random.normal(kx, (n, d))
    return x, x @ w


def _fit_pair(task, state_fn, *, reducer=None, schedule="const:2",
              step_size="linesearch", num_epochs=10, gap_tol=None):
    out = {}
    for mode in ("scan", "legacy"):
        out[mode] = frank_wolfe.fit(
            task, state_fn(), mu=1.0, num_epochs=num_epochs,
            key=jax.random.PRNGKey(1), schedule=schedule, step_size=step_size,
            reducer=reducer, gap_tol=gap_tol, mode=mode,
        )
    return out["scan"], out["legacy"]


def _assert_traj_match(a, b):
    assert a.history["k"] == b.history["k"]
    for key in ("loss", "gap", "sigma", "gamma"):
        np.testing.assert_allclose(a.history[key], b.history[key],
                                   rtol=1e-5, atol=1e-6, err_msg=key)
    np.testing.assert_allclose(a.final_loss, b.final_loss, rtol=1e-5)


@pytest.mark.parametrize("schedule", ["const:2", "log"])
def test_scan_equals_legacy_mtls(schedule):
    x, y = _mtls(jax.random.PRNGKey(0))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    sc, lg = _fit_pair(task, lambda: task.init_state(x, y), schedule=schedule)
    _assert_traj_match(sc, lg)


def test_scan_equals_legacy_logistic_int8():
    """Logistic task + int8 reducer: the stochastic-rounding noise streams
    are keyed by the carried epoch counter, so scan and legacy draw the
    identical noise and the trajectories match."""
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (300, 20))
    yl = jax.random.randint(jax.random.fold_in(key, 1), (300,), 0, 12)
    task = tasks.MultinomialLogistic(d=20, m=12)
    sc, lg = _fit_pair(task, lambda: task.init_state(x, yl),
                       reducer=comm.Int8Reducer(num_workers=1),
                       step_size="default")
    _assert_traj_match(sc, lg)


def test_scan_equals_legacy_matrix_completion():
    key = jax.random.PRNGKey(3)
    d, m, rank = 32, 24, 4
    ku, kv, ko = jax.random.split(key, 3)
    u = jnp.linalg.qr(jax.random.normal(ku, (d, rank)))[0]
    v = jnp.linalg.qr(jax.random.normal(kv, (m, rank)))[0]
    w = (u * (jnp.linspace(1.0, 0.3, rank) / rank)) @ v.T
    mask = jax.random.bernoulli(ko, 0.4, (d, m))
    rows, cols = jnp.nonzero(mask)
    idx, yw = tasks.pack_observations(rows, cols, w[rows, cols])
    task = tasks.MatrixCompletion(d=d, m=m)
    sc, lg = _fit_pair(task, lambda: task.init_state(idx, yw))
    _assert_traj_match(sc, lg)


def test_scan_equals_legacy_with_topk_comm_state():
    """Stateful reducer: the error-feedback residuals thread through the
    scan carry exactly as through the per-epoch loop."""
    x, y = _mtls(jax.random.PRNGKey(4))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    sc, lg = _fit_pair(task, lambda: task.init_state(x, y),
                       reducer=comm.TopKReducer(k=6))
    _assert_traj_match(sc, lg)


# ---------------------------------------------------------------------------
# Gap-certificate early stopping
# ---------------------------------------------------------------------------


def test_early_stop_truncates_consistently():
    x, y = _mtls(jax.random.PRNGKey(5))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    full = frank_wolfe.fit(task, task.init_state(x, y), mu=1.0, num_epochs=40,
                           key=jax.random.PRNGKey(1), step_size="linesearch")
    tol = float(full.history["gap"][0]) * 0.4  # loose: fires mid-run
    sc, lg = _fit_pair(task, lambda: task.init_state(x, y), num_epochs=40,
                       gap_tol=tol)
    assert 0 < sc.epochs_run < 40
    assert sc.epochs_run == lg.epochs_run  # scan and legacy stop identically
    for key in ("loss", "gap", "sigma", "gamma", "k"):
        assert len(sc.history[key]) == sc.epochs_run, key
        assert np.all(np.isfinite(np.asarray(sc.history[key], np.float64))), key
    # the stopping epoch is certified; everything before it is not
    assert sc.history["gap"][-1] <= tol
    assert all(g > tol for g in sc.history["gap"][:-1])
    # the prefix matches the untruncated run
    np.testing.assert_allclose(sc.history["loss"],
                               full.history["loss"][: sc.epochs_run], rtol=1e-5)


def test_early_stop_block_epochs_bounds_overshoot():
    """block_epochs caps how far a converged run can scan past its
    certificate: with blocks of 5, at most 4 no-op epochs trail the stop."""
    x, y = _mtls(jax.random.PRNGKey(6))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    full = frank_wolfe.fit(task, task.init_state(x, y), mu=1.0, num_epochs=40,
                           key=jax.random.PRNGKey(1), step_size="linesearch")
    tol = float(full.history["gap"][0]) * 0.4
    res = frank_wolfe.fit(task, task.init_state(x, y), mu=1.0, num_epochs=40,
                          key=jax.random.PRNGKey(1), step_size="linesearch",
                          gap_tol=tol, block_epochs=5)
    assert res.epochs_run < 40
    # the engine never launched segments past the one that converged
    assert res.stats["segments_run"] <= -(-res.epochs_run // 5)


def test_gap_tol_none_runs_everything():
    x, y = _mtls(jax.random.PRNGKey(7))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    res = frank_wolfe.fit(task, task.init_state(x, y), mu=1.0, num_epochs=12,
                          key=jax.random.PRNGKey(1))
    assert res.epochs_run == 12
    assert len(res.history["loss"]) == 12


# ---------------------------------------------------------------------------
# Dispatch / host-sync regression pins (the engine's reason to exist)
# ---------------------------------------------------------------------------


def test_serial_const2_is_two_dispatches_o1_syncs():
    """A 30-epoch const:2 run is one scan dispatch (+ one final-loss eval):
    <= 2 executables, <= 2 dispatches, O(1) explicit device->host transfers,
    and — enforced by the contract's transfer guard — zero implicit per-epoch
    pulls. The bounds are ``engine.dispatch_contract()``'s declaration, not
    this test's: the same Contract backs ``tools/repro_contracts.py``."""
    x, y = _mtls(jax.random.PRNGKey(8))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    state = task.init_state(x, y)
    contract = engine.dispatch_contract()
    with contract.guard():
        res = frank_wolfe.fit(task, state, mu=1.0, num_epochs=30,
                              key=jax.random.PRNGKey(1),
                              step_size="linesearch")
    assert res.epochs_run == 30
    contract.check_stats(res.stats)
    # legacy mode, by contrast, pays per-epoch dispatches and 4 pulls/epoch
    legacy = frank_wolfe.fit(task, task.init_state(x, y), mu=1.0,
                             num_epochs=30, key=jax.random.PRNGKey(1),
                             step_size="linesearch", mode="legacy")
    assert legacy.stats["dispatches"] == 31
    assert legacy.stats["host_syncs"] >= 4 * 30


def test_serial_const2_pin_holds_with_telemetry_enabled():
    """The acceptance bar for the obs spine: a live Telemetry handle keeps
    the exact same dispatch/compile/host-sync stats under the same transfer
    guard — instrumentation rides existing transfers, it never adds one —
    and the trajectory is bit-identical to the uninstrumented run."""
    from repro.obs import Telemetry

    x, y = _mtls(jax.random.PRNGKey(8))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    base = frank_wolfe.fit(task, task.init_state(x, y), mu=1.0, num_epochs=30,
                           key=jax.random.PRNGKey(1), step_size="linesearch")
    tel = Telemetry()
    contract = engine.dispatch_contract()
    engine.clear_programs()  # both runs build their programs: equal stats
    with contract.guard():
        res = frank_wolfe.fit(task, task.init_state(x, y), mu=1.0,
                              num_epochs=30, key=jax.random.PRNGKey(1),
                              step_size="linesearch", telemetry=tel)
    assert res.epochs_run == 30
    contract.check_stats(res.stats)
    assert res.stats == base.stats
    np.testing.assert_array_equal(np.asarray(res.history["loss"]),
                                  np.asarray(base.history["loss"]))
    names = {ev["name"] for ev in tel.events()}
    assert {"engine.segment", "engine.dispatch"} <= names
    counters = tel.registry.snapshot()["counters"]
    assert counters["comm.rounds"] == 2 * 2 * 30  # 2K exchanges an epoch


def test_log_schedule_is_olog_dispatches():
    n_segments = len(engine.plan_segments("log", 30))
    contract = engine.dispatch_contract(segments=n_segments,
                                        max_compilations=None)
    x, y = _mtls(jax.random.PRNGKey(9))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    with contract.guard():
        res = frank_wolfe.fit(task, task.init_state(x, y), mu=1.0,
                              num_epochs=30, key=jax.random.PRNGKey(1),
                              schedule="log", step_size="linesearch")
    contract.check_stats(res.stats)
    # and the cap is tight: the engine really launches one scan per segment
    assert res.stats["dispatches"] == n_segments + 1


def test_sharded8_const2_is_two_dispatches_o1_syncs():
    """The 8-way pin of the acceptance bar, under the same transfer guard."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import engine, tasks
        from repro.launch import dfw

        n, d, m = 1600, 40, 30
        key = jax.random.PRNGKey(0)
        kx, kw = jax.random.split(key)
        W = jax.random.normal(kw, (d, m)); W = W / jnp.linalg.norm(W, ord="nuc")
        X = jax.random.normal(kx, (n, d)); Y = X @ W
        task = tasks.MultiTaskLeastSquares(d=d, m=m)
        cfg = dfw.DFWConfig(mu=1.0, num_epochs=30, schedule="const:2",
                            step_size="linesearch")
        contract = engine.dispatch_contract(name="engine.dispatch[8-way]")
        with contract.guard():
            res = dfw.fit(task, X, Y, cfg=cfg, key=jax.random.PRNGKey(1),
                          num_workers=8)
        assert res.epochs_run == 30
        contract.check_stats(res.stats)
        assert res.history["loss"][-1] < 0.2 * res.history["loss"][0]
        print("sharded 30-epoch const:2 stats OK", res.stats)
    """)
    assert "OK" in out


# ---------------------------------------------------------------------------
# Callback granularity: per segment, not per epoch
# ---------------------------------------------------------------------------


def test_callback_fires_per_segment_with_host_blocks():
    x, y = _mtls(jax.random.PRNGKey(10))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    calls = []
    res = frank_wolfe.fit(
        task, task.init_state(x, y), mu=1.0, num_epochs=20,
        key=jax.random.PRNGKey(1), step_size="linesearch", block_epochs=8,
        callback=lambda start, aux: calls.append((start, len(aux.loss),
                                                  np.asarray(aux.loss))),
    )
    assert [(s, n) for s, n, _ in calls] == [(0, 8), (8, 8), (16, 4)]
    # the blocks are the history, in order
    np.testing.assert_allclose(np.concatenate([b for _, _, b in calls]),
                               res.history["loss"], rtol=1e-6)


# ---------------------------------------------------------------------------
# 8-way scan-vs-legacy equivalence: three tasks, dense + int8, stragglers on
# ---------------------------------------------------------------------------


@pytest.mark.slow  # subprocess + 12 fits: the full equivalence matrix
def test_sharded8_scan_equals_legacy_all_tasks():
    out = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import tasks
        from repro.launch import dfw

        def check(task, x, y, cfg, tag):
            runs = {}
            for mode in ("scan", "legacy"):
                runs[mode] = dfw.fit(
                    task, x, y, cfg=dataclasses.replace(cfg, engine=mode),
                    key=jax.random.PRNGKey(1), num_workers=8)
            sc, lg = runs["scan"], runs["legacy"]
            assert sc.history["k"] == lg.history["k"], tag
            for k in ("loss", "gap", "sigma", "gamma"):
                np.testing.assert_allclose(sc.history[k], lg.history[k],
                                           rtol=1e-5, atol=1e-6,
                                           err_msg=f"{tag}:{k}")
            np.testing.assert_allclose(sc.final_loss, lg.final_loss, rtol=1e-5)
            if sc.masks is not None:
                np.testing.assert_allclose(np.asarray(sc.masks),
                                           np.asarray(lg.masks))
            print(tag, "OK")

        n, d, m = 1600, 40, 30
        key = jax.random.PRNGKey(0)
        kx, kw = jax.random.split(key)
        W = jax.random.normal(kw, (d, m)); W = W / jnp.linalg.norm(W, ord="nuc")
        X = jax.random.normal(kx, (n, d)); Y = X @ W
        yl = jnp.argmax(X @ W, axis=1)

        # straggler sampling ON for the whole matrix: masks are indexed
        # inside the scan, so this exercises the (num_epochs, nw) path
        base = dfw.DFWConfig(mu=1.0, num_epochs=8, schedule="const:2",
                             step_size="linesearch", sample_prob=0.7)
        mtls = tasks.MultiTaskLeastSquares(d=d, m=m)
        for comm in ("dense", "int8"):
            check(mtls, X, Y, dataclasses.replace(base, comm=comm),
                  f"mtls/{comm}")

        logi = tasks.MultinomialLogistic(d=d, m=m)
        lcfg = dfw.DFWConfig(mu=10.0, num_epochs=8, schedule="log",
                             sample_prob=0.7)
        for comm in ("dense", "int8"):
            check(logi, X, yl, dataclasses.replace(lcfg, comm=comm),
                  f"logistic/{comm}")

        d2, m2, rank = 64, 48, 5
        ku, kv, ko = jax.random.split(jax.random.PRNGKey(7), 3)
        U = jnp.linalg.qr(jax.random.normal(ku, (d2, rank)))[0]
        V = jnp.linalg.qr(jax.random.normal(kv, (m2, rank)))[0]
        sv = jnp.linspace(1.0, 0.2, rank); sv = sv / jnp.sum(sv)
        Wmc = (U * sv) @ V.T
        msk = jax.random.bernoulli(ko, 0.35, (d2, m2))
        rows, cols = jnp.nonzero(msk)
        idx8, yw8 = dfw.shard_observations(rows, cols, Wmc[rows, cols], 8,
                                           d2, m=m2)
        mc = tasks.MatrixCompletion(d=d2, m=m2)
        mcfg = dfw.DFWConfig(mu=1.5, num_epochs=8, schedule="const:2",
                             step_size="linesearch", sample_prob=0.7)
        for comm in ("dense", "int8"):
            check(mc, idx8, yw8, dataclasses.replace(mcfg, comm=comm),
                  f"mc/{comm}")

        # early stop agrees across modes in the sharded driver too
        ecfg = dfw.DFWConfig(mu=1.0, num_epochs=40, schedule="const:2",
                             step_size="linesearch")
        probe = dfw.fit(mtls, X, Y, cfg=ecfg, key=jax.random.PRNGKey(1),
                        num_workers=8)
        tol = float(probe.history["gap"][0]) * 0.4
        ecfg = dataclasses.replace(ecfg, gap_tol=tol)
        es = dfw.fit(mtls, X, Y, cfg=ecfg, key=jax.random.PRNGKey(1),
                     num_workers=8)
        el = dfw.fit(mtls, X, Y,
                     cfg=dataclasses.replace(ecfg, engine="legacy"),
                     key=jax.random.PRNGKey(1), num_workers=8)
        assert 0 < es.epochs_run < 40
        assert es.epochs_run == el.epochs_run
        assert len(es.history["loss"]) == es.epochs_run
        print("early-stop sharded OK", es.epochs_run)
        print("equivalence matrix OK")
    """, timeout=1200)
    assert "equivalence matrix OK" in out


@pytest.mark.parametrize("solver", ["rank1", "block:2"])
def test_compiled_epoch_names_the_solver_phases_in_its_hlo_metadata(solver):
    """The named scopes reach the compiled epoch as op_name metadata, which
    the device trace carries into each operation's name stack."""
    from repro.core import low_rank

    x, y = _mtls(jax.random.PRNGKey(11))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    state = task.init_state(x, y)
    carry = frank_wolfe.init_carry(
        state, low_rank.init(8, 24, 18), jax.random.PRNGKey(1),
        (), probe=frank_wolfe.init_probe(frank_wolfe.parse_solver(solver), 18))
    epoch = frank_wolfe.make_epoch_step(task, 1.0, 2, step_size="linesearch",
                                        reducer=comm.DenseReducer(),
                                        solver=solver)
    text = jax.jit(epoch).lower(carry).compile().as_text()
    for scope in ("dfw.loss", "dfw.power", "dfw.linesearch", "dfw.update"):
        assert f"/{scope}/" in text, scope


def test_block_epoch_names_its_orthonormalizations_inside_the_power_scope():
    """The block LMO's Cholesky-QR steps (Gram, Cholesky, triangular solve)
    carry ``dfw.orth`` inside ``dfw.power``, so the device trace splits the
    k-wide algebra from the matvec kernels; the rank-1 epoch has none."""
    from repro.core import low_rank

    x, y = _mtls(jax.random.PRNGKey(11))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    texts = {}
    for solver in ("rank1", "block:2"):
        carry = frank_wolfe.init_carry(
            task.init_state(x, y), low_rank.init(8, 24, 18), jax.random.PRNGKey(1),
            (), probe=frank_wolfe.init_probe(frank_wolfe.parse_solver(solver), 18))
        epoch = frank_wolfe.make_epoch_step(task, 1.0, 2, step_size="linesearch",
                                            reducer=comm.DenseReducer(),
                                            solver=solver)
        texts[solver] = jax.jit(epoch).lower(carry).compile().as_text()
    for op in ("dot_general", "cholesky", "triangular_solve"):
        assert re.search(rf'op_name="[^"]*/dfw\.power/dfw\.orth/[^"]*{op}',
                         texts["block:2"]), op
    assert "dfw.orth" not in texts["rank1"]


# ---------------------------------------------------------------------------
# Program reuse across runs (engine.cached_program)
# ---------------------------------------------------------------------------

# One change of each value a job's programs close over; None changes nothing.
_REUSE_CHANGES = {
    "same": None,
    "mu": {"mu": 2.0},
    "gap_tol": {"gap_tol": 1e-3},
    "step_size": {"step_size": "default"},
    "schedule_k": {"schedule": "const:3"},
    "solver": {"solver": "block:2"},
    "comm": {"comm": "int8"},
    "topology": {"topology": "ring"},
    "workers": {"workers": 4},
    "kernelize": {"kernelize": False},
    "use_pallas": {"use_pallas": False},
    "task_d_m": {"d": 20, "m": 16},
}


def _reuse_job(tel, key, d=24, m=18, workers=1, **cfg_kw):
    """One ``launch/dfw.fit`` job of a small MTLS problem; returns its result
    and the change of ``tel``'s counters and the names of its spans."""
    from repro.launch import dfw

    x, y = _mtls(jax.random.PRNGKey(12), n=400, d=d, m=m)
    task = tasks.MultiTaskLeastSquares(d=d, m=m)
    cfg = dfw.DFWConfig(**{"mu": 1.0, "num_epochs": 6, "schedule": "const:2",
                           "step_size": "linesearch", "telemetry": tel,
                           **cfg_kw})
    before = tel.registry.snapshot()["counters"]
    t_us = tel.now_us()
    res = dfw.fit(task, x, y, cfg=cfg, key=key, num_workers=workers)
    after = tel.registry.snapshot()["counters"]
    spans = {ev["name"] for ev in tel.events() if ev["ts"] >= t_us}
    return res, {k: v - before.get(k, 0.0) for k, v in after.items()}, spans


def _check_reuse(change):
    """Two jobs with different keys, the second with ``change`` applied: an
    equal configuration reuses every program and compiles nothing; a changed
    closed-over value builds its scan anew. Either way the second job's
    answer is bit-identical to the same job run on an empty cache."""
    from repro.obs import Telemetry

    tel = Telemetry(capture_hlo=False)
    _reuse_job(tel, jax.random.PRNGKey(1))
    kw = dict(change or {})
    res, counts, spans = _reuse_job(tel, jax.random.PRNGKey(2), **kw)
    if change is None:
        assert res.stats["programs_reused"] >= 1, res.stats
        assert res.stats["programs_reused"] == res.stats["compilations"]
        assert counts["engine.program_cache.hits"] >= 3, counts
        assert counts.get("engine.program_cache.misses", 0) == 0, counts
        assert counts["jit.compiles"] == 0, counts
        assert "engine.compile" not in spans
    else:
        assert counts["engine.program_cache.misses"] >= 1, counts
        assert res.stats["programs_reused"] < res.stats["compilations"]
        assert "engine.compile" in spans and counts["jit.compiles"] >= 1
    engine.clear_programs()
    fresh, _, fresh_spans = _reuse_job(Telemetry(capture_hlo=False),
                                       jax.random.PRNGKey(2), **kw)
    assert "engine.compile" in fresh_spans
    assert fresh.stats["programs_reused"] == 0
    assert res.history.keys() == fresh.history.keys()
    for name in res.history:
        np.testing.assert_array_equal(np.asarray(res.history[name]),
                                      np.asarray(fresh.history[name]),
                                      err_msg=name)
    assert res.final_loss == fresh.final_loss
    for a, b in zip(jax.tree.leaves(res.iterate), jax.tree.leaves(fresh.iterate)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", list(_REUSE_CHANGES))
def test_a_second_job_reuses_programs_unless_a_closed_over_value_changed(name):
    change = _REUSE_CHANGES[name]
    if name != "workers":
        _check_reuse(change)
        return
    # more than one worker needs more than the main process's one device
    out = _run(f"""
        import sys
        sys.path.insert(0, {str(Path(__file__).resolve().parent)!r})
        from test_engine import _check_reuse, _reuse_job
        import test_engine
        test_engine._reuse_job = lambda tel, key, **kw: _reuse_job(
            tel, key, **{{"workers": 2, **kw}})
        _check_reuse({change!r})
        print("reuse across worker counts OK")
    """)
    assert "OK" in out


def test_the_program_cache_holds_no_job_data():
    """Programs are cached, arrays are not: once a job's result and data
    are dropped, none of its arrays is alive, though its programs are."""
    import gc

    from repro.launch import dfw

    n = 408  # a row count no other live array has
    x, y = _mtls(jax.random.PRNGKey(13), n=n)
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    cfg = dfw.DFWConfig(mu=1.0, num_epochs=6, step_size="linesearch")
    res = dfw.fit(task, x, y, cfg=cfg, key=jax.random.PRNGKey(1),
                  num_workers=1)
    assert any(a.shape[:1] == (n,) for a in jax.tree.leaves(res.state))
    del res, x, y
    gc.collect()
    assert len(engine._programs) == 3  # scan, state init, final loss
    assert not [a.shape for a in jax.live_arrays() if a.shape[:1] == (n,)]


def test_the_program_cache_keeps_the_32_most_recently_used():
    built = []

    def lookup(key):
        return engine.cached_program(key, lambda: built.append(key) or key)

    for i in range(40):
        assert lookup(("k", i)) == (("k", i), False)
    assert len(engine._programs) == 32
    assert lookup(("k", 8)) == (("k", 8), True)  # kept: now the newest
    assert lookup(("k", 7)) == (("k", 7), False)  # dropped: built again
    assert lookup(("k", 9)) == (("k", 9), False)  # oldest, dropped for k 7
    assert lookup(("k", 8)) == (("k", 8), True)
    assert len(engine._programs) == 32 and len(built) == 42


def test_an_unhashable_key_is_built_every_time_and_counted_as_a_miss():
    from repro.obs import Telemetry

    tel = Telemetry()
    for _ in range(2):
        program, hit = engine.cached_program(["unhashable"], object, tel)
        assert not hit and type(program) is object
    assert not engine._programs
    assert tel.registry.snapshot()["counters"]["engine.program_cache.misses"] == 2


def test_a_method_patched_on_the_task_class_builds_the_programs_again(monkeypatch):
    """The key holds the code of the task's class: a job after a patch runs
    the patched method, as a job after a fresh start would."""
    from repro.obs import Telemetry

    tel = Telemetry(capture_hlo=False)
    before, _, _ = _reuse_job(tel, jax.random.PRNGKey(1))
    monkeypatch.setattr(tasks.MultiTaskLeastSquares, "update",
                        lambda self, s, *a: s)
    after, counts, spans = _reuse_job(tel, jax.random.PRNGKey(1))
    assert counts["engine.program_cache.misses"] == 3, counts
    assert "engine.compile" in spans
    # the state never moves: every epoch sees the loss at W = 0
    assert len(set(after.history["loss"])) == 1
    assert len(set(before.history["loss"])) > 1
