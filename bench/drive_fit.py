"""Training traffic: back-to-back DFW-Trace jobs, each run to a duality gap.

A job is one ``repro.launch.dfw.fit`` call on data already on the devices,
run until the program's gap certificate reaches ``gap_rel`` times the exact
gap at W = 0 (``DFWConfig.gap_tol``), with a cap of ``max_epochs``. Every
job of a run fits the same data, drawn from the seed; job i starts its
power iterations from its own key, ``job_key(i)``, the same in every run,
as users' jobs differ. With the planted truth fixed too
(``bench/problems``), every seed gets the same sequence of jobs on other
rows, so a run's work hangs on the seed as little as the rows allow.

Set-up draws the data on the devices, computes the exact initial gap with
the reference, and runs one job to compile and warm up. The window then
runs jobs back to back and closes at the end of the first job to finish at
or after ``--seconds``; ``fit_to_gap_s`` is the window's time over the jobs
it completed.

After the window, the reference judges a sample of the jobs' answers
(``reference.judge``): the last job and up to two more drawn from the seed,
each against the reference's own solve of that job for as many epochs.
Distinct jobs round differently, so the worst of the sample does not hang
on one lucky rounding.

The traffic file's keys: ``schedule``, ``step_size``, ``solver``, ``comm``,
``topology`` (as ``DFWConfig`` takes them), ``gap_rel``, ``max_epochs``,
and ``limits`` (the most each compared number may read).
"""
from __future__ import annotations

import math
import time
from typing import Callable, List, Tuple

import numpy as np

from bench import harness, reference, trace_reduce


def fit_window(run_fit: Callable[[int], dict], seconds: float,
               clock: Callable[[], float] = time.perf_counter
               ) -> Tuple[List[dict], float, float]:
    """Run jobs back to back until one finishes ``seconds`` or more after
    the window opened. Returns the job records (each with ``t0`` and
    ``dur_s``) and the window's open and close times."""
    records: List[dict] = []
    t_open = clock()
    while True:
        t0 = clock()
        rec = run_fit(len(records))
        t1 = clock()
        rec.update(t0=t0, dur_s=t1 - t0)
        records.append(rec)
        if t1 - t_open >= seconds:
            return records, t_open, t1


def fit_to_gap_s(records: List[dict], t_open: float, t_close: float) -> float:
    return (t_close - t_open) / len(records)


def place_rows(problem, key, rows: int, chips: int, cfg: dict, mesh):
    """Draw each chip's shard of the data on its own device and assemble the
    row-sharded global arrays."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    devices = list(mesh.devices.flat)
    shards = [problem.make_shard(jax.device_put(key, dev), i, rows, cfg)
              for i, dev in enumerate(devices)]
    out = []
    for leaf in range(2):
        parts = [s[leaf] for s in shards]
        shape = (rows * chips,) + parts[0].shape[1:]
        sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
        out.append(jax.make_array_from_single_device_arrays(shape, sharding, parts))
    return tuple(out)


JOB_SEED = 2


def job_key(i: int):
    """The key of job ``i`` of the window (the warm-up job is -1)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(JOB_SEED), i + 1)


def power_iters(schedule: str) -> int:
    if not schedule.startswith("const:"):
        raise ValueError(f"the work counts need a const:K schedule, not {schedule!r}")
    return int(schedule.split(":")[1])


def block_width(solver: str) -> int:
    return int(solver.split(":")[1]) if solver.startswith("block:") else 1


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, fit_fn=None) -> dict:
    import jax

    from repro.launch import dfw

    fit_fn = fit_fn or dfw.fit
    cfg, tr = cell.config, cell.traffic
    problem = harness.problem(cfg["problem"])
    key = harness.seed_key(seed)
    mesh = dfw.data_mesh(cell.chips)
    x, y = place_rows(problem, jax.random.fold_in(key, 1), cfg["rows_per_chip"],
                      cell.chips, cfg, mesh)
    jax.block_until_ready((x, y))
    mu, d, m = cfg["mu"], cfg["d"], cfg["m"]
    gap_target = tr["gap_rel"] * reference.initial_gap(problem, x, y, mu, d, m)
    task = problem.task(cfg)
    tel = harness.annotating_telemetry() if trace else None
    dcfg = dfw.DFWConfig(
        mu=mu, num_epochs=tr["max_epochs"], schedule=tr["schedule"],
        step_size=tr["step_size"], solver=tr["solver"], comm=tr["comm"],
        topology=tr["topology"], gap_tol=gap_target, telemetry=tel)
    traced: dict = {}

    def one_fit(i: int) -> dict:
        t_us = tel.now_us() if tel else 0.0
        with harness.annotate("bench.fit", trace):
            try:
                res = fit_fn(task, x, y, cfg=dcfg, key=job_key(i), mesh=mesh)
            except Exception as e:  # a job that raises is a failed job
                return {"ok": False, "error": repr(e)}
        rec = {"ok": math.isfinite(res.final_loss), "loss": res.final_loss,
               "epochs": int(res.epochs_run), "iterate": res.iterate, "job": i}
        if tel:
            rec["spans_s"] = harness.span_seconds(
                tel, ("engine.dispatch", "engine.fetch"), since_us=t_us)
        return rec

    def window_fit(i: int) -> dict:
        if trace and i == 1:  # trace one whole job in the steady state
            with trace_reduce.capture(traced), harness.annotate(trace_reduce.WINDOW, True):
                return dict(one_fit(i), traced=True)
        return one_fit(i)

    warm = one_fit(-1)
    if not warm["ok"]:
        raise RuntimeError(f"the warm-up job failed: {warm}")
    del warm
    records, t_open, t_close = fit_window(window_fit, seconds)
    memory_peak = harness.memory_peak_bytes(cell.chips)

    done = [r for r in records if r["ok"]]
    checks = judge_fits(problem, x, y, cfg, tr, gap_target, done, seed)
    e2e = {"setup_s": t_open - t_start}
    if done:
        e2e["fit_to_gap_s"] = fit_to_gap_s(records, t_open, t_close)
    shape = (cfg["rows_per_chip"], cfg, power_iters(tr["schedule"]),
             block_width(tr["solver"]))
    ctx = {
        "cell": cell, "fits": done,
        "trace": trace_reduce.reduce(traced.get("trace")),
        "epoch_work": problem.epoch_work(*shape),
        "power_work": problem.power_work(*shape),
        "device_kind": jax.devices()[0].device_kind,
    }
    return {"attempted": len(records), "failed": len(records) - len(done),
            "e2e": e2e, "checks": checks, "ctx": ctx, "memory_peak": memory_peak}


def judged(n_done: int, seed: int) -> List[int]:
    """The jobs whose answers are judged: the last and up to two more drawn
    from the seed."""
    rng = np.random.default_rng(seed)
    pick = {n_done - 1}
    pick.update(rng.choice(n_done, size=min(2, n_done), replace=False).tolist())
    return sorted(pick)


def worst_of(readings: List[dict], limits: dict) -> List[dict]:
    """Each compared number at its worst over the judged answers."""
    worst = {n: 0.0 for n in limits}
    for got in readings:
        for n in worst:
            v = got[n]
            worst[n] = max(worst[n], v if math.isfinite(v) else float("inf"))
    return [{"name": n, "value": worst[n], "limit": limits[n]} for n in limits]


def reference_path(problem, x, y, cfg, tr, job: int, epochs: int, dtype=None):
    """The reference's iterate after ``epochs`` epochs of job ``job``, the
    gap stop off: what the program's job should have returned."""
    import jax.numpy as jnp

    w, _, _ = reference.solve(
        problem, x, y, d=cfg["d"], m=cfg["m"], mu=cfg["mu"],
        k_iters=power_iters(tr["schedule"]), width=block_width(tr["solver"]),
        step=tr["step_size"], gap_tol=-math.inf, max_epochs=epochs,
        key=job_key(job), dtype=dtype or jnp.float32)
    return w


def judge_fits(problem, x, y, cfg, tr, gap_target, done, seed) -> List[dict]:
    """The compared numbers, worst over the judged jobs."""
    limits = tr["limits"]
    if not done:
        return [{"name": n, "value": float("inf"), "limit": lim}
                for n, lim in limits.items()]
    readings = []
    for i in judged(len(done), seed):
        rec = done[i]
        it = rec["iterate"]
        w = reference.dense_iterate(it.u, it.s, it.v, it.alpha)
        w_ref = reference_path(problem, x, y, cfg, tr, rec["job"], rec["epochs"])
        readings.append(reference.judge(problem, x, y, cfg["mu"], gap_target, w,
                                        rec["loss"], w_ref))
    return worst_of(readings, limits)
