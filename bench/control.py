#!/usr/bin/env python3
"""Readings that set the limits of ``correct``, and the serving sweep, on
the chip at a cell's own size. The benchmark's own runs never run this.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --what control
    python3 bench/control.py --workload <cell> --seeds 1,2,3 --what faults --seconds 5
    python3 bench/control.py --workload <cell> --seeds 1 --what trajectory
    python3 bench/control.py --workload <cell> --seeds 1 --what sweep --rates 500,1000 --seconds 5

- ``control``: the plain reference put in the program's place in bfloat16
  (the precision below the configuration's float32), judged as a run's
  answers are: the worst over three jobs with the window's first job keys
  (training), or over the sampled requests (serving); beside it the same
  reference in float32, with the epochs it took.
- ``faults``: a short run of the cell with each fault of ``bench/faults.py``
  that the cell can have planted under the timed path.
- ``trajectory``: the float32 reference's estimated gap, relative to the
  exact initial gap, at every epoch (training), to choose ``gap_rel``.
- ``sweep``: the serving cell's open loop at each of ``--rates`` for
  ``--seconds``: latency quantiles, how late dispatch ran, and whether the
  backlog grew.
- ``record``: one traced run of the cell, and the start of its trace kept
  as ``chiprun_out/trace_<cell>.json`` (the recorded trace of the tests).

Every reading is printed as one JSON line and appended to
``chiprun_out/control.jsonl``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import bench.run as bench_run  # noqa: E402


def emit(rec: dict) -> None:
    line = json.dumps(rec)
    print(line, flush=True)
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    with open(out / "control.jsonl", "a") as f:
        f.write(line + "\n")


def training_setup(cell, seed):
    import jax

    from bench import drive_fit, harness, reference
    from repro.launch import dfw

    cfg, tr = cell.config, cell.traffic
    problem = harness.problem(cfg["problem"])
    key = harness.seed_key(seed)
    mesh = dfw.data_mesh(cell.chips)
    x, y = drive_fit.place_rows(problem, jax.random.fold_in(key, 1),
                                cfg["rows_per_chip"], cell.chips, cfg, mesh)
    gap0 = reference.initial_gap(problem, x, y, cfg["mu"], cfg["d"], cfg["m"])
    return problem, key, x, y, gap0


def reference_run(cell, problem, key, x, y, gap_tol, dtype, job=0, gaps=None):
    """The reference solve in ``dtype`` in place of the window's job ``job``."""
    from bench import drive_fit, reference

    cfg, tr = cell.config, cell.traffic
    return reference.solve(
        problem, x, y, d=cfg["d"], m=cfg["m"], mu=cfg["mu"],
        k_iters=drive_fit.power_iters(tr["schedule"]),
        width=drive_fit.block_width(tr["solver"]), step=tr["step_size"],
        gap_tol=gap_tol, max_epochs=tr["max_epochs"],
        key=drive_fit.job_key(job), dtype=dtype, gaps=gaps)


def reference_in_place(cell, problem, key, x, y, dtype, jobs=3):
    """The compared numbers of the reference in ``dtype`` put in the
    program's place for the window's first ``jobs`` jobs, worst over them as
    a run judges its answers, and the epochs each took."""
    from bench import drive_fit, reference

    target = cell.traffic["gap_rel"] * reference.initial_gap(
        problem, x, y, cell.config["mu"], cell.config["d"], cell.config["m"])
    readings, epochs = [], []
    for job in range(jobs):
        w, loss, ep = reference_run(cell, problem, key, x, y, target, dtype, job)
        w_ref = drive_fit.reference_path(problem, x, y, cell.config, cell.traffic, job, ep)
        readings.append(reference.judge(problem, x, y, cell.config["mu"], target, w,
                                        loss, w_ref))
        epochs.append(ep)
    checks = drive_fit.worst_of(readings, cell.traffic["limits"])
    return {c["name"]: c["value"] for c in checks}, epochs


def control(cell, seed):
    import jax.numpy as jnp
    import numpy as np

    from bench import drive_serve, harness

    if cell.traffic["kind"] == "serve":
        cfg, tr = cell.config, cell.traffic
        u, s, v, pool = drive_serve._draw(
            harness.seed_key(seed), rank=tr["live_rank"], d=cfg["d"], m=cfg["m"],
            pool_rows=tr["pool_rows"])
        due, sizes, offsets = drive_serve.schedule(
            seed, tr["rate_per_s"], 30.0, tr["rows_mean"], tr["rows_max"], tr["pool_rows"])
        pool = np.asarray(pool)
        worst = 0.0
        for j in drive_serve.check_sample(seed, sizes, tr["check_sample"]):
            xj = pool[offsets[j]:offsets[j] + sizes[j]]
            want = np.asarray(drive_serve.reference_scores(u, s, v, 1.0, xj))
            low = np.asarray(drive_serve.reference_scores(u, s, v, 1.0, xj, jnp.bfloat16))
            worst = max(worst, drive_serve.score_error(low, want))
        emit({"cell": cell.name, "seed": seed, "what": "control_bf16",
              "score_err": worst})
        return
    problem, key, x, y, _ = training_setup(cell, seed)
    for dtype in (jnp.bfloat16, jnp.float32):
        t = time.perf_counter()
        got, epochs = reference_in_place(cell, problem, key, x, y, dtype)
        emit({"cell": cell.name, "seed": seed, "what": f"reference_{jnp.dtype(dtype).name}",
              "epochs": epochs, "seconds": time.perf_counter() - t, **got})


def trajectory(cell, seed):
    import jax.numpy as jnp

    problem, key, x, y, gap0 = training_setup(cell, seed)
    for job in range(3):
        gaps = []
        reference_run(cell, problem, key, x, y, 0.0, jnp.float32, job=job, gaps=gaps)
        emit({"cell": cell.name, "seed": seed, "what": "trajectory", "job": job,
              "rel_gap": [g / gap0 for g in gaps]})


def planted(cell, seed, seconds):
    from bench import faults

    for name in faults.applicable(cell.traffic["kind"], cell.chips):
        if name == "state_unchanged":
            continue  # reads 1 / gap_rel on gap_ratio by construction
        ctx, kw = faults.plant(name, cell.traffic["kind"])
        with ctx:
            line, _ = bench_run.run_cell(cell, seed, seconds, False,
                                         time.perf_counter(), **kw)
        emit({"cell": cell.name, "seed": seed, "what": f"fault_{name}",
              "checks": json.loads(line)["checks"]})


def record(cell, seed, seconds, keep_ops=400):
    """One traced run of the cell; its result line, and the first
    ``keep_ops`` device operations of its trace with the host annotations
    around them, written to ``chiprun_out/trace_<cell>.json`` (the recorded
    trace that the readers' name patterns are tested on)."""
    from bench import trace_reduce

    kept, reduce = {}, trace_reduce.reduce

    def keep(trace):
        kept["trace"] = trace
        return reduce(trace)

    trace_reduce.reduce = keep
    try:
        line, _ = bench_run.run_cell(cell, seed, seconds, True, time.perf_counter())
    finally:
        trace_reduce.reduce = reduce
    emit({"cell": cell.name, "seed": seed, "what": "traced", "line": json.loads(line)})
    trace = kept.get("trace")
    if not trace:
        return
    devices = {k: v[:keep_ops] for k, v in trace["devices"].items()}
    ends = [o[2] for ops in devices.values() for o in ops]
    starts = [o[1] for ops in devices.values() for o in ops]
    host = [h for h in trace["host"]
            if ends and h[1] <= max(ends) and h[2] >= min(starts)]
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"trace_{cell.name}.json").write_text(
        json.dumps({"devices": devices, "host": host}))


def sweep(cell, seed, rates, seconds):
    """The open loop at each rate. The backlog grows where requests due in
    the window's last tenth wait much longer than those of its first."""
    import numpy as np

    from bench import harness

    for rate in rates:
        c = harness.resolve(cell.name)
        c.traffic["rate_per_s"] = rate
        out = harness.driver("serve").run(c, seed, seconds, False, time.perf_counter())
        lat, tenth = out["ctx"]["latency_s"], max(1, len(out["ctx"]["latency_s"]) // 10)
        emit({"cell": cell.name, "seed": seed, "what": "sweep", "rate": rate,
              "attempted": out["attempted"], "failed": out["failed"],
              **out["e2e"],
              "queue_p99_ms": float(np.quantile(out["ctx"]["queue_s"], 0.99) * 1e3),
              "service_p50_ms": float(np.median(out["ctx"]["service_s"]) * 1e3),
              "first_tenth_mean_ms": float(np.mean(lat[:tenth]) * 1e3),
              "last_tenth_mean_ms": float(np.mean(lat[-tenth:]) * 1e3)})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", required=True,
                    choices=("control", "faults", "trajectory", "sweep", "record"))
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", default="")
    args = ap.parse_args()
    from bench import harness

    cell = harness.resolve(args.workload)
    jax = bench_run.init_jax()
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    for seed in [int(s) for s in args.seeds.split(",")]:
        if args.what == "control":
            control(cell, seed)
        elif args.what == "faults":
            planted(cell, seed, args.seconds)
        elif args.what == "trajectory":
            trajectory(cell, seed)
        elif args.what == "record":
            record(cell, seed, args.seconds)
        else:
            sweep(cell, seed, [float(r) for r in args.rates.split(",")], args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
