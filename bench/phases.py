#!/usr/bin/env python3
"""Phase readings of a training cell's traced run on the chip: the
program's set-up, compile and dispatch spans, its JAX compile counters, and
the device time under the solver's named scopes. The benchmark's own runs
never run this: it adds by patching what ``drive_fit.py`` (``span_s``,
``counters``) and ``trace_reduce.py`` (``scopes``, ``scope_s``) do not
record yet, and is deleted once they record it and ``bench/run.py
--trace 1`` reads the five metrics below itself.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s>

It makes one traced run of the cell, as ``bench/run.py --trace 1`` does,
and adds to the run's context what the readers ``fit_setup_ms``,
``fit_compile_ms``, ``fit_compiles``, ``power_device_ms`` and
``update_device_ms`` read: each job's seconds by span name (``span_s``) and
the change of the program's counters over it (``counters``), and the
device time under each named scope (``scope_s``, ``bench/scopes.py``). It
prints one JSON line, appended to ``chiprun_out/phases.jsonl``, with:

- ``metrics``: every per-layer metric of the cell and those five;
- ``jobs``: each job's wall time (``fit_s``, the ``launch/dfw.fit`` call),
  its spans by name, and the share of ``fit_s`` that ``engine.setup``,
  ``engine.dispatch``, ``engine.fetch`` and ``engine.final_loss`` cover;
- ``scan``: the device time inside the segments' scans (the ``while``
  operations under no scope and inside no other ``while``) and the part of
  it under the ``dfw.*`` scopes;
- ``breakdown``, ``e2e`` and ``checks`` of the run; the stat that held the
  name stack, the stats of the first device operations, and the names of
  the device planes' lines and of their event metadata's stats;

and keeps the trace from the first scoped operation on (``--keep-ops``
operations, with the host annotations and the scope rows over them) as
``chiprun_out/trace_<cell>_scopes.json``, the recorded trace of the tests.
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

NEW = ("fit_setup_ms", "fit_compile_ms", "fit_compiles", "power_device_ms",
       "update_device_ms")
TOP = ("engine.setup", "engine.dispatch", "engine.fetch", "engine.final_loss")


def job_recorder(jobs: list):
    """A ``fit_fn`` for ``drive_fit.run`` that records, for each call in
    order, its wall time (``fit_s``: the ``launch/dfw.fit`` call alone; a
    job record's ``dur_s`` also holds the profiler's start and stop in the
    traced job), the seconds of its spans by name and its counters'
    change."""
    from repro.launch import dfw

    def fit_fn(task, x, y, *, cfg, key, mesh):
        tel = cfg.telemetry
        if tel is None:
            return dfw.fit(task, x, y, cfg=cfg, key=key, mesh=mesh)
        t_us = tel.now_us()
        before = dict(tel.registry.snapshot()["counters"])
        t0 = time.perf_counter()
        try:
            return dfw.fit(task, x, y, cfg=cfg, key=key, mesh=mesh)
        finally:
            fit_s = time.perf_counter() - t0
            span_s: dict = {}
            for e in tel.events():
                if e.get("ph") == "X" and e["ts"] >= t_us:
                    span_s[e["name"]] = span_s.get(e["name"], 0.0) + e["dur"] / 1e6
            after = tel.registry.snapshot()["counters"]
            jobs.append({"fit_s": fit_s, "span_s": span_s,
                         "counters": {k: v - before.get(k, 0.0)
                                      for k, v in after.items()}})

    return fit_fn


def scan_coverage(trace: dict) -> dict:
    """Device time of the segments' scans on the first device, and the
    part of it that the ``dfw.*`` scopes cover."""
    from bench import trace_reduce

    if not trace or not trace["devices"]:
        return {}
    t0, t1 = trace_reduce._window(trace)
    dev = sorted(trace["devices"])[0]
    scoped = [[s, e] for d, _, s, e in trace.get("scopes") or [] if d == dev]
    scoped_iv = trace_reduce.merge(scoped)

    def under_scope(s, e):
        return any(a <= s and e <= b for a, b in scoped_iv)

    whiles = [[o[1], o[2]] for o in trace["devices"][dev]
              if "while" in o[0].lower() and o[2] > t0 and o[1] < t1
              and not under_scope(o[1], o[2])]
    outer = [w for w in whiles
             if not any(v is not w and v[0] <= w[0] and w[1] <= v[1]
                        and (v[0], v[1]) != (w[0], w[1]) for v in whiles)]
    scan = trace_reduce.merge([[max(s, t0), min(e, t1)] for s, e in outer])
    scan_s = sum(e - s for s, e in scan)
    inside = trace_reduce.merge(
        [[max(s, a), min(e, b)] for s, e in scoped_iv for a, b in scan
         if e > a and s < b])
    covered = sum(e - s for s, e in inside)
    return {"scan_s": scan_s / 1e9, "scoped_s": covered / 1e9,
            "share": covered / scan_s if scan_s else None, "scans": len(outer)}


def keep_trace(trace: dict, keep_ops: int) -> dict:
    """The trace from its first scoped operation on: ``keep_ops`` device
    operations, those that enclose them, and the host annotations and
    scope rows over them."""
    rows = trace.get("scopes") or []
    if not rows:
        return {}
    start = min(r[2] for r in rows)
    devices = {}
    for dev, ops in trace["devices"].items():
        after = [o for o in ops if o[1] >= start][:keep_ops]
        if not after:
            continue
        around = [o for o in ops if o[1] < start and o[2] > start]
        devices[dev] = sorted(around + after, key=lambda o: o[1])
    lo = min(o[1] for ops in devices.values() for o in ops)
    hi = max(o[2] for ops in devices.values() for o in ops)
    return {"devices": devices,
            "host": [h for h in trace["host"] if h[1] <= hi and h[2] >= lo],
            "scopes": [r for r in rows if r[0] in devices and r[2] >= lo and r[3] <= hi],
            "scope_stat": trace.get("scope_stat"),
            "stat_samples": trace.get("stat_samples")}


def run(cell, seed: int, seconds: float, keep_ops: int) -> dict:
    from bench import harness, scopes, trace_reduce

    jobs: list = []
    kept: dict = {}
    load, reduce = trace_reduce.load_xspace, trace_reduce.reduce

    def load_with_scopes(path):
        trace = load(path)
        trace.update(scopes.load(path))
        return trace

    def reduce_with_scopes(trace):
        kept["trace"] = trace
        red = reduce(trace)
        if red is not None:
            red["scope_s"] = scopes.scope_seconds(trace)
        return red

    trace_reduce.load_xspace, trace_reduce.reduce = load_with_scopes, reduce_with_scopes
    try:
        out = harness.driver(cell.traffic["kind"]).run(
            cell, seed, seconds, True, T_START, fit_fn=job_recorder(jobs))
    finally:
        trace_reduce.load_xspace, trace_reduce.reduce = load, reduce
    ctx = out["ctx"]
    for rec in ctx["fits"]:
        rec.update(jobs[rec["job"] + 1])  # jobs[0] is the warm-up job
    metrics = harness.read_per_layer(cell, ctx)
    for name in NEW:
        value = harness.reader(name).read(ctx)
        if value is not None:
            metrics[name] = value
    trace = kept.get("trace") or {}
    red = ctx.get("trace") or {}
    per_job = [{"job": r["job"], "dur_s": r["dur_s"], "fit_s": r["fit_s"],
                "epochs": r["epochs"], "traced": bool(r.get("traced")),
                "span_s": r["span_s"],
                "top_share": sum(r["span_s"].get(n, 0.0) for n in TOP) / r["fit_s"],
                "counters": {k: v for k, v in r["counters"].items()
                             if k.startswith("jit.")}}
               for r in ctx["fits"]]
    if trace:
        out_dir = ROOT / "chiprun_out"
        out_dir.mkdir(exist_ok=True)
        (out_dir / f"trace_{cell.name}_scopes.json").write_text(
            json.dumps(keep_trace(trace, keep_ops)))
    return {
        "cell": cell.name, "seed": seed, "what": "phases",
        "attempted": out["attempted"], "failed": out["failed"],
        "e2e": out["e2e"], "checks": out["checks"], "metrics": metrics,
        "jobs": per_job, "scan": scan_coverage(trace),
        "scope_s": red.get("scope_s"), "window_s": red.get("window_s"),
        "busy_s": red.get("busy_s"), "breakdown": red.get("breakdown"),
        "scope_stat": trace.get("scope_stat"),
        "stat_samples": trace.get("stat_samples"),
        "lines": trace.get("lines"), "metadata_stats": trace.get("metadata_stats"),
        "memory_peak": out["memory_peak"],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--keep-ops", type=int, default=300)
    args = ap.parse_args()
    from bench import harness

    cell = harness.resolve(args.workload)
    jax = bench_run.init_jax()
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("phases: needs the cell's TPU chips", file=sys.stderr)
        return 2
    rec = run(cell, args.seed, args.seconds, args.keep_ops)
    line = json.dumps(rec)
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    with open(out / "phases.jsonl", "a") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
