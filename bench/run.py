#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` (see ``bench/harness.py``). The run sets up
(backend, data and model on the device, one warm-up of every program the
window uses), measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints one JSON line last on
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, with ``--trace 1``, ``breakdown``. ``--trace 0`` reports the
cell's end-to-end metrics with the program's telemetry off; ``--trace 1``
traces a steady part of the window and reports the per-layer metrics.
The numbers compared with the reference are printed beside their limits as
the last lines of standard error and under ``checks`` in the result line.

It exits non-zero with no result line when JAX finds no TPU, or fewer TPU
chips than the cell asks for. JAX's persistent compilation cache is kept
in ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def init_jax():
    """Import JAX with the compile cache on, and put the program's package on
    the import path."""
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    # The cache lives in this checkout whatever the environment names, so
    # that two checkouts on one machine share no compiled program.
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    # Cache every program, however quickly it compiles, so that only the
    # first run of a cell in a checkout compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             **driver_kw) -> tuple:
    """Drive one cell whose devices are already checked. Returns
    ``(result_line, correct)``."""
    import jax

    from bench import harness

    out = harness.driver(cell.traffic["kind"]).run(
        cell, seed, seconds, trace, t_start, **driver_kw)
    checks = out["checks"]
    correct = (out["failed"] == 0 and bool(checks)
               and all(c["value"] <= c["limit"] for c in checks))
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": out["memory_peak"]}
    breakdown = None
    if trace:
        metrics = harness.read_per_layer(cell, out["ctx"])
        reduced = out["ctx"].get("trace")
        if reduced:
            device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            breakdown = reduced["breakdown"]
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {n: {"value": float(out["e2e"][n]), "unit": units[n]}
                   for n in units if n in out["e2e"]}
    harness.report_checks(checks)
    line = harness.result_line(
        correct=correct, attempted=out["attempted"], failed=out["failed"],
        metrics=metrics, device=device, checks=checks, breakdown=breakdown)
    return line, correct


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness

    cell = harness.resolve(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program is not in {ROOT / 'src'}", file=sys.stderr)
        return 2
    jax = init_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, but JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    line, _ = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
