# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV; ``--json PATH`` additionally dumps every row as a structured record
# (suite, parsed derived metrics, jax/device metadata) for the perf
# trajectory and the CI regression gate (benchmarks/check_regression.py).
from __future__ import annotations

import argparse
import importlib
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="run a single benchmark module")
    ap.add_argument("--fast", action="store_true", help="smaller sizes")
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="write structured records (BENCH_<name>.json) besides the CSV",
    )
    args = ap.parse_args()

    # Suites import lazily, so `--only X` imports (and initializes JAX for)
    # X alone: the parent touches no device before a suite that starts
    # fake-device children.
    def suite(module: str, fn: str = "run", **kw):
        return lambda: getattr(
            importlib.import_module(f"{__package__}.{module}"), fn
        )(**kw)

    fast = args.fast
    suites = {
        "table1_comm_cost": suite("comm_cost"),
        "table1_comm_sweep": suite("comm_cost", "run_sweep", fast=fast),
        # gossip_consensus keeps the gated hier.inter_bytes record at the
        # same sizes in --fast: it is an HLO byte ratio of one compiled
        # exchange, immune to runner speed; only the fit epochs shrink.
        "gossip_consensus": suite("gossip_consensus", fast=fast),
        "fig1_mtls": suite("mtls_convergence",
                           **(dict(epochs=15, n=8000, d=128, m=128) if fast else {})),
        "fig2_logistic": suite("logistic_convergence",
                               **(dict(epochs=12, n=4000, d=96, m=48) if fast else {})),
        "fig3_imagenet_head": suite("imagenet_head",
                                    **(dict(epochs=15, m=50, tokens=2048) if fast else {})),
        "fig4_scaling": suite("scaling"),
        "fig4_dfw_scaling": suite("dfw_scaling",
                                  **(dict(n=2048, d=64, m=32, epochs=5) if fast else {})),
        "fig5_matrix_completion": suite(
            "matrix_completion", **(dict(d=128, m=96, obs=0.3, epochs=8) if fast else {})),
        "engine_overhead": suite("engine_bench",
                                 **(dict(epochs=96, block=24) if fast else {})),
        # serving_latency keeps Table-1 sizes even in --fast: the gated
        # record IS the rank=d/8 point at d=m=1024; only repetitions shrink.
        "serving_latency": suite("serving_latency",
                                 **(dict(ranks=(16, 128), dispatches=15) if fast else {})),
        # block_fw_convergence keeps Table-1 sizes even in --fast: the
        # gated epochs_to_gap.speedup records ARE the d=m=1024 cells (the
        # metric is an epoch-count ratio, immune to runner speed).
        "block_fw_convergence": suite("block_fw_convergence"),
        "thm2_power_accuracy": suite("power_accuracy"),
        "kernels": suite("kernel_bench"),
        "roofline": suite("roofline"),
    }
    if args.only:
        suites = {args.only: suites[args.only]}

    from . import common

    print("name,us_per_call,derived")
    failures = []
    for name, fn in suites.items():
        print(f"# === {name} ===", flush=True)
        common.begin_suite(name)
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
            # Through emit, not print: a failed suite must show up in the
            # JSON dump too, or the regression gate would read its absence
            # as "nothing to check" instead of "broken".
            common.emit(name, 0.0, f"FAILED({type(e).__name__}:{e})")
    common.begin_suite(None)
    if args.json:
        common.write_json(args.json)
    if failures:
        sys.exit(f"benchmark suites failed: {failures}")


if __name__ == "__main__":
    main()
