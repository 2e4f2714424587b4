"""Shared set-up of the benchmark's own tests: the checkout's root on the
import path, and cells cut to a size the CPU runs in seconds."""
import os
import sys
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


# Cells whose files are in place but which are not in BENCHMARK.json yet
# (see PERF.md, Open questions): configuration, traffic mix, chips.
FOUR_CHIP = "head_in1k_fit_4chip"
SERVE = "head_in1k_serve_r128"
UNLISTED = {FOUR_CHIP: ("imagenet_head", "fit_rank1_const2", 4),
            SERVE: ("imagenet_head", "serve_open_r128", 1)}


def small_cell(name):
    """The named cell at a tiny size: same code path, same traffic kind."""
    from bench import harness

    if name in UNLISTED:
        config, traffic, chips = UNLISTED[name]
        cell = harness.make_cell(
            name, os.path.join(ROOT, "bench", "configs", f"{config}.json"),
            traffic, chips, harness.load_benchmark())
    else:
        cell = harness.resolve(name)
    cell.config.update(d=64, m=48)
    cell.config["rows_per_chip"] = 512
    if cell.traffic["kind"] == "serve":
        cell.traffic.update(live_rank=16, pool_rows=512, rate_per_s=300,
                            check_sample=32, trace_requests=50)
    return cell


def run_small(name, seed=2**31 + 11, seconds=1.0, **driver_kw):
    """Drive a tiny copy of the cell through the harness (the look for a
    chip skipped) and return the parsed result line."""
    import json

    import bench.run as bench_run

    line, _ = bench_run.run_cell(small_cell(name), seed, seconds, False,
                                 time.perf_counter(), **driver_kw)
    return json.loads(line)


@pytest.fixture(scope="session")
def root():
    return ROOT
