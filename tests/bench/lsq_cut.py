"""The least-squares cell cut to a size the CPU runs in seconds.

``conftest.small_cell``'s widths (d = 64, m = 48) leave the block of 32
probe columns two thirds of the space, where the reference's own block
start (``bench/reference.py``) and the program's move the answer apart by
several percent. At d = m = 500 the block is a sixteenth of the space, as
32 of 1000 is at the source's size, and 8,192 rows keep the rows several
times the width."""
import time

from conftest import small_cell

CELL = "mtls_fig1_fit_block32"


def lsq_cell():
    cell = small_cell(CELL)
    cell.config.update(d=500, m=500, rows_per_chip=8192)
    return cell


def run_lsq(seed, seconds=1.0, **driver_kw):
    """Drive the cut cell through the harness and return the parsed
    result line."""
    import json

    import bench.run as bench_run

    line, _ = bench_run.run_cell(lsq_cell(), seed, seconds, False,
                                 time.perf_counter(), **driver_kw)
    return json.loads(line)
