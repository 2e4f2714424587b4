"""Each fault a cell can have, planted under an otherwise complete run of
the cell at a tiny size, turns ``correct`` false; the unbroken run at the
same size is correct."""
import json
import os
import subprocess
import sys

import pytest

from bench import faults
from conftest import ROOT, run_small, small_cell

ONE_CHIP = ["head_in1k_fit_1chip", "head_in1k_serve_r128"]


def _faults(cell):
    c = small_cell(cell)
    return [(cell, f) for f in faults.applicable(c.traffic["kind"], c.chips)]


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_sound_run_is_correct(cell):
    line = run_small(cell)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1


@pytest.mark.parametrize("cell,fault", [p for c in ONE_CHIP for p in _faults(c)])
def test_planted_fault_is_not_correct(cell, fault):
    ctx, kw = faults.plant(fault, small_cell(cell).traffic["kind"])
    with ctx:
        line = run_small(cell, **kw)
    assert not line["correct"], line["checks"]


_FOUR = """
import contextlib, json, os, sys
sys.path[:0] = [{root!r}, os.path.join({root!r}, "src"), os.path.join({root!r}, "tests", "bench")]
from bench import faults
from conftest import FOUR_CHIP, run_small
out = {{}}
for fault in sys.argv[1:]:
    ctx, kw = faults.plant(fault, "fit") if fault != "none" else (contextlib.nullcontext(), {{}})
    with ctx:
        out[fault] = run_small(FOUR_CHIP, **kw)
print(json.dumps(out))
"""
FOUR_FAULTS = ("none",) + faults.applicable("fit", 4)


def _four(*names):
    """The four-chip head on four CPU devices in a child process (the
    devices must be set before JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run([sys.executable, "-c", _FOUR.format(root=ROOT), *names],
                          env=env, capture_output=True, text=True, timeout=600)


@pytest.fixture(scope="module")
def four_chip_lines():
    """Every case, the exchange left out among them, in one child process
    that has to end cleanly."""
    p = _four(*FOUR_FAULTS)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", FOUR_FAULTS)
def test_four_chip_cell_on_four_cpu_devices(fault, four_chip_lines):
    line = four_chip_lines[fault]
    assert line["correct"] is (fault == "none"), line["checks"]
