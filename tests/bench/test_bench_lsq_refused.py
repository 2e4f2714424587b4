"""The least-squares cell (``mtls_fig1_fit_block32``) at a cut size on the
CPU: the plain reference in bfloat16 put in the program's place, and each
fault of ``bench/faults.py`` planted under the timed path, come out not
correct; the same reference in float32 comes out correct."""
import jax
import jax.numpy as jnp
import pytest

from bench import control, faults, harness
from lsq_cut import lsq_cell, run_lsq


def _readings(dtype, seed):
    cell = lsq_cell()
    problem = harness.problem(cell.config["problem"])
    key = harness.seed_key(seed)
    x, y = problem.make_shard(jax.random.fold_in(key, 1), 0,
                              cell.config["rows_per_chip"], cell.config)
    got, epochs = control.reference_in_place(cell, problem, key, x, y, dtype)
    return got, cell.traffic["limits"], min(epochs)


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_bf16_control_fails_and_f32_reference_passes(seed):
    got, limits, _ = _readings(jnp.bfloat16, seed)
    assert any(got[n] > limits[n] for n in limits), got
    got, limits, epochs = _readings(jnp.float32, seed)
    assert all(got[n] <= limits[n] for n in limits), got
    assert epochs >= 1


@pytest.mark.parametrize("fault", faults.applicable("fit", 1))
def test_planted_fault_is_not_correct(fault):
    ctx, kw = faults.plant(fault, "fit")
    with ctx:
        line = run_lsq(2**31 + 11, **kw)
    assert not line["correct"], line["checks"]
