"""The control -- the plain reference put in the program's place and
computed in bfloat16, the precision below the float32 the configurations
state -- must come out not correct; the same reference in float32 must come
out correct. At a tiny size on the CPU; bench/control.py reads the same at
the cells' own sizes on the chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, drive_serve, harness
from conftest import small_cell

TRAINING = ["head_in1k_fit_1chip"]


def _readings(cell_name, dtype, seed):
    cell = small_cell(cell_name)
    problem = harness.problem(cell.config["problem"])
    key = harness.seed_key(seed)
    x, y = problem.make_shard(jax.random.fold_in(key, 1), 0,
                              cell.config["rows_per_chip"], cell.config)
    got, epochs = control.reference_in_place(cell, problem, key, x, y, dtype)
    return got, cell.traffic["limits"], min(epochs)


@pytest.mark.parametrize("cell", TRAINING)
@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_training_control_fails_and_reference_passes(cell, seed):
    got, limits, _ = _readings(cell, jnp.bfloat16, seed)
    assert any(got[n] > limits[n] for n in limits), got
    got, limits, epochs = _readings(cell, jnp.float32, seed)
    assert all(got[n] <= limits[n] for n in limits), got
    assert epochs >= 1


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_serving_control_fails_and_reference_passes(seed):
    cell = small_cell("head_in1k_serve_r128")
    cfg, tr = cell.config, cell.traffic
    u, s, v, pool = drive_serve._draw(harness.seed_key(seed), rank=tr["live_rank"],
                                      d=cfg["d"], m=cfg["m"], pool_rows=tr["pool_rows"])
    x = np.asarray(pool[:40])
    want = np.asarray(drive_serve.reference_scores(u, s, v, 1.0, x))
    low = np.asarray(drive_serve.reference_scores(u, s, v, 1.0, x, jnp.bfloat16))
    assert drive_serve.score_error(low, want) > tr["limits"]["score_err"]
    again = np.asarray(drive_serve.reference_scores(u, s, v, 1.0, x))
    assert drive_serve.score_error(again, want) <= tr["limits"]["score_err"]


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 2**40 + 7])
def test_reference_follows_the_programs_path(seed):
    """On the CPU, where the program's products are float32 too, the
    reference's solve of a job from the job's key lands on the program's
    iterate up to rounding: ``w_rel`` then reads rounding alone."""
    from conftest import run_small

    line = run_small("head_in1k_fit_1chip", seed=seed)
    assert line["checks"]["w_rel"]["value"] < 1e-5, line["checks"]
