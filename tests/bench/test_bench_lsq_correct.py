"""The least-squares cell (``mtls_fig1_fit_block32``) at a cut size on the
CPU: the program's block:32 jobs are correct against
``bench/problems/lsq.py``'s plain f32 reference, and every seed poses the
same planted truth on other rows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, work
from lsq_cut import lsq_cell, run_lsq


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 2**40 + 7])
def test_sound_run_is_correct(seed):
    """Correct, and on the reference's own path: the program starts each
    job's block from the job's key as the reference does, so on the CPU,
    where both compute in f32, ``w_rel`` reads rounding alone."""
    line = run_lsq(seed)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["checks"]["w_rel"]["value"] < 1e-4, line["checks"]


def test_every_seed_poses_the_same_truth():
    """Two seeds draw other rows, and both label them with W*(TRUTH_SEED):
    Y = X W* exactly as the reference computes it, with the same W*."""
    cell = lsq_cell()
    cfg = cell.config
    problem = harness.problem(cfg["problem"])
    w_star = np.asarray(problem.truth(d=cfg["d"], m=cfg["m"],
                                      rank=cfg["planted_rank"]), np.float64)
    s = np.linalg.svd(w_star, compute_uv=False)
    assert np.sum(s) == pytest.approx(1.0, rel=1e-5)  # ||W*||_* = 1
    assert np.sum(s > 1e-6) == cfg["planted_rank"]
    xs = []
    for seed in (5, 2**33 + 1):
        key = jax.random.fold_in(harness.seed_key(seed), 1)
        x, y = problem.make_shard(key, 0, 1024, cfg)
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        np.testing.assert_allclose(y, x @ w_star, rtol=0, atol=1e-4)
        xs.append(x)
    assert not np.allclose(xs[0], xs[1])


def test_work_counts_are_the_block_passes():
    cell = lsq_cell()
    problem = harness.problem(cell.config["problem"])
    shape = (100_000, {"d": 1000, "m": 1000}, 2, 32)
    assert problem.epoch_work(*shape) == work.mtls_block_epoch(100_000, 1000, 1000, 2, 32)
    assert problem.power_work(*shape) == work.mtls_block_power(100_000, 1000, 1000, 2, 32)


def test_reference_loss_and_gradient_are_the_f32_maths():
    """The problem's block terms are F, X.T (X W - Y) and ||X D||^2."""
    cell = lsq_cell()
    problem = harness.problem(cell.config["problem"])
    rng = np.random.default_rng(0)
    x, y = rng.normal(size=(64, 12)), rng.normal(size=(64, 9))
    w, d = rng.normal(size=(12, 9)), rng.normal(size=(12, 9))
    loss, grad = problem.block_loss_grad(jnp.asarray(x, jnp.float32),
                                         jnp.asarray(y, jnp.float32),
                                         jnp.asarray(w, jnp.float32), jnp.float32)
    r = x @ w - y
    assert float(loss) == pytest.approx(0.5 * np.sum(r * r), rel=1e-5)
    np.testing.assert_allclose(np.asarray(grad), x.T @ r, rtol=1e-4, atol=1e-3)
    sq = problem.block_step_terms(jnp.asarray(x, jnp.float32),
                                  jnp.asarray(d, jnp.float32), jnp.float32)
    assert float(sq) == pytest.approx(np.sum((x @ d) ** 2), rel=1e-5)
