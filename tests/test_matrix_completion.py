"""MatrixCompletion task: sparse sufficient information vs dense oracles.

The task state is O(|Omega_j|) COO shards; every check here compares the
segment-gather/scatter chains against an explicitly materialized d x m
simulation of the same FW trajectory (small instances only).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fit, low_rank, tasks
from repro.launch import dfw

KEY = jax.random.PRNGKey(0)


def _mc_problem(key, d=30, m=24, rank=3, obs=0.4):
    ku, kv, kx = jax.random.split(key, 3)
    u = jnp.linalg.qr(jax.random.normal(ku, (d, rank)))[0]
    v = jnp.linalg.qr(jax.random.normal(kv, (m, rank)))[0]
    s = jnp.linspace(1.0, 0.3, rank)
    s = s / jnp.sum(s)  # trace norm exactly 1
    w_true = (u * s) @ v.T
    mask = jax.random.bernoulli(kx, obs, (d, m))
    rows, cols = jnp.nonzero(mask)
    return rows, cols, w_true[rows, cols], w_true


def _dense_grad(d, m, rows, cols, resid):
    g = np.zeros((d, m), np.float32)
    np.add.at(g, (np.asarray(rows), np.asarray(cols)), np.asarray(resid))
    return g


def test_matvec_rmatvec_match_dense_oracle():
    rows, cols, vals, _ = _mc_problem(KEY)
    task = tasks.MatrixCompletion(d=30, m=24)
    s = task.init_state(*tasks.pack_observations(rows, cols, vals))
    g = _dense_grad(30, 24, rows, cols, s.resid)
    v = jax.random.normal(jax.random.fold_in(KEY, 1), (24,))
    u = jax.random.normal(jax.random.fold_in(KEY, 2), (30,))
    np.testing.assert_allclose(np.asarray(task.matvec(s, v)), g @ np.asarray(v),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(task.rmatvec(s, u)), g.T @ np.asarray(u),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(task.local_grad(s)), g,
                               rtol=1e-5, atol=1e-6)


def test_fw_trajectory_matches_dense_simulation():
    """Run real FW epochs on the sparse state and replay them densely: the
    materialize-free losses/gaps must match the dense-oracle bookkeeping."""
    rows, cols, vals, _ = _mc_problem(KEY)
    d, m, mu = 30, 24, 1.2
    task = tasks.MatrixCompletion(d=d, m=m)
    res = fit(task, task.init_state(*tasks.pack_observations(rows, cols, vals)),
              mu=mu, num_epochs=10, key=jax.random.PRNGKey(1),
              schedule="const:2", step_size="linesearch")
    w = low_rank.materialize(res.iterate)
    # state residual == dense residual of the factored iterate
    np.testing.assert_allclose(np.asarray(res.state.resid),
                               np.asarray(w[rows, cols] - vals),
                               rtol=1e-3, atol=1e-5)
    # sufficient-information loss == dense objective
    dense_loss = 0.5 * float(jnp.sum((w[rows, cols] - vals) ** 2))
    np.testing.assert_allclose(float(task.local_loss(res.state)), dense_loss,
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(res.final_loss, dense_loss, rtol=1e-4, atol=1e-7)


def test_inner_w_grad_matches_dense():
    rows, cols, vals, _ = _mc_problem(KEY)
    task = tasks.MatrixCompletion(d=30, m=24)
    s = task.init_state(*tasks.pack_observations(rows, cols, vals))
    u = jax.random.normal(jax.random.fold_in(KEY, 3), (30,))
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (24,))
    u, v = u / jnp.linalg.norm(u), v / jnp.linalg.norm(v)
    s = task.update(s, u, v, 0.4, 1.5)  # some nonzero iterate
    w = np.zeros((30, 24), np.float32)
    w[np.asarray(rows), np.asarray(cols)] = np.asarray(s.resid + vals)
    g = _dense_grad(30, 24, rows, cols, s.resid)
    np.testing.assert_allclose(float(task.inner_w_grad(s)), float((w * g).sum()),
                               rtol=1e-4, atol=1e-5)


def test_linesearch_is_exact_quadratic_minimizer():
    rows, cols, vals, _ = _mc_problem(KEY)
    d, m, mu = 30, 24, 1.5
    task = tasks.MatrixCompletion(d=d, m=m)
    s = task.init_state(*tasks.pack_observations(rows, cols, vals))
    u = jax.random.normal(jax.random.fold_in(KEY, 5), (d,))
    v = jax.random.normal(jax.random.fold_in(KEY, 6), (m,))
    u, v = u / jnp.linalg.norm(u), v / jnp.linalg.norm(v)
    s = task.update(s, u, v, 0.3, mu)  # move off W=0 first
    numer, denom = task.linesearch_terms(s, u, v, mu)
    gamma_star = float(numer) / float(denom)

    def dense_loss(gamma):
        w = np.zeros((d, m), np.float32)
        w[np.asarray(rows), np.asarray(cols)] = np.asarray(s.resid + vals)
        w2 = (1 - gamma) * w - gamma * mu * np.outer(u, v)
        return 0.5 * ((w2[np.asarray(rows), np.asarray(cols)]
                       - np.asarray(vals)) ** 2).sum()

    eps = 1e-3
    assert dense_loss(gamma_star) <= dense_loss(gamma_star + eps) + 1e-9
    assert dense_loss(gamma_star) <= dense_loss(gamma_star - eps) + 1e-9


def test_zero_weight_padding_is_noop():
    """Padded states must give the same losses, matvecs and updates — the
    invariant the shard_map driver's static shapes rest on.

    Every padding entry contributes an exact 0 (its residual is 0 and stays
    0, asserted below), and the matvecs scatter those zeros without changing
    any row's sum. The losses, though, sum a longer vector, and XLA's
    reduction tree depends on the length: they agree to a few f32 ulp, not
    bit for bit."""
    rows, cols, vals, _ = _mc_problem(KEY)
    task = tasks.MatrixCompletion(d=30, m=24)
    s0 = task.init_state(*tasks.pack_observations(rows, cols, vals))

    pad = 17  # arbitrary coordinates with weight 0 — values must not matter
    rows_p = jnp.concatenate([rows, jnp.full((pad,), 3, rows.dtype)])
    cols_p = jnp.concatenate([cols, jnp.full((pad,), 5, cols.dtype)])
    vals_p = jnp.concatenate([vals, jnp.full((pad,), 123.0)])
    w_p = jnp.concatenate([jnp.ones_like(vals), jnp.zeros((pad,))])
    s1 = task.init_state(*tasks.pack_observations(rows_p, cols_p, vals_p, w_p))

    v = jax.random.normal(jax.random.fold_in(KEY, 7), (24,))
    u = jax.random.normal(jax.random.fold_in(KEY, 8), (30,))
    np.testing.assert_array_equal(np.asarray(task.matvec(s0, v)),
                                  np.asarray(task.matvec(s1, v)))
    ulps = dict(rtol=4 * np.finfo(np.float32).eps, atol=0)
    np.testing.assert_allclose(float(task.local_loss(s0)),
                               float(task.local_loss(s1)), **ulps)
    u, v = u / jnp.linalg.norm(u), v / jnp.linalg.norm(v)
    s0u, s1u = task.update(s0, u, v, 0.5, 1.0), task.update(s1, u, v, 0.5, 1.0)
    np.testing.assert_allclose(float(task.local_loss(s0u)),
                               float(task.local_loss(s1u)), **ulps)
    np.testing.assert_allclose(task.linesearch_terms(s0u, u, v, 1.0),
                               task.linesearch_terms(s1u, u, v, 1.0),
                               rtol=1e-6)
    # padded residuals stay exactly zero through updates
    assert float(jnp.max(jnp.abs(s1u.resid[-pad:]))) == 0.0


def test_gather_entries_matches_materialize():
    rows, cols, vals, _ = _mc_problem(KEY)
    task = tasks.MatrixCompletion(d=30, m=24)
    res = fit(task, task.init_state(*tasks.pack_observations(rows, cols, vals)),
              mu=1.0, num_epochs=6, key=jax.random.PRNGKey(2),
              schedule="const:1")
    w = low_rank.materialize(res.iterate)
    got = low_rank.gather_entries(res.iterate, rows, cols)
    np.testing.assert_allclose(np.asarray(got), np.asarray(w[rows, cols]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.slow  # 80-epoch recovery sweep
def test_completion_recovers_low_rank_matrix():
    """Acceptance: held-out RMSE decreasing, duality gap reaching tolerance."""
    rows, cols, vals, w_true = _mc_problem(jax.random.PRNGKey(3),
                                           d=48, m=36, rank=3, obs=0.45)
    ks = jax.random.fold_in(KEY, 9)
    holdout = jax.random.bernoulli(ks, 0.15, rows.shape)
    tr = jnp.nonzero(~holdout)[0]
    ho = jnp.nonzero(holdout)[0]
    task = tasks.MatrixCompletion(d=48, m=36)

    def ho_rmse(it):
        pred = low_rank.gather_entries(it, rows[ho], cols[ho])
        return float(jnp.sqrt(jnp.mean((pred - vals[ho]) ** 2)))

    state0 = task.init_state(*tasks.pack_observations(rows[tr], cols[tr],
                                                      vals[tr]))
    short = fit(task, state0, mu=1.0, num_epochs=10, key=jax.random.PRNGKey(4),
                schedule="const:2", step_size="linesearch")
    res = fit(task, state0, mu=1.0, num_epochs=80, key=jax.random.PRNGKey(4),
              schedule="const:2", step_size="linesearch")
    # train loss collapses; gap reaches tolerance
    assert res.final_loss < 0.02 * res.history["loss"][0]
    assert res.history["gap"][-1] < 0.1 * res.history["gap"][0]
    # held-out RMSE decreases with epochs and beats the predict-zero baseline
    base = float(jnp.sqrt(jnp.mean(vals[ho] ** 2)))
    assert ho_rmse(res.iterate) < ho_rmse(short.iterate) < base
    assert ho_rmse(res.iterate) < 0.5 * base


def test_kernelized_mc_matches_base_task():
    rows, cols, vals, _ = _mc_problem(KEY)
    task = tasks.MatrixCompletion(d=30, m=24)
    s = task.init_state(*tasks.pack_observations(rows, cols, vals))
    ktask = dfw.kernelize(task)
    v = jax.random.normal(jax.random.fold_in(KEY, 10), (24,))
    u = jax.random.normal(jax.random.fold_in(KEY, 11), (30,))
    np.testing.assert_allclose(np.asarray(ktask.matvec(s, v)),
                               np.asarray(task.matvec(s, v)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ktask.rmatvec(s, u)),
                               np.asarray(task.rmatvec(s, u)),
                               rtol=1e-5, atol=1e-5)
    err = dfw.verify_kernelized(task, ktask, s, jax.random.fold_in(KEY, 12))
    assert err < 1e-4


def test_shard_observations_row_blocks():
    d, nw = 30, 4
    rows, cols, vals, _ = _mc_problem(KEY, d=d)
    idx, yw = dfw.shard_observations(rows, cols, vals, nw, d, m=24)
    assert idx.shape[0] % nw == 0
    p = idx.shape[0] // nw
    block = -(-d // nw)
    for j in range(nw):
        sl = slice(j * p, (j + 1) * p)
        w = np.asarray(yw[sl, 1])
        r = np.asarray(idx[sl, 0])
        # live entries sit in worker j's row block; padding has weight 0
        live = w > 0
        assert np.all(r[live] // block == j) or not live.any()
    # no observation lost or duplicated: weighted values reassemble exactly
    got = np.zeros((d, 24), np.float32)
    np.add.at(got, (np.asarray(idx[:, 0]), np.asarray(idx[:, 1])),
              np.asarray(yw[:, 0] * yw[:, 1]))
    want = np.zeros((d, 24), np.float32)
    np.add.at(want, (np.asarray(rows), np.asarray(cols)), np.asarray(vals))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert float(jnp.sum(yw[:, 1])) == rows.shape[0]


def test_shard_observations_rejects_bad_indices():
    with pytest.raises(ValueError, match="row indices"):
        dfw.shard_observations(jnp.array([0, 40]), jnp.array([0, 1]),
                               jnp.array([1.0, 2.0]), 4, 30)
    # out-of-range columns would be silently clipped by the downstream
    # gather/segment chains — the host-side layout must reject them
    with pytest.raises(ValueError, match="column indices"):
        dfw.shard_observations(jnp.array([0, 1]), jnp.array([0, 24]),
                               jnp.array([1.0, 2.0]), 4, 30, m=24)
    with pytest.raises(ValueError, match="nonnegative"):
        dfw.shard_observations(jnp.array([0, 1]), jnp.array([0, -1]),
                               jnp.array([1.0, 2.0]), 4, 30)
