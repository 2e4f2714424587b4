"""Block Frank-Wolfe solver tier (``solver="block:k"``).

Covers the shared solver-spec grammar (the single validation point for
``frank_wolfe.fit`` / ``fit_serial`` / ``DFWConfig``), the block power
primitives (Cholesky-QR orthonormalization, rank-k LMO recovery), the
rank-k iterate update, ``block:1`` == ``rank1`` trajectory equivalence
(serial + 8-way), the spectral-gap-adaptive iteration budget, warm-start
vs cold-start convergence, engine dispatch pins with the block solver,
and checkpoint format v2 (probe-carrying payloads resume bit-exactly;
v1 payloads restore with a cold probe and still converge).
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, frank_wolfe, low_rank, power_method, tasks
from repro.launch import dfw

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _run(script: str, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr[-4000:]}"
    return res.stdout


def _mtls(key, n=400, d=24, m=18, rank=None):
    kx, kw = jax.random.split(key)
    w = jax.random.normal(kw, (d, m))
    if rank is not None:
        u, s, vt = jnp.linalg.svd(w, full_matrices=False)
        w = (u[:, :rank] * s[:rank]) @ vt[:rank]
    w = w / jnp.linalg.norm(w, ord="nuc")
    x = jax.random.normal(kx, (n, d))
    return x, x @ w


# ---------------------------------------------------------------------------
# Solver-spec grammar: one shared validation point
# ---------------------------------------------------------------------------


def test_parse_solver_grammar():
    s = frank_wolfe.parse_solver("rank1")
    assert s == frank_wolfe.SolverSpec("rank1", 1, False, False)
    s = frank_wolfe.parse_solver("block:4")
    assert (s.kind, s.k, s.adaptive, s.cold) == ("block", 4, False, False)
    s = frank_wolfe.parse_solver("block:2:adapt")
    assert s.adaptive and not s.cold
    s = frank_wolfe.parse_solver("block:2:cold:adapt")
    assert s.adaptive and s.cold
    # an already-parsed spec passes through
    assert frank_wolfe.parse_solver(s) is s


@pytest.mark.parametrize(
    "bad",
    ["block:0", "block:-3", "block:", "block", "block:x", "block:2:warm",
     "svd", ""],
)
def test_parse_solver_rejects_malformed(bad):
    with pytest.raises(ValueError):
        frank_wolfe.parse_solver(bad)


def test_parse_solver_rejects_non_string():
    with pytest.raises(ValueError, match="string"):
        frank_wolfe.parse_solver(4)


def test_all_entry_points_share_validation(tmp_path):
    """frank_wolfe.fit, fit_serial, and the sharded driver all reject a
    malformed spec with the same parse error — no driver-specific grammar."""
    x, y = _mtls(jax.random.PRNGKey(0), n=64)
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    state = task.init_state(x, y)
    with pytest.raises(ValueError, match="block width"):
        frank_wolfe.fit(task, state, mu=1.0, num_epochs=2,
                        key=jax.random.PRNGKey(1), solver="block:0")
    cfg = dfw.DFWConfig(mu=1.0, num_epochs=2, solver="block:-3",
                        verify_kernels=False)
    with pytest.raises(ValueError, match="block width"):
        dfw.fit_serial(task, x, y, cfg=cfg, key=jax.random.PRNGKey(1))
    cfg = dfw.DFWConfig(mu=1.0, num_epochs=2, solver="block:",
                        verify_kernels=False)
    with pytest.raises(ValueError, match="needs a width"):
        dfw.fit(task, x, y, cfg=cfg, key=jax.random.PRNGKey(1), num_workers=1)


def test_block_width_exceeding_dims_rejected():
    x, y = _mtls(jax.random.PRNGKey(0), n=64)
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    state = task.init_state(x, y)
    with pytest.raises(ValueError, match="exceeds"):
        frank_wolfe.fit(task, state, mu=1.0, num_epochs=2,
                        key=jax.random.PRNGKey(1), solver="block:19")


def test_init_probe_shapes():
    assert frank_wolfe.init_probe("rank1", 10) == ()
    p = frank_wolfe.init_probe("block:3", 10)
    assert p.shape == (10, 3)
    np.testing.assert_allclose(np.asarray(p.T @ p), np.eye(3), atol=1e-5)


def test_init_probe_is_drawn_from_the_runs_key():
    """A block run starts from epoch 0's key, as a rank-1 run does: block:1's
    probe is the rank-1 solver's epoch-0 vector (up to sign), and two keys
    give two starts."""
    key = jax.random.PRNGKey(7)
    p1 = np.asarray(frank_wolfe.init_probe("block:1", 30, key))[:, 0]
    v0 = np.asarray(power_method.sphere_vector(jax.random.fold_in(key, 0), 30))
    assert abs(float(p1 @ v0)) == pytest.approx(1.0, abs=1e-5)
    a = np.asarray(frank_wolfe.init_probe("block:3", 30, key))
    b = np.asarray(frank_wolfe.init_probe("block:3", 30, jax.random.PRNGKey(8)))
    np.testing.assert_allclose(a.T @ a, np.eye(3), atol=1e-5)
    assert not np.allclose(np.abs(a), np.abs(b))


# ---------------------------------------------------------------------------
# Block power primitives
# ---------------------------------------------------------------------------


def test_orthonormalize_block():
    b = jax.random.normal(jax.random.PRNGKey(0), (50, 6))
    q = power_method.orthonormalize_block(b)
    np.testing.assert_allclose(np.asarray(q.T @ q), np.eye(6), atol=1e-4)
    # span is preserved: projection of b onto span(q) equals b
    np.testing.assert_allclose(
        np.asarray(q @ (q.T @ b)), np.asarray(b), atol=1e-3
    )
    # all-zero block maps to all-zero block (jitter keeps cholesky defined)
    z = power_method.orthonormalize_block(jnp.zeros((50, 6)))
    assert np.all(np.isfinite(np.asarray(z)))


@pytest.mark.parametrize("seed", range(4))
def test_orthonormalize_rank_deficient_wide_block_stays_finite(seed):
    """A width-32 block of rank 10 at d = 1000, as the least-squares
    gradient against a rank-10 truth gives: its f32 Gram's null
    eigenvalues round below zero, and the jitter has to cover them or the
    Cholesky factor is NaN. The first 10 columns span the block, and no
    column comes out longer than a unit vector."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(k1, (1000, 10)) * jnp.linspace(1.0, 0.1, 10)
    b = np.asarray(a @ jax.random.normal(k2, (10, 32)))
    q = np.asarray(power_method.orthonormalize_block(jnp.asarray(b)))
    assert np.all(np.isfinite(q))
    p = np.linalg.qr(q[:, :10])[0]
    assert np.linalg.norm(b - p @ (p.T @ b)) < 1e-3 * np.linalg.norm(b)
    assert np.all(np.linalg.norm(q, axis=0) <= 1.0 + 1e-3)


def test_block_power_recovers_top_k():
    # Controlled spectrum: well-separated top-k so K iterations provably
    # converge (a raw Gaussian can have arbitrarily small sigma_k gaps).
    key = jax.random.PRNGKey(1)
    qu, _ = jnp.linalg.qr(jax.random.normal(key, (40, 30)))
    qv, _ = jnp.linalg.qr(jax.random.normal(jax.random.fold_in(key, 1), (30, 30)))
    spec = jnp.concatenate([jnp.asarray([8.0, 6.0, 4.0, 2.5]),
                            jnp.full((26,), 0.5)])
    a = (qu * spec) @ qv.T
    k = 4
    v0 = frank_wolfe.init_probe(f"block:{k}", 30)
    res, cs = power_method.block_power_iterations(
        lambda v: a @ v, lambda u: a.T @ u, v0, 40
    )
    assert cs == ()
    true_s = np.linalg.svd(np.asarray(a), compute_uv=False)[:k]
    np.testing.assert_allclose(
        np.sort(np.asarray(res.sigma))[::-1], true_s, rtol=1e-3
    )
    # u/v columns pair as atoms: u_j^T A v_j == sigma_j
    pairs = np.asarray(jnp.einsum("dk,dm,mk->k", res.u, a, res.v))
    np.testing.assert_allclose(pairs, np.asarray(res.sigma), rtol=1e-3)
    # the probe is orthonormal — a valid warm start
    np.testing.assert_allclose(
        np.asarray(res.probe.T @ res.probe), np.eye(k), atol=1e-4
    )
    assert int(res.iters) == 40


def test_block_collective_rounds_contract_fields():
    c = power_method.block_collective_rounds_contract(3, 4)
    assert c.collective_counts == {"all-reduce": 6.0}
    assert "k=4" in c.name


def test_fw_update_block_matches_dense_recurrence():
    key = jax.random.PRNGKey(2)
    d, m, k, mu = 12, 9, 3, 2.0
    it = low_rank.init(10, d, m)
    # seed with one rank-1 step so alpha-folding is exercised
    u1 = jax.random.normal(jax.random.fold_in(key, 0), (d,))
    v1 = jax.random.normal(jax.random.fold_in(key, 1), (m,))
    u1, v1 = u1 / jnp.linalg.norm(u1), v1 / jnp.linalg.norm(v1)
    it = low_rank.fw_update(it, u1, v1, jnp.float32(0.7), mu)
    ub = jax.random.normal(jax.random.fold_in(key, 2), (d, k))
    ub = ub / jnp.linalg.norm(ub, axis=0)
    vb = jax.random.normal(jax.random.fold_in(key, 3), (m, k))
    vb = vb / jnp.linalg.norm(vb, axis=0)
    c = jnp.asarray([0.5, 0.3, 0.2])
    gamma = jnp.float32(0.4)
    w_before = low_rank.materialize(it)
    it2 = low_rank.fw_update_block(it, ub, vb, c, gamma, mu)
    s_block = -mu * jnp.einsum("k,dk,mk->dm", c, ub, vb)
    expect = (1.0 - gamma) * w_before + gamma * s_block
    np.testing.assert_allclose(
        np.asarray(low_rank.materialize(it2)), np.asarray(expect), atol=1e-5
    )
    assert int(it2.count) == int(it.count) + k
    # gamma == 1 annihilates the old iterate, exactly like fw_update
    it3 = low_rank.fw_update_block(it, ub, vb, c, jnp.float32(1.0), mu)
    np.testing.assert_allclose(
        np.asarray(low_rank.materialize(it3)), np.asarray(s_block), atol=1e-5
    )


# ---------------------------------------------------------------------------
# Solver behavior: equivalence, adaptivity, warm start
# ---------------------------------------------------------------------------


def test_block1_cold_matches_rank1_serial():
    """block:1:cold and rank1 compute the same top singular atom each epoch
    up to LMO convergence (different v0 draws, same fixed point up to sign —
    the atom u v^T is sign-invariant), so the trajectories coincide to the
    (sigma_2/sigma_1)^K power-iteration error, not bit-exactly."""
    x, y = _mtls(jax.random.PRNGKey(4))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    kwargs = dict(mu=1.0, num_epochs=10, key=jax.random.PRNGKey(1),
                  schedule="const:25", step_size="linesearch")
    r1 = frank_wolfe.fit(task, task.init_state(x, y), **kwargs)
    rb = frank_wolfe.fit(task, task.init_state(x, y), solver="block:1:cold",
                         **kwargs)
    # Epoch 0 is pre-update: identical state, so identical loss exactly.
    assert rb.history["loss"][0] == r1.history["loss"][0]
    np.testing.assert_allclose(rb.history["loss"], r1.history["loss"],
                               rtol=2e-2)
    np.testing.assert_allclose(rb.history["gap"], r1.history["gap"],
                               rtol=5e-2, atol=1e-4)
    assert rb.epochs_run == r1.epochs_run


def test_adaptive_stops_power_iterations_early():
    """The spectral-gap-adaptive budget executes fewer iterations once the
    warm-started probe is converged; the history-visible trajectory is
    intact and per-epoch piters (captured via the segment callback's aux
    rows) never exceeds K and drops below it on later epochs."""
    x, y = _mtls(jax.random.PRNGKey(5), rank=3)
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    K = 12
    piters = []
    res = frank_wolfe.fit(
        task, task.init_state(x, y), mu=1.0, num_epochs=12,
        key=jax.random.PRNGKey(1), schedule=f"const:{K}",
        step_size="linesearch", solver="block:3:adapt",
        callback=lambda t, aux: piters.extend(np.asarray(aux.piters)),
    )
    piters = [p for p in piters if not np.isnan(p)]
    assert len(piters) == res.epochs_run
    assert max(piters) <= K
    assert min(piters[1:]) < K, piters
    assert res.history["gap"][-1] < res.history["gap"][0]


def test_warm_start_beats_cold_start():
    """Carrying the converged right block between epochs gives a more
    accurate LMO than re-randomizing it every epoch, at the same per-epoch
    iteration budget — the reason the probe leaf exists.

    Accuracy is the power method's top singular value over the exact one of
    the epoch's gradient (the certificate's sigma term). The recorded gap is
    not the measure: an under-converged sigma *lowers* that lower-bound
    certificate, so on one final epoch a cold start can read smaller by
    chance."""
    x, y = _mtls(jax.random.PRNGKey(6), n=600, d=32, m=24, rank=6)
    task = tasks.MultiTaskLeastSquares(d=32, m=24)
    epochs = 15

    def sigma_accuracy(solver):
        sspec = frank_wolfe.parse_solver(solver)
        epoch = jax.jit(frank_wolfe.make_epoch_step(
            task, 1.0, 2, step_size="linesearch", solver=sspec))
        carry = frank_wolfe.init_carry(
            task.init_state(x, y), low_rank.init(epochs * 6, 32, 24),
            jax.random.PRNGKey(1), probe=frank_wolfe.init_probe(sspec, 24))
        ratios = []
        for _ in range(epochs):
            exact = np.linalg.svd(np.asarray(task.local_grad(carry.state)),
                                  compute_uv=False)[0]
            carry, aux = epoch(carry)
            ratios.append(float(aux.sigma) / exact)
        return np.asarray(ratios[1:])  # epoch 0: both start from random blocks

    warm, cold = sigma_accuracy("block:6"), sigma_accuracy("block:6:cold")
    assert np.all(warm <= 1 + 1e-5) and np.all(cold <= 1 + 1e-5)
    assert warm.mean() > cold.mean(), (warm.mean(), cold.mean())
    assert warm.min() > cold.min(), (warm.min(), cold.min())


def test_block_beats_rank1_epochs_to_gap():
    """The tentpole claim at test scale: on a low-rank MTLS problem the
    block solver reaches a fixed duality gap in >= 5x fewer epochs than
    rank1 (the benchmark suite pins this at Table-1 scale)."""
    x, y = _mtls(jax.random.PRNGKey(7), n=600, d=32, m=24, rank=6)
    task = tasks.MultiTaskLeastSquares(d=32, m=24)
    kwargs = dict(mu=1.0, num_epochs=60, key=jax.random.PRNGKey(1),
                  schedule="const:2", step_size="linesearch")
    r1 = frank_wolfe.fit(task, task.init_state(x, y), **kwargs)
    rb = frank_wolfe.fit(task, task.init_state(x, y), solver="block:6",
                         **kwargs)
    target = r1.history["gap"][0] * 0.05

    def epochs_to(hist):
        for i, g in enumerate(hist):
            if g <= target:
                return i + 1
        return None

    e1, eb = epochs_to(r1.history["gap"]), epochs_to(rb.history["gap"])
    assert eb is not None, "block solver never reached the target gap"
    assert e1 is None or e1 >= 5 * eb, (e1, eb)


def test_engine_dispatch_pins_hold_with_block_solver():
    """A const:K block run is still one scan dispatch + the final loss eval,
    device-resident under the transfer guard — the block tier changes the
    epoch math, not the execution discipline."""
    x, y = _mtls(jax.random.PRNGKey(8))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    c = engine.dispatch_contract()
    with c.guard():
        res = frank_wolfe.fit(
            task, task.init_state(x, y), mu=1.0, num_epochs=20,
            key=jax.random.PRNGKey(1), step_size="linesearch",
            solver="block:4:adapt",
        )
    c.check_stats(res.stats)
    assert int(res.iterate.count) == 20 * 4


def test_max_rank_capacity_scales_with_block_width():
    x, y = _mtls(jax.random.PRNGKey(9), n=64)
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    state = task.init_state(x, y)
    with pytest.raises(ValueError, match="overflow"):
        frank_wolfe.fit(task, state, mu=1.0, num_epochs=4, max_rank=4,
                        key=jax.random.PRNGKey(1), solver="block:3")
    res = frank_wolfe.fit(task, state, mu=1.0, num_epochs=4, max_rank=12,
                          key=jax.random.PRNGKey(1), solver="block:3")
    assert res.iterate.s.shape[0] == 12


def test_block_telemetry_through_registry():
    """dfw.block.k / dfw.block.power_iters ride the existing obs registry —
    no ad-hoc counters, no extra syncs."""
    from repro.obs import Telemetry

    x, y = _mtls(jax.random.PRNGKey(10), n=64)
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    tel = Telemetry()
    frank_wolfe.fit(task, task.init_state(x, y), mu=1.0, num_epochs=6,
                    key=jax.random.PRNGKey(1), solver="block:3",
                    telemetry=tel)
    snap = tel.registry.snapshot()
    assert snap["gauges"]["dfw.block.k"] == 3
    assert snap["counters"]["dfw.block.power_iters"] == 6 * 2  # const:2


# ---------------------------------------------------------------------------
# k-wide block products: one pass over the data for all k probe columns
# ---------------------------------------------------------------------------


def _lsq_cut(key, n=8192, d=500, m=500):
    """The least-squares cell's CPU cut (d = m = 500, 8,192 rows) with
    noisy targets, so the gradient at W = 0 has full rank and every probe
    column is pinned by the spectrum, not by rounding."""
    x, y = _mtls(key, n=n, d=d, m=m, rank=10)
    y = y + 1e-3 * jax.random.normal(jax.random.fold_in(key, 7), y.shape)
    return tasks.MultiTaskLeastSquares(d=d, m=m), x, y


def test_block_power_kwide_route_matches_vmap_route():
    task, x, y = _lsq_cut(jax.random.PRNGKey(20))
    state = task.init_state(x, y)
    v0 = frank_wolfe.init_probe("block:32", task.m, jax.random.PRNGKey(3))

    @partial(jax.jit, static_argnums=0)
    def lmo(native):
        kw = (dict(matmat=lambda v: task.matmat(state, v),
                   rmatmat=lambda u: task.rmatmat(state, u)) if native else {})
        res, _ = power_method.block_power_iterations(
            lambda v: task.matvec(state, v), lambda u: task.rmatvec(state, u),
            v0, 2, **kw)
        return res.sigma, res.u, res.v

    want, got = lmo(False), lmo(True)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    np.testing.assert_allclose(got[1], want[1], atol=1e-5)
    np.testing.assert_allclose(got[2], want[2], atol=1e-5)


def test_interpret_matmat_matches_ref_at_highest():
    """The k-wide Pallas kernels (in the interpreter) against the task's
    own f32 maths (``kernels="ref"``)."""
    ref, x, y = _lsq_cut(jax.random.PRNGKey(21), n=300, d=40, m=28)
    ref = dataclasses.replace(ref, kernels="ref")
    task = dataclasses.replace(ref, kernels="interpret")
    state = task.init_state(x, y)
    v = jax.random.normal(jax.random.PRNGKey(4), (task.m, 5))
    u = jax.random.normal(jax.random.PRNGKey(5), (task.d, 5))
    with jax.default_matmul_precision("highest"):
        want_v, want_u = ref.matmat(state, v), ref.rmatmat(state, u)
    for got, want in ((task.matmat(state, v), want_v),
                      (task.rmatmat(state, u), want_u)):
        assert got.shape == want.shape
        np.testing.assert_allclose(
            got, want, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(want))))


def _completion_obs(key, d=30, m=24):
    """A 40 % observed (d, m) completion problem, packed."""
    rows, cols = jnp.nonzero(jax.random.bernoulli(key, 0.4, (d, m)))
    vals = jax.random.normal(jax.random.fold_in(key, 1), rows.shape) / 10
    return tasks.pack_observations(rows, cols, vals)


_PROBE_NDIMS: list = []


@dataclasses.dataclass(frozen=True)
class _SpyCompletion(tasks.MatrixCompletion):
    """Matrix completion that records the rank of each probe its vector
    products are traced with."""

    def matvec(self, s, v):
        _PROBE_NDIMS.append(v.ndim)
        return super().matvec(s, v)


def test_completion_block_lmo_takes_the_vmap_fallback():
    task = _SpyCompletion(d=30, m=24)
    _PROBE_NDIMS.clear()
    res = frank_wolfe.fit(
        task, task.init_state(*_completion_obs(jax.random.PRNGKey(6))),
        mu=1.0, num_epochs=3, key=jax.random.PRNGKey(1), solver="block:2")
    assert np.isfinite(res.history["loss"]).all()
    # vmap hands the vector product one column at a time
    assert _PROBE_NDIMS and set(_PROBE_NDIMS) == {1}


def _logistic_state(key, n=64, d=24, m=18):
    task = tasks.MultinomialLogistic(d=d, m=m)
    x = jax.random.normal(key, (n, d))
    s = task.init_state(x, jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, m))
    # a nonzero iterate, so P is not uniform
    s = task.update(s, jax.random.normal(jax.random.fold_in(key, 2), (d,)),
                    jax.random.normal(jax.random.fold_in(key, 3), (m,)), 0.5, 1.0)
    return task, s


@pytest.mark.parametrize("kind", ["lsq", "logistic", "completion"])
def test_block_products_stack_the_vector_products(kind):
    """Every task's ``matmat``/``rmatmat``, the products the block LMO
    takes, are its ``matvec``/``rmatvec`` on each column, stacked."""
    key = jax.random.PRNGKey(22)
    if kind == "lsq":
        task = tasks.MultiTaskLeastSquares(d=24, m=18)
        state = task.init_state(*_mtls(key, n=64))
    elif kind == "logistic":
        task, state = _logistic_state(key)
    else:
        task = tasks.MatrixCompletion(d=30, m=24)
        state = task.init_state(*_completion_obs(key))
    v = jax.random.normal(jax.random.fold_in(key, 4), (task.m, 3))
    u = jax.random.normal(jax.random.fold_in(key, 5), (task.d, 3))
    for block, vector, probe in ((task.matmat, task.matvec, v),
                                 (task.rmatmat, task.rmatvec, u)):
        want = jnp.stack([vector(state, probe[:, j]) for j in range(3)], axis=1)
        got = block(state, probe)
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-6 * float(jnp.max(jnp.abs(want))))


# ---------------------------------------------------------------------------
# 8-way SPMD equivalence (subprocess: device count locks at first jax init)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_block_sharded_equals_serial_and_block1_equals_rank1_8way():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import tasks, frank_wolfe, low_rank, engine

        n, d, m = 1600, 40, 30
        key = jax.random.PRNGKey(0)
        kx, kw = jax.random.split(key)
        W = jax.random.normal(kw, (d, m)); W = W / jnp.linalg.norm(W, ord="nuc")
        X = jax.random.normal(kx, (n, d)); Y = X @ W
        task = tasks.MultiTaskLeastSquares(d=d, m=m)
        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        ss = tasks.MTLSState(x=P("data"), y=P("data"), r=P("data"))

        def fit(**kw):
            return frank_wolfe.fit(task, task.init_state(X, Y), mu=1.0,
                                   num_epochs=8, key=jax.random.PRNGKey(1),
                                   step_size="linesearch", **kw)

        # --- block:4 sharded == serial (same reducer, same seed) ---
        serial = fit(schedule="const:3", solver="block:4")
        wrap = engine.shard_map_segment_wrapper(
            mesh, "data", ss,
            probe_example=frank_wolfe.init_probe("block:4", m))
        dist = fit(schedule="const:3", solver="block:4", axis_name="data",
                   segment_wrapper=wrap)
        np.testing.assert_allclose(serial.history["loss"],
                                   dist.history["loss"], rtol=1e-4)
        W1 = low_rank.materialize(serial.iterate)
        W2 = low_rank.materialize(dist.iterate)
        assert float(jnp.max(jnp.abs(W1 - W2))) < 1e-4
        print("block shard_map == serial OK")

        # --- block:1:cold == rank1, 8-way (converged LMO) ---
        wrap1 = engine.shard_map_segment_wrapper(
            mesh, "data", ss,
            probe_example=frank_wolfe.init_probe("block:1", m))
        wrap_r = engine.shard_map_segment_wrapper(mesh, "data", ss)
        r1 = fit(schedule="const:25", axis_name="data", segment_wrapper=wrap_r)
        b1 = fit(schedule="const:25", solver="block:1:cold",
                 axis_name="data", segment_wrapper=wrap1)
        assert b1.history["loss"][0] == r1.history["loss"][0]
        np.testing.assert_allclose(b1.history["loss"], r1.history["loss"],
                                   rtol=2e-2)
        np.testing.assert_allclose(b1.history["gap"], r1.history["gap"],
                                   rtol=5e-2, atol=1e-4)
        print("block:1 == rank1 8-way OK")
    """)
    assert out.count("OK") == 2


@pytest.mark.slow
def test_block_collective_rounds_hlo_8way():
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.compat import shard_map_compat
        from repro.core import power_method

        K, k, n, m = 3, 4, 512, 48
        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))

        def run(a, v0):
            return power_method.block_power_iterations(
                lambda v: a @ v, lambda u: a.T @ u, v0, K, axis_name="data")

        bspec = power_method.BlockPowerResult(
            u=P(), v=P(), sigma=P(), probe=P(), iters=P())
        wrapped = shard_map_compat(run, mesh, in_specs=(P("data"), P()),
                                   out_specs=(bspec, ()))
        c = power_method.block_collective_rounds_contract(K, k)
        c.check_hlo(wrapped,
                    jax.ShapeDtypeStruct((n, m), jnp.float32),
                    jax.ShapeDtypeStruct((m, k), jnp.float32))
        print("block 2K rounds OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_block_int8_topk_reducers_compose_8way():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import tasks, frank_wolfe
        from repro.launch import dfw

        n, d, m = 1600, 40, 30
        key = jax.random.PRNGKey(0)
        kx, kw = jax.random.split(key)
        W = jax.random.normal(kw, (d, m)); W = W / jnp.linalg.norm(W, ord="nuc")
        X = jax.random.normal(kx, (n, d)); Y = X @ W
        task = tasks.MultiTaskLeastSquares(d=d, m=m)
        for comm in ("int8", "topk:64"):
            cfg = dfw.DFWConfig(mu=1.0, num_epochs=10, schedule="const:2",
                                step_size="linesearch", comm=comm,
                                solver="block:4", verify_kernels=False)
            res = dfw.fit(task, X, Y, cfg=cfg, key=jax.random.PRNGKey(1),
                          num_workers=8)
            assert res.epochs_run == 10
            assert res.history["gap"][-1] < res.history["gap"][0], comm
            print(comm, "block OK", res.history["gap"][-1])
    """)
    assert out.count("OK") == 2


# ---------------------------------------------------------------------------
# Checkpoint format v2: probe-carrying payloads
# ---------------------------------------------------------------------------


def test_block_resume_bitexact_v2_probe(tmp_path):
    """Same-mesh resume of a block run restores the warm-start probe from
    the v2 payload and reproduces the uninterrupted trajectory bit for
    bit — the probe is part of the carry, not re-derived."""
    x, y = _mtls(jax.random.PRNGKey(11))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    ckdir = str(tmp_path / "ck_block")
    cfg = dfw.DFWConfig(
        mu=1.0, num_epochs=20, schedule="const:2", step_size="linesearch",
        solver="block:3", block_epochs=5, checkpoint_dir=ckdir,
        checkpoint_keep=None, verify_kernels=False,
    )
    full = dfw.fit_serial(task, x, y, cfg=cfg, key=jax.random.PRNGKey(1))
    rcfg = dataclasses.replace(
        cfg, checkpoint_dir=None, resume_from=ckdir, resume_step=10
    )
    res = dfw.fit_serial(task, x, y, cfg=rcfg, key=jax.random.PRNGKey(1))
    assert res.epochs_run == full.epochs_run == 20
    for k in ("loss", "gap", "sigma", "gamma", "k"):
        assert res.history[k] == full.history[k], k
    assert res.final_loss == full.final_loss
    for name, a, b in zip(res.iterate._fields, res.iterate, full.iterate):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_v2_checkpoint_stamps_solver_and_probe(tmp_path):
    from repro.checkpoint import dfw as ckpt

    x, y = _mtls(jax.random.PRNGKey(12), n=64)
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    ckdir = str(tmp_path / "ck")
    cfg = dfw.DFWConfig(mu=1.0, num_epochs=6, solver="block:3",
                        block_epochs=3, checkpoint_dir=ckdir,
                        verify_kernels=False)
    dfw.fit_serial(task, x, y, cfg=cfg, key=jax.random.PRNGKey(1))
    _, extra = ckpt.read_run_extra(ckdir)
    assert extra["payload_format"] == ckpt.PAYLOAD_FORMAT
    assert extra["solver"] == "block:3"
    state = task.init_state(x, y)
    snap = ckpt.restore_run(ckdir, state_like=state)
    assert np.asarray(snap.carry.probe).shape == (18, 3)


def test_v1_payload_restores_with_cold_probe_and_converges(tmp_path):
    """A rank1 checkpoint rewritten to payload_format=1 with no solver key
    (byte-identical to what the pre-block build wrote) restores into a
    block-solver run: the probe falls back to the deterministic cold start
    and the resumed run still converges."""
    x, y = _mtls(jax.random.PRNGKey(13))
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    ckdir = tmp_path / "ck_v1"
    cfg = dfw.DFWConfig(
        mu=1.0, num_epochs=20, schedule="const:2", step_size="linesearch",
        block_epochs=5, checkpoint_dir=str(ckdir), checkpoint_keep=None,
        verify_kernels=False,
    )
    dfw.fit_serial(task, x, y, cfg=cfg, key=jax.random.PRNGKey(1))
    # Rewrite the step-10 manifest to the v1 schema: format 1, no solver.
    mpath = ckdir / "step_00000010" / "manifest.json"
    manifest = json.loads(mpath.read_text())
    manifest["extra"]["payload_format"] = 1
    manifest["extra"].pop("solver", None)
    mpath.write_text(json.dumps(manifest))

    rcfg = dataclasses.replace(
        cfg, checkpoint_dir=None, resume_from=str(ckdir), resume_step=10,
        solver="block:3",
    )
    res = dfw.fit_serial(task, x, y, cfg=rcfg, key=jax.random.PRNGKey(1))
    assert res.epochs_run == 20
    assert len(res.history["gap"]) == 20
    assert res.history["gap"][-1] < res.history["gap"][9]


def test_unknown_payload_format_rejected(tmp_path):
    from repro.checkpoint import dfw as ckpt

    x, y = _mtls(jax.random.PRNGKey(14), n=64)
    task = tasks.MultiTaskLeastSquares(d=24, m=18)
    ckdir = tmp_path / "ck"
    cfg = dfw.DFWConfig(mu=1.0, num_epochs=4, checkpoint_dir=str(ckdir),
                        verify_kernels=False)
    dfw.fit_serial(task, x, y, cfg=cfg, key=jax.random.PRNGKey(1))
    step_dirs = sorted(ckdir.glob("step_*"))
    mpath = step_dirs[-1] / "manifest.json"
    manifest = json.loads(mpath.read_text())
    manifest["extra"]["payload_format"] = 99
    mpath.write_text(json.dumps(manifest))
    with pytest.raises(ValueError, match="payload format"):
        ckpt.restore_run(str(ckdir), state_like=task.init_state(x, y))
