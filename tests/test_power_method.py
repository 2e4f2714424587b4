"""Distributed power method: accuracy, two-sided sign property, K(t) regimes."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import power_method, sphere_vector, top_singular_pair


@pytest.mark.parametrize("d,m", [(30, 20), (64, 64), (17, 51)])
def test_converges_to_top_pair(d, m):
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (d, m))
    u, s, vt = np.linalg.svd(np.asarray(a), full_matrices=False)
    # iteration budget must cover the worst spectral gap across the
    # parametrized shapes: (17, 51) has s2/s1 ~ 0.983, so ~100 iterations
    # only contract the off-axis mass to ~0.18 — 300 converge fully
    res = top_singular_pair(a, jax.random.PRNGKey(1), num_iters=300)
    assert res.sigma == pytest.approx(s[0], rel=1e-4)
    # direction match up to sign (sign fixed by two-sided iteration: u^T A v >= 0)
    assert abs(float(jnp.dot(res.u, u[:, 0]))) > 0.999
    assert abs(float(jnp.dot(res.v, vt[0]))) > 0.999
    assert float(res.u @ np.asarray(a) @ res.v) >= 0.0


def test_sigma_underestimates_monotone():
    """||A^T u_K|| is nondecreasing in K and bounded by sigma1."""
    a = jax.random.normal(jax.random.PRNGKey(3), (40, 30))
    s1 = float(jnp.linalg.svd(a, compute_uv=False)[0])
    prev = 0.0
    for k in [1, 2, 4, 8, 16]:
        res = top_singular_pair(a, jax.random.PRNGKey(7), num_iters=k)
        sig = float(res.sigma)
        assert sig <= s1 * (1 + 1e-5)
        assert sig >= prev - 1e-5
        prev = sig


def test_kuczynski_expected_error_bound():
    """Thm (Kuczyński & Woźniakowski): E|sigma_est-s1|/s1 <= 0.871 ln(m)/(K-1)
    for the eigenvalue estimate of A^T A. Monte-Carlo over random starts."""
    key = jax.random.PRNGKey(11)
    a = jax.random.normal(key, (50, 32))
    s1sq = float(jnp.linalg.svd(a, compute_uv=False)[0]) ** 2
    m = 32
    for K in (3, 6, 12):
        errs = []
        for trial in range(64):
            res = top_singular_pair(a, jax.random.fold_in(key, 1000 + trial * 13 + K), num_iters=K)
            errs.append(abs(float(res.sigma) ** 2 - s1sq) / s1sq)
        bound = 0.871 * np.log(m) / (K - 1)
        assert np.mean(errs) <= bound, (K, np.mean(errs), bound)


def test_sphere_vector_unit_norm():
    for i in range(5):
        v = sphere_vector(jax.random.PRNGKey(i), 33)
        assert float(jnp.linalg.norm(v)) == pytest.approx(1.0, abs=1e-5)


def test_worker_weight_zero_removes_contribution():
    """Straggler masking: weight=0 must reproduce the masked-out result."""
    a = jax.random.normal(jax.random.PRNGKey(0), (20, 10))
    res_w = power_method.power_iterations(
        lambda v: a @ v, lambda u: a.T @ u,
        sphere_vector(jax.random.PRNGKey(1), 10), 50,
        worker_weight=jnp.float32(0.5),  # scale-invariant: same direction
    )
    res = power_method.power_iterations(
        lambda v: a @ v, lambda u: a.T @ u,
        sphere_vector(jax.random.PRNGKey(1), 10), 50,
    )
    np.testing.assert_allclose(res_w.u, res.u, atol=1e-5)
    assert float(res_w.sigma) == pytest.approx(0.5 * float(res.sigma), rel=1e-5)


# ---------------------------------------------------------------------------
# Perf fix regression: sigma is carried out of the loop, not recomputed
# ---------------------------------------------------------------------------


def _reference_power_iterations(matvec, rmatvec, v0, num_iters):
    """The pre-fix implementation (2K+1 aggregation rounds): loop carries
    (u, v) only and sigma is recomputed with an extra rmatvec afterwards.
    Kept verbatim as the trajectory oracle for the carried-sigma version."""
    def body(_, carry):
        _, v = carry
        u = matvec(v)
        u = u / (jnp.linalg.norm(u) + 1e-30)
        vv = rmatvec(u)
        v = vv / (jnp.linalg.norm(vv) + 1e-30)
        return (u, v)

    u0 = jnp.zeros_like(matvec(v0))
    u, v = jax.lax.fori_loop(0, num_iters, body, (u0, v0))
    sigma = jnp.linalg.norm(rmatvec(u))
    return power_method.PowerResult(u=u, v=v, sigma=sigma)


@pytest.mark.parametrize("k", [1, 2, 5])
def test_carried_sigma_trajectory_unchanged(k):
    """The 2K-round version must produce the same (u, v, sigma): the last
    loop iteration's aggregated rmatvec IS the old post-loop recompute.

    u and v come out of identical loop bodies and match bit for bit. sigma
    is the same norm of the same vector, but one version takes it inside
    the loop body and the other after the loop, and XLA may fuse (and so
    order) the two reductions differently: it is held to a few f32 ulp."""
    a = jax.random.normal(jax.random.PRNGKey(42), (40, 30))
    v0 = sphere_vector(jax.random.PRNGKey(43), 30)
    got = power_method.power_iterations(
        lambda v: a @ v, lambda u: a.T @ u, v0, k
    )
    want = _reference_power_iterations(lambda v: a @ v, lambda u: a.T @ u, v0, k)
    assert np.array_equal(np.asarray(got.u), np.asarray(want.u))
    assert np.array_equal(np.asarray(got.v), np.asarray(want.v))
    np.testing.assert_allclose(np.asarray(got.sigma), np.asarray(want.sigma),
                               rtol=4 * np.finfo(np.float32).eps, atol=0)


def test_collective_rounds_per_epoch_is_2k():
    """An epoch's power method costs exactly 2K collective rounds (was 2K+1
    before the sigma carry). The bound itself lives with the code that owns
    it — ``power_method.collective_rounds_contract(K)`` — and this test (like
    ``tools/repro_contracts.py``) just checks that declaration against the
    compiled HLO of a shard_map'd power_iterations on 8 fake devices."""
    src = str(Path(__file__).resolve().parent.parent / "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = src
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.compat import shard_map_compat
        from repro.core import power_method

        # Row-shard an explicit (n, m) matrix: each worker holds a (n/8, m)
        # summand A_j, so the implicit operator A = sum_j A_j is (n/8, m).
        K, n, m = 3, 512, 48
        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))

        def run(a, v0):
            return power_method.power_iterations(
                lambda v: a @ v, lambda u: a.T @ u, v0, K, axis_name="data")

        wrapped = shard_map_compat(
            run, mesh, in_specs=(P("data"), P()),
            out_specs=power_method.PowerResult(u=P(), v=P(), sigma=P()))
        a = jax.ShapeDtypeStruct((n, m), jnp.float32)
        v0 = jax.ShapeDtypeStruct((m,), jnp.float32)
        contract = power_method.collective_rounds_contract(K)
        analysis = contract.check_hlo(wrapped, a, v0)
        print("collective rounds:", analysis["collective_count"])
    """)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"
    assert "collective rounds" in out.stdout
