"""Tests for the distributed DFW-Trace execution layer (launch/dfw.py).

Multi-device coverage runs in subprocesses with 8 fake CPU devices (the
device count locks at the first jax init in the main pytest process); the
kernel-routing and worker-schedule units run in-process on one device.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tasks
from repro.kernels.power_matvec import ref as pm_ref
from repro.launch import dfw

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _run(script: str, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr[-4000:]}"
    return res.stdout


_PROBLEM = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import tasks, low_rank
        from repro.launch import dfw

        n, d, m = 1600, 40, 30
        key = jax.random.PRNGKey(0)
        kx, kw = jax.random.split(key)
        W = jax.random.normal(kw, (d, m)); W = W / jnp.linalg.norm(W, ord="nuc")
        X = jax.random.normal(kx, (n, d)); Y = X @ W
        yl = jnp.argmax(X @ W, axis=1)
"""


@pytest.mark.slow  # subprocess: fresh jax init + 8 fake devices
def test_sharded_equals_serial_mtls():
    """shard_map driver == serial driver on MTLS + line search (8 workers)."""
    out = _run(_PROBLEM + """
        task = tasks.MultiTaskLeastSquares(d=d, m=m)
        cfg = dfw.DFWConfig(mu=1.0, num_epochs=8, schedule="const:2",
                            step_size="linesearch")
        ser = dfw.fit_serial(task, X, Y, cfg=cfg, key=jax.random.PRNGKey(1))
        dist = dfw.fit(task, X, Y, cfg=cfg, key=jax.random.PRNGKey(1),
                       num_workers=8)
        np.testing.assert_allclose(ser.history["loss"], dist.history["loss"],
                                   rtol=1e-5)
        np.testing.assert_allclose(ser.history["gap"], dist.history["gap"],
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(ser.history["sigma"], dist.history["sigma"],
                                   rtol=1e-4)
        W1 = low_rank.materialize(ser.iterate)
        W2 = low_rank.materialize(dist.iterate)
        assert float(jnp.max(jnp.abs(W1 - W2))) < 1e-6
        print("mtls sharded == serial OK")
    """)
    assert "OK" in out


@pytest.mark.slow  # subprocess: fresh jax init + 8 fake devices
def test_sharded_equals_serial_logistic():
    """shard_map driver == serial driver on multinomial logistic (8 workers)."""
    out = _run(_PROBLEM + """
        task = tasks.MultinomialLogistic(d=d, m=m)
        cfg = dfw.DFWConfig(mu=10.0, num_epochs=8, schedule="log")
        ser = dfw.fit_serial(task, X, yl, cfg=cfg, key=jax.random.PRNGKey(1))
        dist = dfw.fit(task, X, yl, cfg=cfg, key=jax.random.PRNGKey(1),
                       num_workers=8)
        np.testing.assert_allclose(ser.history["loss"], dist.history["loss"],
                                   rtol=1e-5)
        np.testing.assert_allclose(ser.history["gap"], dist.history["gap"],
                                   rtol=1e-4, atol=1e-4)
        assert ser.history["k"] == dist.history["k"]  # same K(t) compilations
        print("logistic sharded == serial OK")
    """)
    assert "OK" in out


@pytest.mark.slow  # subprocess: fresh jax init + 8 fake devices
def test_sharded_equals_serial_matrix_completion():
    """shard_map driver == serial driver on matrix completion: row-block entry
    sharding with zero-weight padding, COO sufficient information (8 workers)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import tasks, low_rank
        from repro.launch import dfw

        d, m, rank = 64, 48, 5
        key = jax.random.PRNGKey(0)
        ku, kv, ko = jax.random.split(key, 3)
        U = jnp.linalg.qr(jax.random.normal(ku, (d, rank)))[0]
        V = jnp.linalg.qr(jax.random.normal(kv, (m, rank)))[0]
        sv = jnp.linspace(1.0, 0.2, rank); sv = sv / jnp.sum(sv)
        W = (U * sv) @ V.T
        mask = jax.random.bernoulli(ko, 0.35, (d, m))
        rows, cols = jnp.nonzero(mask)
        vals = W[rows, cols]

        task = tasks.MatrixCompletion(d=d, m=m)
        cfg = dfw.DFWConfig(mu=1.5, num_epochs=10, schedule="const:2",
                            step_size="linesearch")
        idx, yw = tasks.pack_observations(rows, cols, vals)
        ser = dfw.fit_serial(task, idx, yw, cfg=cfg, key=jax.random.PRNGKey(1))
        idx8, yw8 = dfw.shard_observations(rows, cols, vals, 8, d, m=m)
        dist = dfw.fit(task, idx8, yw8, cfg=cfg, key=jax.random.PRNGKey(1),
                       num_workers=8)
        np.testing.assert_allclose(ser.history["loss"], dist.history["loss"],
                                   rtol=1e-5)
        np.testing.assert_allclose(ser.history["gap"], dist.history["gap"],
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(ser.history["sigma"], dist.history["sigma"],
                                   rtol=1e-4)
        np.testing.assert_allclose(ser.final_loss, dist.final_loss, rtol=1e-5)
        W1 = low_rank.materialize(ser.iterate)
        W2 = low_rank.materialize(dist.iterate)
        assert float(jnp.max(jnp.abs(W1 - W2))) < 1e-6
        assert dist.final_loss < 0.3 * dist.history["loss"][0]  # it converges
        print("matrix completion sharded == serial OK")
    """)
    assert "OK" in out


@pytest.mark.slow  # subprocess: fresh jax init + 8 fake devices
def test_sampled_worker_mode_converges():
    """Bernoulli worker sampling (paper's straggler model): some workers drop
    every epoch, the run still converges, and masks are recorded."""
    out = _run(_PROBLEM + """
        task = tasks.MultiTaskLeastSquares(d=d, m=m)
        cfg = dfw.DFWConfig(mu=1.0, num_epochs=25, schedule="const:2",
                            step_size="linesearch", sample_prob=0.6)
        res = dfw.fit(task, X, Y, cfg=cfg, key=jax.random.PRNGKey(2),
                      num_workers=8)
        assert res.masks.shape == (25, 8)
        alive = jnp.sum(res.masks > 0, axis=1)
        assert float(jnp.min(alive)) >= 1          # LMO always defined
        assert float(jnp.max(alive)) <= 8
        assert bool(jnp.any(alive < 8))            # sampling actually dropped
        # reweighting keeps the psum an unbiased full-data estimate
        np.testing.assert_allclose(jnp.sum(res.masks, axis=1), 8.0, rtol=1e-5)
        assert res.history["loss"][-1] < 0.35 * res.history["loss"][0]
        print("sampled-worker mode OK", res.history["loss"][-1])
    """)
    assert "OK" in out


def test_uneven_rows_rejected():
    out = _run(_PROBLEM + """
        task = tasks.MultiTaskLeastSquares(d=d, m=m)
        cfg = dfw.DFWConfig(mu=1.0, num_epochs=2)
        try:
            dfw.fit(task, X[:1597], Y[:1597], cfg=cfg,
                    key=jax.random.PRNGKey(0), num_workers=8)
        except ValueError as e:
            assert "divisible" in str(e)
            print("uneven rows rejected OK")
    """)
    assert "OK" in out


def test_shard_rowwise_sends_host_rows_to_their_devices():
    """A host array goes straight to its row shards, and the state fit
    builds from it (the logistic Z) is born sharded the same way, with the
    data passed through uncopied."""
    out = _run("""
        import jax, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import tasks
        from repro.launch import dfw

        mesh = dfw.data_mesh(8)
        x = np.arange(64 * 3, dtype=np.float32).reshape(64, 3)
        y = np.arange(64, dtype=np.int32) % 5
        xs, ys = dfw.shard_rowwise(mesh, (x, y))
        for arr, host in ((xs, x), (ys, y)):
            shards = sorted(arr.addressable_shards, key=lambda s: s.device.id)
            assert len({s.device.id for s in shards}) == 8
            for s in shards:
                np.testing.assert_array_equal(np.asarray(s.data), host[s.index])
                assert s.data.shape[0] == 8
        task = tasks.MultinomialLogistic(d=3, m=5)
        st = dfw.init_rowwise_state(task, xs, ys)
        assert st.x is xs and st.y is ys  # the data is not copied
        assert st.z.sharding == NamedSharding(mesh, P("data"))
        assert {s.data.shape for s in st.z.addressable_shards} == {(8, 5)}
        print("row shards OK")
    """)
    assert "OK" in out


def test_data_mesh_names_the_platform():
    with pytest.raises(ValueError, match=r"visible cpu devices=\d+; on CPU "
                                         r"set XLA_FLAGS"):
        dfw.data_mesh(len(jax.devices()) + 1)


def test_device_peaks_are_keyed_by_kind():
    from repro.launch import mesh

    peaks = mesh.device_peaks("TPU v5 lite")
    assert (peaks.flops_bf16, peaks.hbm_bytes_per_s) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        mesh.device_peaks("cpu")


def test_compile_cache_is_fixed_or_left_to_the_environment(monkeypatch):
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = Path(__file__).resolve().parent.parent
    assert path == str(root / ".jax_cache")
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


# ---------------------------------------------------------------------------
# Kernel routing (single device; ops dispatch to the jnp ref off-TPU)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("taskcls", [tasks.MultiTaskLeastSquares,
                                     tasks.MultinomialLogistic])
def test_task_kernels_match_the_ref_route(taskcls):
    """The Pallas kernels (in the interpreter) against the task's f32
    maths, and dfw.verify_kernels on them."""
    n, d, m = 192, 24, 16
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n, d))
    task = taskcls(d=d, m=m, kernels="interpret")
    ref = dataclasses.replace(task, kernels="ref")
    if taskcls is tasks.MultinomialLogistic:
        y = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, m)
    else:
        y = jax.random.normal(jax.random.fold_in(key, 1), (n, m))
    state = task.init_state(x, y)
    v = jax.random.normal(jax.random.fold_in(key, 2), (m,))
    u = jax.random.normal(jax.random.fold_in(key, 3), (d,))
    np.testing.assert_allclose(np.asarray(task.matvec(state, v)),
                               np.asarray(ref.matvec(state, v)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(task.rmatvec(state, u)),
                               np.asarray(ref.rmatvec(state, u)),
                               rtol=1e-5, atol=1e-5)
    # the up-front driver check agrees too
    assert dfw.verify_kernels(task, state, jax.random.fold_in(key, 4)) < 1e-4


def test_mtls_products_chain_power_matvec_ref():
    """The least-squares products == an explicit chain through
    power_matvec/ref.py."""
    n, d, m = 128, 20, 12
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (n, d))
    y = jax.random.normal(jax.random.fold_in(key, 1), (n, m))
    task = tasks.MultiTaskLeastSquares(d=d, m=m)
    s = task.init_state(x, y)
    v = jax.random.normal(jax.random.fold_in(key, 2), (m,))
    want = pm_ref.rmatvec(s.x, pm_ref.matvec(s.r, v))
    np.testing.assert_allclose(np.asarray(task.matvec(s, v)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


class _DoubledMatvec(tasks.MultiTaskLeastSquares):
    """Least squares whose kernel route (any but "ref") doubles A v."""

    def matvec(self, st, v):
        out = super().matvec(st, v)
        return out if self.kernels == "ref" else 2.0 * out


class _DoubledRmatmat(tasks.MultiTaskLeastSquares):
    """Least squares whose kernel route doubles the block product A^T U
    alone."""

    def rmatmat(self, st, u):
        out = super().rmatmat(st, u)
        return out if self.kernels == "ref" else 2.0 * out


def _small_mtls_state(key):
    task = tasks.MultiTaskLeastSquares(d=8, m=6)
    return task.init_state(jax.random.normal(key, (32, 8)),
                           jax.random.normal(jax.random.fold_in(key, 1), (32, 6)))


def test_verify_kernels_catches_divergence():
    key = jax.random.PRNGKey(6)
    s = _small_mtls_state(key)
    with pytest.raises(AssertionError, match="diverge"):
        dfw.verify_kernels(_DoubledMatvec(d=8, m=6), s, key)


def test_verify_kernels_probes_the_block_route():
    """For a block solver (k > 1) the check runs matmat/rmatmat: a fault
    there alone passes the vector probes and trips the block ones."""
    key = jax.random.PRNGKey(8)
    s = _small_mtls_state(key)
    task = tasks.MultiTaskLeastSquares(d=8, m=6)
    assert dfw.verify_kernels(task, s, key, k=4) < 1e-4
    assert dfw.verify_kernels(_DoubledRmatmat(d=8, m=6), s, key) < 1e-4
    with pytest.raises(AssertionError, match="diverge"):
        dfw.verify_kernels(_DoubledRmatmat(d=8, m=6), s, key, k=4)


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(a.dtype)


@pytest.mark.parametrize("taskcls", [tasks.MultiTaskLeastSquares,
                                     tasks.MultinomialLogistic])
def test_verify_kernels_catches_bf16_operands(taskcls):
    """A kernel that rounds its operands to bf16 (what TPU's default matmul
    precision does to f32) must trip the check: its oracle runs at
    Precision.HIGHEST, so precision loss is not hidden on either side."""
    n, d, m = 256, 32, 24
    key = jax.random.PRNGKey(7)
    task = taskcls(d=d, m=m)
    y = (jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, m)
         if taskcls is tasks.MultinomialLogistic
         else jax.random.normal(jax.random.fold_in(key, 1), (n, m)))
    s = task.init_state(jax.random.normal(key, (n, d)), y)
    s = task.update(s, jax.random.normal(jax.random.fold_in(key, 2), (d,)),
                    jax.random.normal(jax.random.fold_in(key, 3), (m,)),
                    0.5, 1.0)  # a nonzero iterate: Z/R carry real values

    class Bf16Operands(taskcls):
        def matvec(self, st, v):
            if self.kernels != "ref":
                st = jax.tree.map(
                    lambda a: _bf16(a) if jnp.issubdtype(a.dtype, jnp.floating)
                    else a, st)
                v = _bf16(v)
            return super().matvec(st, v)

    assert dfw.verify_kernels(task, s, key) < 1e-4
    with pytest.raises(AssertionError, match="diverge"):
        dfw.verify_kernels(Bf16Operands(d=d, m=m), s, key)


def test_fit_serial_rejects_sample_prob():
    """Regression: fit_serial used to silently ignore sample_prob < 1 (and
    reweight), so a 'straggler mode' serial benchmark measured nothing. One
    worker has nobody to sample — reject loudly."""
    task = tasks.MultiTaskLeastSquares(d=8, m=6)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, 8))
    y = jax.random.normal(jax.random.fold_in(key, 1), (64, 6))
    cfg = dfw.DFWConfig(mu=1.0, num_epochs=2, sample_prob=0.5)
    with pytest.raises(ValueError, match="sample_prob"):
        dfw.fit_serial(task, x, y, cfg=cfg, key=key)
    # sample_prob=1.0 (the default) still runs
    ok = dfw.fit_serial(task, x, y,
                        cfg=dfw.DFWConfig(mu=1.0, num_epochs=2), key=key)
    assert ok.epochs_run == 2


def test_max_rank_underflow_rejected():
    """One factor is appended per epoch; an undersized iterate store would be
    silently corrupted by fw_update's clamped writes, so fit() rejects it."""
    task = tasks.MultiTaskLeastSquares(d=8, m=6)
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (64, 8))
    y = jax.random.normal(jax.random.fold_in(key, 1), (64, 6))
    cfg = dfw.DFWConfig(mu=1.0, num_epochs=5, max_rank=3)
    with pytest.raises(ValueError, match="max_rank"):
        dfw.fit(task, x, y, cfg=cfg, key=key, num_workers=1)


# ---------------------------------------------------------------------------
# Worker-sampling schedule units
# ---------------------------------------------------------------------------


def test_worker_schedule_always_keeps_one_alive():
    masks = dfw.worker_schedule(jax.random.PRNGKey(0), 200, 8, 0.05,
                                reweight=False)
    assert masks.shape == (200, 8)
    alive = np.asarray(jnp.sum(masks > 0, axis=1))
    assert alive.min() >= 1
    assert set(np.unique(masks)).issubset({0.0, 1.0})


def test_worker_schedule_reweight_is_unbiased():
    masks = dfw.worker_schedule(jax.random.PRNGKey(1), 100, 8, 0.5,
                                reweight=True)
    np.testing.assert_allclose(np.asarray(jnp.sum(masks, axis=1)),
                               np.full(100, 8.0), rtol=1e-5)
    alive = np.asarray(jnp.sum(masks > 0, axis=1))
    # with p=0.5 over 100 epochs we should see real variation
    assert alive.min() < 8


def test_worker_schedule_full_participation():
    masks = dfw.worker_schedule(jax.random.PRNGKey(2), 10, 4, 1.0)
    np.testing.assert_allclose(np.asarray(masks), np.ones((10, 4)))
