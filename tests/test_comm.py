"""Compressed power-method collectives (repro/comm + kernels/quantize).

In-process units cover the reducer math (bit-exact dense plumbing, int8
unbiasedness, top-k error feedback) on one device; the 8-worker tolerance and
wire-bytes checks run in subprocesses with fake CPU devices, matching the
idiom of tests/test_dfw_launch.py.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm
from repro.core import power_method, tasks
from repro.core.power_method import sphere_vector
from repro.kernels.quantize import ops as qops
from repro.kernels.quantize import ref as qref
from repro.launch import dfw

SRC = str(Path(__file__).resolve().parent.parent / "src")
KEY = jax.random.PRNGKey(0)


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


# ---------------------------------------------------------------------------
# Factory / spec parsing
# ---------------------------------------------------------------------------


def test_make_reducer_parses_all_specs():
    assert isinstance(comm.make_reducer("dense"), comm.DenseReducer)
    r8 = comm.make_reducer("int8", num_workers=8)
    assert isinstance(r8, comm.Int8Reducer) and r8.budget == 15
    rk = comm.make_reducer("topk:32")
    assert isinstance(rk, comm.TopKReducer) and rk.k == 32
    assert rk.spec == "topk:32" and r8.spec == "int8"


def test_make_reducer_rejects_bad_specs():
    with pytest.raises(ValueError, match="unknown comm spec"):
        comm.make_reducer("float16")
    with pytest.raises(ValueError, match="k must be"):
        comm.make_reducer("topk:0")
    with pytest.raises(ValueError, match="1..127"):
        comm.make_reducer("int8", num_workers=256)


def test_dfw_config_rejects_bad_comm_spec():
    task = tasks.MultiTaskLeastSquares(d=8, m=6)
    x = jax.random.normal(KEY, (64, 8))
    y = jax.random.normal(jax.random.fold_in(KEY, 1), (64, 6))
    cfg = dfw.DFWConfig(mu=1.0, num_epochs=2, comm="nope")
    with pytest.raises(ValueError, match="unknown comm spec"):
        dfw.fit(task, x, y, cfg=cfg, key=KEY, num_workers=1)


# ---------------------------------------------------------------------------
# Dense reducer: the plumbing itself must be bit-exact
# ---------------------------------------------------------------------------


def test_dense_reducer_bit_exact_vs_uninjected():
    a = jax.random.normal(KEY, (40, 30))
    v0 = sphere_vector(jax.random.fold_in(KEY, 1), 30)
    plain = power_method.power_iterations(
        lambda v: a @ v, lambda u: a.T @ u, v0, 8
    )
    routed, cs = power_method.power_iterations(
        lambda v: a @ v, lambda u: a.T @ u, v0, 8, reducer=comm.DenseReducer()
    )
    assert cs == ()
    for got, want in zip(routed, plain):
        assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# int8: stochastic rounding is unbiased; roundtrip error is one grid step
# ---------------------------------------------------------------------------


def test_int8_stochastic_rounding_unbiased():
    """E[dequant(quant(x))] = x: the empirical mean over independent noise
    draws converges at the CLT rate; assert within 6 standard errors."""
    r = comm.Int8Reducer(num_workers=8)  # budget 15: the coarse, real regime
    x = jax.random.normal(KEY, (64,)) * jnp.linspace(0.01, 3.0, 64)
    trials = 4000

    def one(k):
        y, _ = r.exchange(x, (), slot="u", key=k, axis_name=None)
        return y

    ys = jax.vmap(one)(jax.random.split(jax.random.fold_in(KEY, 2), trials))
    mean = np.asarray(jnp.mean(ys, axis=0))
    step = float(jnp.max(jnp.abs(x))) / r.budget  # quantization grid step
    stderr = 0.5 * step / np.sqrt(trials)  # SR noise std <= step/2
    np.testing.assert_array_less(np.abs(mean - np.asarray(x)), 5.0 * stderr)


def test_int8_roundtrip_error_bounded_by_grid_step():
    r = comm.Int8Reducer(num_workers=4)
    x = jax.random.normal(KEY, (257,))
    y, _ = r.exchange(x, (), slot="v", key=jax.random.fold_in(KEY, 3), axis_name=None)
    step = float(jnp.max(jnp.abs(x))) / r.budget
    assert float(jnp.max(jnp.abs(y - x))) <= step * (1 + 1e-6)


def test_int8_zero_vector_is_fixed_point():
    r = comm.Int8Reducer(num_workers=8)
    y, _ = r.exchange(jnp.zeros((32,)), (), slot="u", key=KEY, axis_name=None)
    np.testing.assert_array_equal(np.asarray(y), np.zeros(32, np.float32))


def test_verify_quantize_kernels_passes_and_catches():
    err = comm.verify_quantize_kernels(KEY, num_workers=8)
    assert err <= 1e-6
    with pytest.raises(AssertionError, match="diverges"):
        comm.verify_quantize_kernels(KEY, num_workers=8, tol=-1.0)


def test_verify_quantize_kernels_catches_one_integer_off(monkeypatch):
    """A quantize kernel that moves a single integer by one (a different
    rounding of the same value) trips the check."""
    from repro.comm import int8

    honest = int8.qops.quantize

    def one_off(*args, **kw):
        q = honest(*args, **kw)
        return q.at[3].set(jnp.where(q[3] < 0, q[3] + 1, q[3] - 1))

    monkeypatch.setattr(int8.qops, "quantize", one_off)
    with pytest.raises(AssertionError, match="diverges"):
        comm.verify_quantize_kernels(KEY, num_workers=8)


# ---------------------------------------------------------------------------
# Quantize kernel trio: interpret-mode Pallas vs jnp ref (exact: same noise)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,budget", [(256, 15), (300, 127), (7, 1)])
def test_quantize_kernel_matches_ref(n, budget):
    x = jax.random.normal(KEY, (n,)) * 2.0
    noise = jax.random.uniform(jax.random.fold_in(KEY, 4), (n,))
    scale = jnp.max(jnp.abs(x))
    got = qops.quantize(x, noise, scale, budget=budget, block_n=64, interpret=True)
    want = qref.quantize(x, noise, scale, budget)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == jnp.int8
    assert int(jnp.max(jnp.abs(got.astype(jnp.int32)))) <= budget
    deq = qops.dequantize(got, scale, budget=budget, block_n=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(deq), np.asarray(qref.dequantize(want, scale, budget)),
        rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# top-k: error feedback keeps the transmitted signal honest
# ---------------------------------------------------------------------------


def test_topk_exact_when_k_covers_dim():
    r = comm.TopKReducer(k=64)
    st = r.init_state(16, 12)
    x = jax.random.normal(KEY, (16,))
    y, st = r.exchange(x, st, slot="u", key=KEY, axis_name=None)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-6)
    assert float(jnp.linalg.norm(st["u"])) == 0.0


def test_topk_error_feedback_residual_decays():
    """EF identity: sum_t y_t = T x + e_0 - e_T, so for a constant input the
    residual stays bounded by the unsent mass and the running-mean error
    decays as O(1/T) — the property that makes sparsification safe."""
    r = comm.TopKReducer(k=8)
    d = 32
    st = {"u": jnp.zeros((d,)), "v": jnp.zeros((2,))}
    x = jax.random.normal(KEY, (d,))
    x_norm = float(jnp.linalg.norm(x))
    ys, enorms = [], []
    for t in range(64):
        y, st = r.exchange(x, st, slot="u", key=jax.random.fold_in(KEY, t),
                           axis_name=None)
        ys.append(np.asarray(y))
        enorms.append(float(jnp.linalg.norm(st["u"])))
    # residual stays under the EF plateau: with contraction factor
    # delta = k/d, ||e_{t+1}|| <= sqrt(1-delta) (||x|| + ||e_t||), whose
    # fixed point is sqrt(1-delta) / (1 - sqrt(1-delta)) * ||x||.
    c = np.sqrt(1.0 - r.k / d)
    assert max(enorms) <= c / (1.0 - c) * x_norm * (1 + 1e-5)
    # running-mean deviation decays ~1/T (sum_t y_t = T x - e_T exactly)
    err_10 = np.linalg.norm(np.mean(ys[:10], axis=0) - np.asarray(x))
    err_64 = np.linalg.norm(np.mean(ys, axis=0) - np.asarray(x))
    assert err_64 < err_10 / 2.0
    np.testing.assert_allclose(err_64, enorms[-1] / 64, rtol=1e-4)


def test_topk_masked_worker_sends_nothing_and_freezes_residual():
    """Straggler interaction: a sampled-out worker (weight 0) has x = 0 but a
    nonzero residual; it must neither leak top-k(e) into the aggregate nor
    update e — otherwise the driver's unbiased reweighting breaks."""
    r = comm.TopKReducer(k=4)
    e0 = jax.random.normal(KEY, (16,))
    st = {"u": e0, "v": jnp.zeros((2,))}
    y, st2 = r.exchange(jnp.zeros((16,)), st, slot="u",
                        key=jax.random.fold_in(KEY, 1), axis_name=None,
                        weight=jnp.float32(0.0))
    np.testing.assert_array_equal(np.asarray(y), np.zeros(16, np.float32))
    np.testing.assert_array_equal(np.asarray(st2["u"]), np.asarray(e0))
    # a live worker (any weight > 0, incl. fractional reweights) still sends
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (16,))
    y_w, _ = r.exchange(x, st, slot="u", key=jax.random.fold_in(KEY, 3),
                        axis_name=None, weight=jnp.float32(8.0 / 5.0))
    y_n, _ = r.exchange(x, st, slot="u", key=jax.random.fold_in(KEY, 3),
                        axis_name=None, weight=None)
    np.testing.assert_array_equal(np.asarray(y_w), np.asarray(y_n))


def test_topk_state_threads_through_power_iterations():
    a = jax.random.normal(KEY, (24, 18))
    v0 = sphere_vector(jax.random.fold_in(KEY, 1), 18)
    r = comm.TopKReducer(k=6)
    res, cs = power_method.power_iterations(
        lambda v: a @ v, lambda u: a.T @ u, v0, 4, reducer=r,
        key=jax.random.fold_in(KEY, 2),
    )
    assert set(cs) == {"u", "v"}
    assert cs["u"].shape == (24,) and cs["v"].shape == (18,)
    assert float(jnp.linalg.norm(cs["u"])) > 0.0  # k=6 < 24: mass withheld
    # threading the state back in continues, not restarts
    res2, cs2 = power_method.power_iterations(
        lambda v: a @ v, lambda u: a.T @ u, res.v, 4, reducer=r, comm_state=cs,
        key=jax.random.fold_in(KEY, 3),
    )
    assert res2.sigma > 0.0


# ---------------------------------------------------------------------------
# 8-worker sharded runs: every reducer tracks the serial dense trajectory
# ---------------------------------------------------------------------------

_SETUP = """
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import tasks
        from repro.launch import dfw

        n, d, m = 1600, 40, 30
        key = jax.random.PRNGKey(0)
        kx, kw = jax.random.split(key)
        W = jax.random.normal(kw, (d, m)); W = W / jnp.linalg.norm(W, ord="nuc")
        X = jax.random.normal(kx, (n, d)); Y = X @ W
        task = tasks.MultiTaskLeastSquares(d=d, m=m)
        base = dfw.DFWConfig(mu=1.0, num_epochs=6, schedule="const:2",
                             step_size="linesearch")
        ser = dfw.fit_serial(task, X, Y, cfg=base, key=jax.random.PRNGKey(1))
"""


def test_sharded_reducers_track_serial_dense():
    """8-worker runs under each reducer stay within tolerance of the serial
    dense trajectory; comm='dense' reproduces it to psum rounding exactly as
    the un-knobbed driver does."""
    out = _run(_SETUP + """
        tol = {"dense": 1e-4, "int8": 0.02, "topk:16": 0.35}
        for cm, rtol in tol.items():
            cfg = dataclasses.replace(base, comm=cm)
            dist = dfw.fit(task, X, Y, cfg=cfg, key=jax.random.PRNGKey(1),
                           num_workers=8)
            np.testing.assert_allclose(ser.history["loss"], dist.history["loss"],
                                       rtol=rtol)
            rel = abs(dist.final_loss - ser.final_loss) / ser.final_loss
            assert rel < rtol, (cm, rel)
            print(cm, "rel", rel)
        print("sharded reducers OK")
    """)
    assert "sharded reducers OK" in out


def test_sharded_dense_reducer_bit_exact_vs_legacy_epoch():
    """The unified-carry reducer plumbing must be lossless: one epoch built
    with the default DenseReducer yields floats identical to a hand-inlined
    raw-psum epoch (the pre-engine construction, kept here as the oracle)."""
    out = _run(_SETUP + """
        from jax.sharding import PartitionSpec as P
        from repro.compat import shard_map_compat
        from repro.core import frank_wolfe, low_rank, power_method
        from repro.core.trace_norm import duality_gap

        mesh = dfw.data_mesh(8)
        xs, ys = dfw.shard_rowwise(mesh, (X, Y))
        state = task.init_state(xs, ys)
        it = low_rank.init(base.num_epochs, d, m)
        k = jax.random.PRNGKey(3)
        mask = jnp.ones((8,), jnp.float32)

        # hand-inlined raw-psum epoch: exactly the legacy un-injected math
        def oracle(state, it, kk, mask):
            w = mask[0]
            v0 = power_method.sphere_vector(
                jax.random.fold_in(kk, jnp.int32(0)), m)
            res = power_method.power_iterations(
                lambda v: task.matvec(state, v),
                lambda u: task.rmatvec(state, u),
                v0, 2, axis_name="data", worker_weight=w)
            loss = jax.lax.psum(w * task.local_loss(state), "data")
            inner = jax.lax.psum(w * task.inner_w_grad(state), "data")
            gap = duality_gap(inner, res.sigma, 1.0)
            numer, denom = task.linesearch_terms(state, res.u, res.v, 1.0)
            numer = jax.lax.psum(w * numer, "data")
            denom = jax.lax.psum(w * denom, "data")
            gamma = jnp.clip(numer / jnp.maximum(denom, 1e-30), 0.0, 1.0)
            state = task.update(state, res.u, res.v, gamma, 1.0)
            it = low_rank.fw_update(it, res.u, res.v, gamma, 1.0)
            return state, it, frank_wolfe.EpochAux(
                loss, gap, res.sigma, gamma, jnp.full((), 2, jnp.float32))

        ss = jax.tree.map(lambda _: P("data"), state)
        isp = low_rank.FactoredIterate(u=P(), s=P(), v=P(), alpha=P(), count=P())
        asp = frank_wolfe.EpochAux(P(), P(), P(), P(), P())
        wrapped = shard_map_compat(oracle, mesh,
            in_specs=(ss, isp, P(), P("data")), out_specs=(ss, isp, asp))
        s1, it1, aux1 = jax.jit(wrapped)(state, it, k, mask)

        routed = dfw.make_sharded_epoch(task, base, mesh, 2,
                                        state_example=state)
        carry = frank_wolfe.init_carry(state, it, k)
        carry2, aux2 = jax.jit(routed)(carry, mask)
        assert carry2.comm_state == ()
        assert int(carry2.t) == 1
        for a, b in zip(jax.tree.leaves((s1, it1, aux1)),
                        jax.tree.leaves((carry2.state, carry2.iterate, aux2))):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        print("dense reducer sharded bit-exact OK")
    """)
    assert "OK" in out


@pytest.mark.slow  # subprocess + multi-epoch sweep: the acceptance-bar check
def test_int8_within_2pct_and_3x_fewer_bytes():
    """The PR acceptance bar, as a test: 8-way MTLS and matrix-completion
    runs under comm='int8' reach within 2% of dense final loss while the
    HLO-measured collective bytes per epoch drop >= 3x."""
    out = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.analysis import contracts
        from repro.core import tasks, low_rank, frank_wolfe
        from repro.launch import dfw
        from repro import comm as comm_lib

        # --- convergence: MTLS ---
        n, d, m = 1600, 40, 30
        key = jax.random.PRNGKey(0)
        kx, kw = jax.random.split(key)
        W = jax.random.normal(kw, (d, m)); W = W / jnp.linalg.norm(W, ord="nuc")
        X = jax.random.normal(kx, (n, d)); Y = X @ W
        task = tasks.MultiTaskLeastSquares(d=d, m=m)
        base = dfw.DFWConfig(mu=1.0, num_epochs=15, schedule="const:2",
                             step_size="linesearch")
        dense = dfw.fit(task, X, Y, cfg=base, key=jax.random.PRNGKey(1),
                        num_workers=8)
        int8 = dfw.fit(task, X, Y,
                       cfg=dataclasses.replace(base, comm="int8"),
                       key=jax.random.PRNGKey(1), num_workers=8)
        rel = abs(int8.final_loss - dense.final_loss) / dense.final_loss
        assert rel < 0.02, ("mtls", rel, int8.final_loss, dense.final_loss)
        print("mtls int8 rel", rel)

        # --- convergence: matrix completion ---
        d2, m2, rank = 64, 48, 5
        ku, kv, ko = jax.random.split(jax.random.PRNGKey(7), 3)
        U = jnp.linalg.qr(jax.random.normal(ku, (d2, rank)))[0]
        V = jnp.linalg.qr(jax.random.normal(kv, (m2, rank)))[0]
        sv = jnp.linspace(1.0, 0.2, rank); sv = sv / jnp.sum(sv)
        Wmc = (U * sv) @ V.T
        mask = jax.random.bernoulli(ko, 0.35, (d2, m2))
        rows, cols = jnp.nonzero(mask)
        vals = Wmc[rows, cols]
        mtask = tasks.MatrixCompletion(d=d2, m=m2)
        mcfg = dfw.DFWConfig(mu=1.5, num_epochs=15, schedule="const:2",
                             step_size="linesearch")
        idx8, yw8 = dfw.shard_observations(rows, cols, vals, 8, d2, m=m2)
        mdense = dfw.fit(mtask, idx8, yw8, cfg=mcfg,
                         key=jax.random.PRNGKey(2), num_workers=8)
        mint8 = dfw.fit(mtask, idx8, yw8,
                        cfg=dataclasses.replace(mcfg, comm="int8"),
                        key=jax.random.PRNGKey(2), num_workers=8)
        mrel = abs(mint8.final_loss - mdense.final_loss) / mdense.final_loss
        assert mrel < 0.02, ("mc", mrel)
        print("mc int8 rel", mrel)

        # --- wire bytes: HLO-measured epoch collectives, dense vs int8,
        # at the SAME sizes the convergence runs above used ---
        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        K = 2
        x = jax.ShapeDtypeStruct((n, d), jnp.float32)
        y = jax.ShapeDtypeStruct((n, m), jnp.float32)
        st = tasks.MTLSState(x=x, y=y, r=y)
        it = jax.eval_shape(lambda: low_rank.init(30, d, m))
        msk = jax.ShapeDtypeStruct((8,), jnp.float32)
        bytes_by = {}
        for cm in ("dense", "int8"):
            cfg = dataclasses.replace(base, comm=cm)
            red = comm_lib.make_reducer(cm, num_workers=8)
            ep = dfw.make_sharded_epoch(task, cfg, mesh, K,
                                        state_example=st, reducer=red)
            carry = frank_wolfe.EpochCarry(
                state=st, iterate=it,
                comm_state=jax.tree.map(
                    lambda l: jax.ShapeDtypeStruct((8,) + l.shape, l.dtype),
                    red.init_state(d, m)),
                t=jax.ShapeDtypeStruct((), jnp.int32),
                key=jax.ShapeDtypeStruct((2,), jnp.uint32))
            bytes_by[cm] = contracts.measure(
                ep, carry, msk)["collective_bytes_total"]
        ratio = bytes_by["dense"] / bytes_by["int8"]
        assert ratio >= 3.0, bytes_by
        print("bytes ratio", ratio)
        print("acceptance OK")
    """, timeout=900)
    assert "acceptance OK" in out
