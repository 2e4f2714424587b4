"""Pallas kernels vs pure-jnp oracles (interpret mode, shape/dtype sweeps)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    factor_matvec,
    flash_attention,
    mc_matvec,
    power_matvec,
    quantize,
    rank1_update,
)

KEY = jax.random.PRNGKey(0)


def _tol(dt):
    return dict(rtol=3e-2, atol=3e-2) if dt == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n,m", [(256, 256), (300, 200), (65, 33), (128, 512), (1, 7)])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_matvec_rmatvec(n, m, dt):
    a = (jax.random.normal(KEY, (n, m)) / np.sqrt(m)).astype(dt)
    v = jax.random.normal(jax.random.fold_in(KEY, 1), (m,)).astype(dt)
    u = jax.random.normal(jax.random.fold_in(KEY, 2), (n,)).astype(dt)
    got = power_matvec.matvec(a, v, block_r=64, block_c=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(power_matvec.ref.matvec(a, v)[:, 0], np.float32), **_tol(dt))
    got = power_matvec.rmatvec(a, u, block_r=64, block_c=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(power_matvec.ref.rmatvec(a, u)[:, 0], np.float32), **_tol(dt))


@pytest.mark.parametrize("k", [1, 3, 32])
@pytest.mark.parametrize("n,m", [(300, 200), (65, 33)])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_matvec_rmatvec_block(n, m, dt, k):
    """A (., k) probe block in one pass: each column is the vector kernel's
    product, and a 1-D probe still gives a 1-D result."""
    a = (jax.random.normal(KEY, (n, m)) / np.sqrt(m)).astype(dt)
    v = jax.random.normal(jax.random.fold_in(KEY, 1), (m, k)).astype(dt)
    u = jax.random.normal(jax.random.fold_in(KEY, 2), (n, k)).astype(dt)
    got = power_matvec.matvec(a, v, interpret=True)
    assert got.shape == (n, k)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(power_matvec.ref.matvec(a, v), np.float32), **_tol(dt))
    got = power_matvec.rmatvec(a, u, interpret=True)
    assert got.shape == (m, k)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(power_matvec.ref.rmatvec(a, u), np.float32), **_tol(dt))
    assert power_matvec.matvec(a, v[:, 0], interpret=True).shape == (n,)
    assert power_matvec.rmatvec(a, u[:, 0], interpret=True).shape == (m,)


def test_power_iter_step_matches_ref():
    n, d, m = 300, 40, 28
    x = jax.random.normal(KEY, (n, d)) / np.sqrt(d)
    r = jax.random.normal(jax.random.fold_in(KEY, 3), (n, m))
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (m,))
    v = v / jnp.linalg.norm(v)
    u1, v1 = power_matvec.power_iter_step(x, r, v, interpret=True)
    u2, v2 = power_matvec.ref.power_iter_step(x, r, v.reshape(-1, 1))
    np.testing.assert_allclose(u1, u2[:, 0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(v1, v2[:, 0], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("d,m,p", [(64, 48, 1000), (37, 23, 700), (128, 5, 64),
                                   (9, 130, 1)])
def test_mc_coo_matvec(d, m, p):
    """Observed-entry (COO) matvec kernel vs the segment_sum oracle, including
    duplicate coordinates and non-block-multiple entry counts."""
    rows = jax.random.randint(KEY, (p,), 0, d)
    cols = jax.random.randint(jax.random.fold_in(KEY, 20), (p,), 0, m)
    vals = jax.random.normal(jax.random.fold_in(KEY, 21), (p,))
    v = jax.random.normal(jax.random.fold_in(KEY, 22), (m,))
    u = jax.random.normal(jax.random.fold_in(KEY, 23), (d,))
    got = mc_matvec.matvec(rows, cols, vals, v, d, block_e=128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(mc_matvec.ref.matvec(rows, cols, vals, v, d)),
        rtol=1e-5, atol=1e-5)
    got = mc_matvec.rmatvec(rows, cols, vals, u, m, block_e=128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(mc_matvec.ref.rmatvec(rows, cols, vals, u, m)),
        rtol=1e-5, atol=1e-5)


def test_mc_coo_matvec_matches_dense():
    """The segment_sum reference itself equals the dense P_Omega gradient."""
    d, m, p = 40, 30, 500
    rows = jax.random.randint(KEY, (p,), 0, d)
    cols = jax.random.randint(jax.random.fold_in(KEY, 24), (p,), 0, m)
    vals = jax.random.normal(jax.random.fold_in(KEY, 25), (p,))
    v = jax.random.normal(jax.random.fold_in(KEY, 26), (m,))
    g = np.zeros((d, m), np.float32)
    np.add.at(g, (np.asarray(rows), np.asarray(cols)), np.asarray(vals))
    np.testing.assert_allclose(
        np.asarray(mc_matvec.ref.matvec(rows, cols, vals, v, d)),
        g @ np.asarray(v), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bt,n_in,r,n_out", [
    (128, 256, 8, 256),   # block-aligned
    (130, 300, 7, 65),    # every axis off its block/sublane multiple
    (1, 7, 1, 3),         # single tiny request
    (33, 129, 12, 257),   # one past block boundaries
])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_factor_matvec_kernel(bt, n_in, r, n_out, dt):
    """Fused factor-scoring kernel (interpret) vs the jnp oracle vs the
    materialized dense product, across non-multiple-of-block shapes."""
    x = (jax.random.normal(KEY, (bt, n_in)) / np.sqrt(n_in)).astype(dt)
    a = jax.random.normal(jax.random.fold_in(KEY, 40), (r, n_in)).astype(dt)
    s = jax.random.normal(jax.random.fold_in(KEY, 41), (r,))
    b = jax.random.normal(jax.random.fold_in(KEY, 42), (r, n_out)).astype(dt)
    got = factor_matvec.factor_matvec(
        x, a, s, b, alpha=0.7, block_b=32, block_o=64, interpret=True)
    assert got.shape == (bt, n_out) and got.dtype == jnp.float32
    want_ref = factor_matvec.ref.factor_matvec(x, a, 0.7 * s, b)
    want_dense = factor_matvec.ref.dense_matvec(x, a, 0.7 * s, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_ref), **_tol(dt))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_dense),
                               **(_tol(dt) if dt == jnp.bfloat16
                                  else dict(rtol=1e-4, atol=1e-4)))


def test_factor_matvec_rank_zero_and_dispatch():
    """Rank 0 (untrained iterate) scores exactly zero without entering the
    kernel, and the off-TPU default path (use_pallas=None on CPU) agrees
    with the interpret-mode kernel."""
    x = jax.random.normal(KEY, (9, 50))
    z = factor_matvec.factor_matvec(
        x, jnp.zeros((0, 50)), jnp.zeros((0,)), jnp.zeros((0, 30)), interpret=True)
    assert z.shape == (9, 30) and not np.any(np.asarray(z))
    a = jax.random.normal(jax.random.fold_in(KEY, 43), (5, 50))
    s = jax.random.normal(jax.random.fold_in(KEY, 44), (5,))
    b = jax.random.normal(jax.random.fold_in(KEY, 45), (5, 30))
    via_ref = factor_matvec.factor_matvec(x, a, s, b, alpha=1.3)
    via_kernel = factor_matvec.factor_matvec(
        x, a, s, b, alpha=1.3, block_b=32, block_o=32, interpret=True)
    np.testing.assert_allclose(np.asarray(via_ref), np.asarray(via_kernel),
                               rtol=1e-5, atol=1e-5)


def test_factor_matvec_zero_tail_rows_are_exact_noops():
    """The low_rank invariant the serving engine relies on: capacity rows
    with s == 0 change nothing, so bucket padding is free."""
    x = jax.random.normal(KEY, (6, 40))
    a = jax.random.normal(jax.random.fold_in(KEY, 46), (3, 40))
    s = jax.random.normal(jax.random.fold_in(KEY, 47), (3,))
    b = jax.random.normal(jax.random.fold_in(KEY, 48), (3, 20))
    def pad(t, rows):
        return jnp.concatenate([t, jnp.zeros((rows,) + t.shape[1:])])
    live = factor_matvec.factor_matvec(x, a, s, b, interpret=True,
                                       block_b=32, block_o=32)
    padded = factor_matvec.factor_matvec(
        x, pad(a, 13), pad(s, 13), pad(b, 13), interpret=True,
        block_b=32, block_o=32)
    np.testing.assert_array_equal(np.asarray(live), np.asarray(padded))


@pytest.mark.parametrize("n,m", [(128, 128), (100, 90), (33, 257)])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_rank1_update(n, m, dt):
    z = jax.random.normal(KEY, (n, m)).astype(dt)
    y0 = jax.random.normal(jax.random.fold_in(KEY, 5), (n, m)).astype(dt)
    xv = jax.random.normal(jax.random.fold_in(KEY, 6), (n,)).astype(dt)
    yv = jax.random.normal(jax.random.fold_in(KEY, 7), (m,)).astype(dt)
    got = rank1_update.rank1_update(z, xv, yv, 0.7, -0.3,
                                    block_r=64, block_c=64, interpret=True)
    want = rank1_update.ref.rank1_update(z, xv, yv, 0.7, -0.3)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dt))
    got = rank1_update.rank1_update_axpy(z, y0, xv, yv, 0.7, -0.3, -0.5,
                                         block_r=64, block_c=64, interpret=True)
    want = rank1_update.ref.rank1_update_axpy(z, y0, xv, yv, 0.7, -0.3, -0.5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dt))


@pytest.mark.parametrize("n,budget,block_n", [(512, 15, 128), (130, 127, 64),
                                              (31, 3, 32)])
def test_quantize_dequantize_kernel(n, budget, block_n):
    """Fused stochastic-round quantize + dequantize vs the jnp oracle.

    Exact equality: noise is an explicit operand, so kernel and ref compute
    the identical floor (see kernels/quantize/kernel.py)."""
    x = jax.random.normal(KEY, (n,)) * 3.0
    noise = jax.random.uniform(jax.random.fold_in(KEY, 30), (n,))
    scale = jnp.max(jnp.abs(x))
    q = quantize.ops.quantize(x, noise, scale, budget=budget,
                              block_n=block_n, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(q), np.asarray(quantize.ref.quantize(x, noise, scale, budget)))
    assert q.dtype == jnp.int8
    assert int(jnp.max(jnp.abs(q.astype(jnp.int32)))) <= budget
    deq = quantize.ops.dequantize(q, scale, budget=budget,
                                  block_n=block_n, interpret=True)
    np.testing.assert_allclose(
        np.asarray(deq), np.asarray(quantize.ref.dequantize(q, scale, budget)),
        rtol=1e-6, atol=1e-6)
    # the roundtrip lands within one grid step of the input
    assert float(jnp.max(jnp.abs(deq - x))) <= float(scale) / budget * (1 + 1e-6)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
def test_flash_attention_gqa(causal, hq, hkv):
    b, sq, skv, dh = 2, 96, 96, 32
    q = jax.random.normal(KEY, (b, hq, sq, dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 8), (b, hkv, skv, dh))
    v = jax.random.normal(jax.random.fold_in(KEY, 9), (b, hkv, skv, dh))
    got = flash_attention.flash_attention(
        q, k, v, scale=dh**-0.5, causal=causal, block_q=32, block_k=32, interpret=True)
    want = flash_attention.ref.attention(q, k, v, scale=dh**-0.5, causal=causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_ragged_padding():
    """Non-multiple seq lens exercise the kv_len mask path."""
    b, hq, hkv, sq, skv, dh = 1, 2, 2, 50, 70, 16
    q = jax.random.normal(KEY, (b, hq, sq, dh))
    k = jax.random.normal(jax.random.fold_in(KEY, 10), (b, hkv, skv, dh))
    v = jax.random.normal(jax.random.fold_in(KEY, 11), (b, hkv, skv, dh))
    got = flash_attention.flash_attention(
        q, k, v, scale=dh**-0.5, causal=False, block_q=32, block_k=32, interpret=True)
    want = flash_attention.ref.attention(q, k, v, scale=dh**-0.5, causal=False)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    b, hq, hkv, s, dh = 2, 4, 2, 64, 32
    q = jax.random.normal(KEY, (b, hq, s, dh)).astype(jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(KEY, 12), (b, hkv, s, dh)).astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(KEY, 13), (b, hkv, s, dh)).astype(jnp.bfloat16)
    got = flash_attention.flash_attention(
        q, k, v, scale=dh**-0.5, causal=True, block_q=32, block_k=32, interpret=True)
    want = flash_attention.ref.attention(q, k, v, scale=dh**-0.5, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("q,dk,dv", [(32, 16, 16), (64, 64, 64), (16, 32, 64)])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_wkv6_chunk_kernel(q, dk, dv, dt):
    """WKV6 chunk kernel vs the exact sequential recurrence."""
    from repro.kernels import wkv6_chunk

    bh = 3
    ks = jax.random.split(KEY, 6)
    r = (jax.random.normal(ks[0], (bh, q, dk)) * 0.5).astype(dt)
    k = (jax.random.normal(ks[1], (bh, q, dk)) * 0.5).astype(dt)
    v = jax.random.normal(ks[2], (bh, q, dv)).astype(dt)
    logw = (-jnp.exp(jax.random.normal(ks[3], (bh, q, dk)) * 0.3 - 1.0)).astype(dt)
    u = (jax.random.normal(ks[4], (bh, dk)) * 0.2).astype(dt)
    s0 = (jax.random.normal(ks[5], (bh, dk, dv)) * 0.3).astype(jnp.float32)

    y_ref, s_ref = wkv6_chunk.ref.wkv6_chunk_batched(r, k, v, logw, u, s0)
    y_k, s_k = wkv6_chunk.kernel.wkv6_chunk(r, k, v, logw, u, s0, interpret=True)
    tol = dict(rtol=5e-2, atol=5e-2) if dt == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), **tol)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref), **tol)


def test_wkv6_chunk_matches_model_time_mix_step():
    """The kernel computes the same chunk transition the rwkv6 model uses."""
    from repro.configs import get_config
    from repro.kernels import wkv6_chunk
    from repro.models import rwkv6

    cfg = get_config("rwkv6_7b", smoke=True)
    b, s, d = 1, 32, cfg.d_model
    h = d // rwkv6.HEAD
    p = rwkv6.init_rwkv(jax.random.PRNGKey(3), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (b, s, d)) * 0.5
    x_prev = jnp.zeros((b, d))
    s0 = jnp.zeros((b, h, rwkv6.HEAD, rwkv6.HEAD))
    y_model, s_model, _ = rwkv6.time_mix(p, x, cfg, x_prev, s0)

    # recompute the same projections and feed the kernel chunk-by-chunk
    xs = rwkv6._token_shift(x, x_prev)
    r = (rwkv6._mix(x, xs, p["mu_r"]) @ p["wr"]).reshape(b, s, h, rwkv6.HEAD)
    k = (rwkv6._mix(x, xs, p["mu_k"]) @ p["wk"]).reshape(b, s, h, rwkv6.HEAD)
    v = (rwkv6._mix(x, xs, p["mu_v"]) @ p["wv"]).reshape(b, s, h, rwkv6.HEAD)
    wx = rwkv6._mix(x, xs, p["mu_w"])
    logw = (
        -jnp.exp(p["w_base"] + jnp.tanh(wx @ p["w_lora_a"]) @ p["w_lora_b"])
    ).reshape(b, s, h, rwkv6.HEAD)
    q = cfg.ssm_chunk
    state = jnp.zeros((b * h, rwkv6.HEAD, rwkv6.HEAD))
    u = jnp.broadcast_to(p["u_bonus"], (b, h, rwkv6.HEAD)).reshape(b * h, rwkv6.HEAD)
    for c0 in range(0, s, q):
        args = [t[:, c0 : c0 + q].transpose(0, 2, 1, 3).reshape(b * h, q, rwkv6.HEAD)
                for t in (r, k, v, logw)]
        _, state = wkv6_chunk.kernel.wkv6_chunk(*args, u, state, interpret=True)
    np.testing.assert_allclose(
        np.asarray(state.reshape(b, h, rwkv6.HEAD, rwkv6.HEAD)),
        np.asarray(s_model), rtol=1e-3, atol=1e-3)
