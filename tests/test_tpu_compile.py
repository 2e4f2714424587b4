"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached, at the widths the chip runs use (``chip_smoke.py``).

Nothing runs: each test lowers and compiles through the kernel's ``ops``
layer with ``use_pallas=True`` and asserts that the compiled module holds
the kernel (``tpu_custom_call``). What the chip's compiler refuses (slices
off the tiling, too much fast memory, a program too large for the device)
fails here at no chip time. The topology is described inside a module
fixture, so workers that never run this file never load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.hlo import tpu_kernel_calls
from repro.comm import Int8Reducer
from repro.core import frank_wolfe, low_rank, tasks
from repro.kernels.factor_matvec import ops as fm_ops
from repro.kernels.mc_matvec import ops as mc_ops
from repro.kernels.power_matvec import ops as pm_ops
from repro.kernels.quantize import ops as q_ops
from repro.launch.dfw import kernelize

HEAD_ROWS = 327_680  # one chip's share of 1.28M ImageNet rows over 4 chips
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; returns its HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(sharding, shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("cols", [2048, 1000])
@pytest.mark.parametrize("op", ["matvec", "rmatvec"])
def test_power_matvec_compiles_at_head_width(one_chip, op, cols):
    a = _sds(one_chip, (HEAD_ROWS, cols))
    if op == "matvec":
        vec = _sds(one_chip, (cols,))
        fn = lambda a, v: pm_ops.matvec(a, v, use_pallas=True)  # noqa: E731
    else:
        vec = _sds(one_chip, (HEAD_ROWS,))
        fn = lambda a, u: pm_ops.rmatvec(a, u, use_pallas=True)  # noqa: E731
    assert tpu_kernel_calls(_compile(fn, a, vec)) >= 1


@pytest.mark.parametrize("op", ["matvec", "rmatvec"])
def test_power_matvec_compiles_k_wide_at_fig1_width(one_chip, op):
    """The block:32 LMO's k-wide products over the Fig. 1 least-squares
    data (100,000 x 1000): one kernel call, no batch grid over columns."""
    a = _sds(one_chip, (100_000, 1000))
    if op == "matvec":
        fn = lambda a, v: pm_ops.matvec(a, v, use_pallas=True)  # noqa: E731
        blk = _sds(one_chip, (1000, 32))
    else:
        fn = lambda a, u: pm_ops.rmatvec(a, u, use_pallas=True)  # noqa: E731
        blk = _sds(one_chip, (100_000, 32))
    assert tpu_kernel_calls(_compile(fn, a, blk)) == 1


@pytest.mark.parametrize("op", ["matvec", "rmatvec"])
def test_mc_matvec_compiles_at_completion_width(one_chip, op):
    d = m = 1024
    p = d * m // 10  # 10% of entries observed
    idx = [_sds(one_chip, (p,), I32), _sds(one_chip, (p,), I32)]
    vals = _sds(one_chip, (p,))
    if op == "matvec":
        fn = lambda r, c, x, v: mc_ops.matvec(r, c, x, v, d, use_pallas=True)  # noqa: E731
        vec = _sds(one_chip, (m,))
    else:
        fn = lambda r, c, x, u: mc_ops.rmatvec(r, c, x, u, m, use_pallas=True)  # noqa: E731
        vec = _sds(one_chip, (d,))
    assert tpu_kernel_calls(_compile(fn, *idx, vals, vec)) >= 1


def test_factor_matvec_compiles_at_serving_width(one_chip):
    r, d, m, batch = 128, 1024, 1024, 64
    args = (_sds(one_chip, (batch, d)), _sds(one_chip, (r, d)),
            _sds(one_chip, (r,)), _sds(one_chip, (r, m)))
    fn = lambda x, a, s, b: fm_ops.factor_matvec(x, a, s, b, use_pallas=True)  # noqa: E731
    assert tpu_kernel_calls(_compile(fn, *args)) >= 1


@pytest.mark.parametrize("op", ["quantize", "dequantize"])
def test_quantize_compiles(one_chip, op):
    n, budget = 2048, 127
    scale = _sds(one_chip, ())
    if op == "quantize":
        fn = lambda x, nz, s: q_ops.quantize(x, nz, s, budget=budget, use_pallas=True)  # noqa: E731
        args = (_sds(one_chip, (n,)), _sds(one_chip, (n,)), scale)
    else:
        fn = lambda q, s: q_ops.dequantize(q, s, budget=budget, use_pallas=True)  # noqa: E731
        args = (_sds(one_chip, (n,), jnp.int8), scale)
    assert tpu_kernel_calls(_compile(fn, *args)) >= 1


def test_block32_mtls_epoch_compiles_at_table1_width(one_chip):
    """One block:32 epoch (block power iterations with Cholesky-QR, int8
    exchange, line search) at Table-1 width: the whole epoch program lowers
    for v5e with its matvecs and the quantize pair as kernels."""
    n, d, m, k, epochs = 65_536, 1024, 1024, 32, 10
    task = tasks.MultiTaskLeastSquares(d=d, m=m)
    sspec = frank_wolfe.parse_solver(f"block:{k}")
    epoch = frank_wolfe.make_epoch_step(
        kernelize(task, use_pallas=True), 1.0, 2, step_size="linesearch",
        solver=sspec, reducer=Int8Reducer(num_workers=1, use_pallas=True),
    )
    carry = jax.eval_shape(lambda: frank_wolfe.init_carry(
        task.init_state(jnp.zeros((n, d)), jnp.zeros((n, m))),
        low_rank.init(epochs * k, d, m), jax.random.PRNGKey(0),
        probe=frank_wolfe.init_probe(sspec, m),
    ))
    carry = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), carry)
    # power_matvec (matvec + rmatvec of X and R) and quantize/dequantize
    assert tpu_kernel_calls(_compile(epoch, carry)) >= 4
