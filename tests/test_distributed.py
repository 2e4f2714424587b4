"""Multi-device SPMD tests (8 fake CPU devices via a subprocess, since the
device count locks at first jax init in the main pytest process)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

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


def test_sharded_dfw_trace_equals_serial():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import tasks, frank_wolfe, low_rank

        n, d, m = 1600, 40, 30
        key = jax.random.PRNGKey(0)
        kx, kw = jax.random.split(key)
        W = jax.random.normal(kw, (d, m)); W = W / jnp.linalg.norm(W, ord="nuc")
        X = jax.random.normal(kx, (n, d)); Y = X @ W
        task = tasks.MultiTaskLeastSquares(d=d, m=m)

        serial = frank_wolfe.fit(task, task.init_state(X, Y), mu=1.0, num_epochs=8,
                                 key=jax.random.PRNGKey(1), schedule="const:2",
                                 step_size="linesearch")

        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        ss = tasks.MTLSState(x=P("data"), y=P("data"), r=P("data"))
        from repro.core import engine
        wrap = engine.shard_map_segment_wrapper(mesh, "data", ss)
        dist = frank_wolfe.fit(task, task.init_state(X, Y), mu=1.0, num_epochs=8,
                               key=jax.random.PRNGKey(1), schedule="const:2",
                               step_size="linesearch", axis_name="data",
                               segment_wrapper=wrap)
        np.testing.assert_allclose(serial.history["loss"], dist.history["loss"], rtol=1e-4)
        W1 = low_rank.materialize(serial.iterate); W2 = low_rank.materialize(dist.iterate)
        assert float(jnp.max(jnp.abs(W1 - W2))) < 1e-5
        print("DFW shard_map == serial OK")
    """)
    assert "OK" in out


def test_sharded_head_training_and_powersgd():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import dfw_head
        from repro.optim import compression

        # --- dfw_head.sharded_fit converges on separable features ---
        n, d, m = 2048, 32, 16
        key = jax.random.PRNGKey(0)
        W = jax.random.normal(key, (d, m))
        X = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
        y = jnp.argmax(X @ W, axis=1)
        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        res = dfw_head.sharded_fit(mesh, X, y, m, mu=8.0, num_epochs=25)
        assert res.history["loss"][-1] < 0.7 * res.history["loss"][0]
        err = dfw_head.top_k_error(res.iterate, X, y, k=5)
        assert err < 0.5, err
        print("sharded head fit OK", res.history["loss"][-1], err)

        # --- PowerSGD: the psum'd (distributed) compression must equal the
        # single-process compression of the MEAN gradient ---
        g_shards = jax.random.normal(jax.random.fold_in(key, 2), (8, 64, 48))
        params = {"w": jnp.zeros((64, 48))}
        st = compression.init(params, rank=8, min_size=16)
        def per_shard(g):
            synced, _ = compression.compress_and_sync({"w": g[0]}, st, min_size=16,
                                                      axis_name="data")
            return synced["w"][None]
        from repro.compat import shard_map_compat
        out_dist = shard_map_compat(per_shard, mesh,
                                    in_specs=(P("data", None, None),),
                                    out_specs=P("data", None, None))(g_shards)
        g_mean = jnp.mean(g_shards, axis=0)
        out_ser, _ = compression.compress_and_sync({"w": g_mean}, st, min_size=16)
        np.testing.assert_allclose(np.asarray(out_dist[0]), np.asarray(out_ser["w"]),
                                   rtol=1e-3, atol=1e-4)
        print("powersgd distributed == mean-gradient OK")
    """)
    assert "sharded head fit OK" in out


def test_seq_sharded_flash_decode():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.sharding import use_mesh
        from repro.models import layers
        from repro.kernels.flash_attention import ref

        mesh = jax.make_mesh((8, 1), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        b, hq, hkv, S, dh = 1, 4, 2, 128, 16
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (b, hq, 1, dh))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, hkv, S, dh))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, S, dh))
        pos = 100
        with use_mesh(mesh):
            got = layers.decode_attention_seq_sharded(
                q, k, v, scale=dh**-0.5, cache_pos=jnp.int32(pos), mesh=mesh)
        want = ref.attention(q, k[:, :, :pos], v[:, :, :pos], scale=dh**-0.5, causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
        print("seq-sharded flash decode OK")
    """)
    assert "OK" in out


@pytest.mark.slow  # ~2.5 min: 200-epoch convergence under worker dropout
def test_straggler_dropout_still_converges():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import tasks, frank_wolfe, low_rank
        from repro.compat import shard_map_compat

        n, d, m = 1600, 30, 20
        key = jax.random.PRNGKey(0)
        W = jax.random.normal(key, (d, m)); W = W / jnp.linalg.norm(W, ord="nuc")
        X = jax.random.normal(jax.random.fold_in(key, 1), (n, d)); Y = X @ W
        task = tasks.MultiTaskLeastSquares(d=d, m=m)
        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        ss = tasks.MTLSState(x=P("data"), y=P("data"), r=P("data"))
        isp = low_rank.FactoredIterate(u=P(), s=P(), v=P(), alpha=P(), count=P())
        asp = frank_wolfe.EpochAux(P(), P(), P(), P(), P())
        csp = frank_wolfe.EpochCarry(state=ss, iterate=isp, comm_state=(),
                                     t=P(), key=P())

        # one random straggler dropped per epoch (BSP timeout simulation),
        # driven through the unified-carry epoch contract directly
        ep = frank_wolfe.make_epoch_step(task, 1.0, 2,
            step_size="linesearch", axis_name="data")
        def step(carry, mask):
            return ep(carry, worker_weight=mask[0])
        wrap = jax.jit(shard_map_compat(step, mesh,
            in_specs=(csp, P("data")), out_specs=(csp, asp)))

        losses = []
        carry = frank_wolfe.init_carry(task.init_state(X, Y),
                                       low_rank.init(30, d, m),
                                       jax.random.PRNGKey(1))
        for t in range(30):
            drop = int(jax.random.randint(jax.random.fold_in(key, 100+t), (), 0, 8))
            mask = jnp.ones((8,)).at[drop].set(0.0)
            carry, aux = wrap(carry, mask)
            losses.append(float(aux.loss))
        assert int(carry.t) == 30
        assert losses[-1] < 0.15 * losses[0], losses[-1] / losses[0]
        print("straggler-robust convergence OK", losses[0], losses[-1])
    """)
    assert "OK" in out


def test_elastic_checkpoint_remesh():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointStore

        mesh8 = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        mesh2 = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        x = jnp.arange(64.0).reshape(8, 8)
        xs = jax.device_put(x, NamedSharding(mesh8, P("data", None)))
        with tempfile.TemporaryDirectory() as dd:
            st = CheckpointStore(dd)
            st.save(1, {"w": xs})
            # restore onto a DIFFERENT mesh/sharding (elastic re-shard)
            _, tree, _ = st.restore(like={"w": x},
                shardings={"w": NamedSharding(mesh2, P("model", "data"))})
            np.testing.assert_array_equal(np.asarray(tree["w"]), np.asarray(x))
            assert tree["w"].sharding.mesh.shape == {"data": 2, "model": 4}
        print("elastic remesh OK")
    """)
    assert "OK" in out


def test_moe_ep_shard_map_matches_local():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_config
        from repro.models import moe
        from repro.launch.sharding import use_mesh

        cfg = dataclasses.replace(get_config("arctic_480b", smoke=True),
                                  moe_capacity_factor=32.0)
        key = jax.random.PRNGKey(0)
        p = moe.init_moe(key, cfg, jnp.float32)
        x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, cfg.d_model))

        out_local, aux_local = moe.moe_block(p, x, cfg)

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with use_mesh(mesh):
            out_ep, aux_ep = jax.jit(lambda p, x: moe.moe_block(p, x, cfg))(p, x)
        np.testing.assert_allclose(np.asarray(out_local), np.asarray(out_ep),
                                   rtol=2e-3, atol=2e-3)
        print("MoE EP == local OK")
    """)
    assert "OK" in out
