"""The paper's ImageNet pipeline on the LM zoo: frozen backbone features ->
trace-norm-constrained classifier head via the DISTRIBUTED power method.

This script runs the real multi-worker code path on 8 simulated devices
(the same shard_map program the 256-chip dry-run lowers): features and labels
are sharded across workers; each FW epoch exchanges only the O(d+m)
power-iteration vectors (2*K psums), never a d x m gradient.

Run:  PYTHONPATH=src python examples/distributed_head_training.py
(sets XLA_FLAGS itself — run as a standalone script)
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import dfw_head  # noqa: E402
from repro.models import lm  # noqa: E402

# --- 1. frozen backbone features (stand-in for the paper's ResNet50) -------
cfg = get_config("qwen2_1_5b", smoke=True)
params = lm.init_params(cfg, jax.random.PRNGKey(0))
batches = []
for i in range(4):
    key = jax.random.PRNGKey(10 + i)
    toks = jax.random.randint(key, (4, 64), 0, cfg.vocab_size)
    batches.append({"tokens": toks, "labels": toks})
x, _ = dfw_head.extract_features(params, batches, cfg)
print(f"extracted features: {x.shape} from {cfg.name}")

# --- 2. planted 1000-class-style problem (low-rank class structure) --------
m = 64
key = jax.random.PRNGKey(3)
w_star = jax.random.normal(key, (x.shape[1], 10)) @ jax.random.normal(
    jax.random.fold_in(key, 1), (10, m)
)
y = jnp.argmax(x @ w_star, axis=1)

# --- 3. distributed DFW-TRACE over 8 workers -------------------------------
mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
res = dfw_head.sharded_fit(mesh, x, y, m, mu=20.0, num_epochs=40,
                           schedule="const:2")
err5 = dfw_head.top_k_error(res.iterate, x, y, k=5)
print(f"final objective {res.history['loss'][-1]:.2f} "
      f"(epoch 0: {res.history['loss'][0]:.2f}), top-5 err {err5:.3f}, "
      f"head rank <= {int(res.iterate.count)}")

d, v = x.shape[1], m
per_epoch_vectors = 2 * 2 * (d + v) * 4  # 2 power iters x (u,v) x f32
print(f"per-epoch wire traffic per worker: {per_epoch_vectors/1e3:.1f} KB "
      f"(naive gradient sync would be {d*v*4/1e3:.1f} KB)")
assert res.history["loss"][-1] < res.history["loss"][0]
