"""Matrix-completion convergence + worker scaling on the real execution layer.

The paper's third synthetic task (§5.1): recover a rank-r matrix from sparse
observed entries. Two sweeps, mirroring ``dfw_scaling.py``:

1. Worker scaling — the identical completion program serial and 2/4/8-way
   row-block-sharded (fake CPU devices in subprocesses), reporting the median
   epoch time plus the serial/sharded final-loss drift as a correctness check.
   The padding overhead of equalizing entry shards is also reported — it is
   the price of static shapes under shard_map.

2. Schedule sweep — final train loss (of the *returned* iterate, via
   ``final_loss`` — history[-1] is one epoch stale) and held-out RMSE after a
   fixed epoch budget for the paper's K(t) families.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

from .common import emit

_SCALE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=__NDEV__"
import sys, json, time
sys.path.insert(0, "__SRC__")
import jax, jax.numpy as jnp
from repro.core import tasks
from repro.launch import dfw

NDEV = __NDEV__
d, m, rank, obs, epochs = __D__, __M__, 8, __OBS__, __EPOCHS__
key = jax.random.PRNGKey(0)
ku, kv, ko = jax.random.split(key, 3)
u = jnp.linalg.qr(jax.random.normal(ku, (d, rank)))[0]
v = jnp.linalg.qr(jax.random.normal(kv, (m, rank)))[0]
sv = jnp.linspace(1.0, 0.2, rank)
w_true = (u * (sv / jnp.sum(sv))) @ v.T
mask = jax.random.bernoulli(ko, obs, (d, m))
rows, cols = jnp.nonzero(mask)
vals = w_true[rows, cols]

task = tasks.MatrixCompletion(d=d, m=m)
cfg = dfw.DFWConfig(mu=1.0, num_epochs=epochs, schedule="const:2",
                    step_size="linesearch", verify_kernels=False,
                    block_epochs=max(1, epochs // 4))

ts, prev = [], [time.perf_counter()]
def cb(start, aux):  # per-segment: aux is an EpochAux of (block,) np arrays
    now = time.perf_counter()
    ts.append((now - prev[0]) / len(aux.loss))
    prev[0] = now

if NDEV == 1:
    idx, yw = tasks.pack_observations(rows, cols, vals)
    res = dfw.fit_serial(task, idx, yw, cfg=cfg, key=jax.random.PRNGKey(1),
                         callback=cb)
    pad = 0.0
else:
    idx, yw = dfw.shard_observations(rows, cols, vals, NDEV, d, m=m)
    pad = idx.shape[0] / rows.size - 1.0
    res = dfw.fit(task, idx, yw, cfg=cfg, key=jax.random.PRNGKey(1),
                  num_workers=NDEV, callback=cb)
ts.sort()
print(json.dumps({"us_per_epoch": ts[len(ts) // 2] * 1e6,
                  "final_loss": res.final_loss, "pad_frac": pad}))
"""


def _worker_scaling(d, m, obs, epochs):
    src = str(Path(__file__).resolve().parent.parent / "src")
    serial_loss = None
    for ndev in (1, 2, 4, 8):
        script = (
            _SCALE_SCRIPT.replace("__NDEV__", str(ndev))
            .replace("__SRC__", src)
            .replace("__D__", str(d))
            .replace("__M__", str(m))
            .replace("__OBS__", str(obs))
            .replace("__EPOCHS__", str(epochs))
        )
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"  # fake-device child: never contend for a TPU
        out = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True, timeout=900, env=env)
        if out.returncode != 0:
            emit(f"matrix_completion.workers{ndev}", 0.0,
                 f"SKIPPED:{out.stderr[-200:]}")
            continue
        data = json.loads(out.stdout.strip().splitlines()[-1])
        if ndev == 1:
            serial_loss = data["final_loss"]
        if serial_loss is None:
            drift = "n/a"
        else:
            drift = "{:.2e}".format(
                abs(data["final_loss"] - serial_loss) / (abs(serial_loss) + 1e-12)
            )
        emit(f"matrix_completion.workers{ndev}", data["us_per_epoch"],
             f"final_loss={data['final_loss']:.6f};serial_drift={drift};"
             f"pad_frac={data['pad_frac']:.3f}")


def _schedule_sweep(d, m, obs, epochs):
    import jax
    import jax.numpy as jnp

    from repro.core import low_rank, tasks
    from repro.launch import dfw

    key = jax.random.PRNGKey(0)
    ku, kv, ko, ks = jax.random.split(key, 4)
    rank = 8
    u = jnp.linalg.qr(jax.random.normal(ku, (d, rank)))[0]
    v = jnp.linalg.qr(jax.random.normal(kv, (m, rank)))[0]
    sv = jnp.linspace(1.0, 0.2, rank)
    w_true = (u * (sv / jnp.sum(sv))) @ v.T
    mask = jax.random.bernoulli(ko, obs, (d, m))
    rows, cols = jnp.nonzero(mask)
    vals = w_true[rows, cols]
    holdout = jax.random.bernoulli(ks, 0.1, rows.shape)
    tr, ho = jnp.nonzero(~holdout)[0], jnp.nonzero(holdout)[0]
    idx, yw = tasks.pack_observations(rows[tr], cols[tr], vals[tr])

    task = tasks.MatrixCompletion(d=d, m=m)
    for sched in ("const:1", "const:2", "log", "linear:0.2"):
        cfg = dfw.DFWConfig(mu=1.0, num_epochs=epochs, schedule=sched,
                            step_size="linesearch", verify_kernels=False,
                            block_epochs=max(1, epochs // 4))
        ts, prev = [], [time.perf_counter()]

        def cb(start, aux):  # per-segment callback (engine contract)
            now = time.perf_counter()
            ts.append((now - prev[0]) / len(aux.loss))
            prev[0] = now

        res = dfw.fit_serial(task, idx, yw, cfg=cfg, key=jax.random.PRNGKey(1),
                             callback=cb)
        ts.sort()
        pred = low_rank.gather_entries(res.iterate, rows[ho], cols[ho])
        rmse = float(jnp.sqrt(jnp.mean((pred - vals[ho]) ** 2)))
        emit(f"matrix_completion.sched[{sched}]", ts[len(ts) // 2] * 1e6,
             f"final_loss={res.final_loss:.6f};holdout_rmse={rmse:.6f};"
             f"gap_final={res.history['gap'][-1]:.5f};"
             f"k_total={sum(res.history['k'])}")


def run(d=384, m=256, obs=0.2, epochs=20):
    _worker_scaling(d, m, obs, epochs)
    _schedule_sweep(d, m, obs, epochs)
