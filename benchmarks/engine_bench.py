"""Epoch-engine dispatch overhead: scan-compiled segments vs legacy loop.

DFW-Trace epochs are O(d+m) cheap, so the driver's fixed costs — one jit
dispatch and four blocking scalar device->host pulls per epoch in the
pre-engine loop — dominate wall clock long before the algorithm does. This
bench pins the engine's win directly: the same fit run through

- ``engine="legacy"``: per-epoch dispatch + blocking ``float()`` pulls (the
  pre-engine driver, kept in ``core/engine.py`` as the baseline), and
- ``engine="scan"``: one ``lax.scan`` dispatch per K(t) segment, histories
  on device, host transfers at segment boundaries only,

reporting steady-state epochs/sec (compile excluded: segments share one
executable, so every timed block after the first is compile-free) and the
engine's own host-sync counter. Serial and 8-way sharded (the latter in a
subprocess: the device count locks at first jax init).

The acceptance bar this encodes: >= 5x epochs/sec for scan over legacy at
d = m = 256 on CPU.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

from .common import emit


def _steady_epochs_per_sec(run_fit):
    """Run a fit with a per-segment timing callback; return (epochs/sec over
    all blocks after the first, stats). The first block carries compilation
    and is dropped — later blocks reuse the same executable."""
    ts = []
    prev = [time.perf_counter()]

    def cb(start, aux):
        now = time.perf_counter()
        ts.append((now - prev[0], len(aux.loss)))
        prev[0] = now

    res = run_fit(cb)
    rest = ts[1:] if len(ts) > 1 else ts
    total_t = sum(t for t, _ in rest)
    total_e = sum(n for _, n in rest)
    return total_e / max(total_t, 1e-12), res.stats


def _serial(d, m, n, epochs, block):
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core import tasks
    from repro.launch import dfw

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n, d))
    w = jax.random.normal(jax.random.fold_in(key, 1), (d, m))
    y = x @ (w / jnp.linalg.norm(w, ord="nuc"))
    task = tasks.MultiTaskLeastSquares(d=d, m=m)
    cfg = dfw.DFWConfig(mu=1.0, num_epochs=epochs, schedule="const:2",
                        step_size="linesearch", verify_kernels=False,
                        block_epochs=block)
    out = {}
    for mode in ("legacy", "scan"):
        eps, stats = _steady_epochs_per_sec(
            lambda cb, mode=mode: dfw.fit_serial(
                task, x, y, cfg=dataclasses.replace(cfg, engine=mode),
                key=jax.random.PRNGKey(1), callback=cb)
        )
        out[mode] = {"eps": eps, "host_syncs": stats["host_syncs"],
                     "dispatches": stats["dispatches"]}
    return out


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, time, dataclasses
sys.path.insert(0, "__SRC__")
import jax, jax.numpy as jnp
from repro.core import tasks
from repro.launch import dfw

d, m, n, epochs, block = __D__, __M__, __N__, __EPOCHS__, __BLOCK__
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (n, d))
w = jax.random.normal(jax.random.fold_in(key, 1), (d, m))
y = x @ (w / jnp.linalg.norm(w, ord="nuc"))
task = tasks.MultiTaskLeastSquares(d=d, m=m)
cfg = dfw.DFWConfig(mu=1.0, num_epochs=epochs, schedule="const:2",
                    step_size="linesearch", verify_kernels=False,
                    block_epochs=block)
out = {}
for mode in ("legacy", "scan"):
    ts, prev = [], [time.perf_counter()]
    def cb(start, aux):
        now = time.perf_counter()
        ts.append((now - prev[0], len(aux.loss)))
        prev[0] = now
    res = dfw.fit(task, x, y, cfg=dataclasses.replace(cfg, engine=mode),
                  key=jax.random.PRNGKey(1), num_workers=8, callback=cb)
    rest = ts[1:] if len(ts) > 1 else ts
    out[mode] = {"eps": sum(n_ for _, n_ in rest) / max(sum(t for t, _ in rest), 1e-12),
                 "host_syncs": res.stats["host_syncs"],
                 "dispatches": res.stats["dispatches"]}
print(json.dumps(out))
"""


def _emit_pair(label, out, epochs):
    legacy, scan = out["legacy"], out["scan"]
    speedup = scan["eps"] / max(legacy["eps"], 1e-12)
    emit(f"engine.{label}.legacy", 1e6 / max(legacy["eps"], 1e-12),
         f"epochs_per_sec={legacy['eps']:.1f};host_syncs={legacy['host_syncs']};"
         f"dispatches={legacy['dispatches']};epochs={epochs}")
    emit(f"engine.{label}.scan", 1e6 / max(scan["eps"], 1e-12),
         f"epochs_per_sec={scan['eps']:.1f};host_syncs={scan['host_syncs']};"
         f"dispatches={scan['dispatches']};epochs={epochs}")
    emit(f"engine.{label}.speedup", 0.0,
         f"scan_vs_legacy={speedup:.2f}x")


def run(d=256, m=256, n=64, epochs=192, block=32):
    # n is deliberately thin: this bench isolates *driver* overhead (dispatch
    # + host syncs) at the acceptance sizes d = m = 256; per-epoch FLOPs
    # scale with n and would mask it. Compute-bound scaling lives in
    # dfw_scaling.py / matrix_completion.py.
    # serial (in-process: single device)
    out = _serial(d, m, n, epochs, block)
    _emit_pair("serial", out, epochs)

    # 8-way sharded (subprocess: fake CPU devices)
    src = str(Path(__file__).resolve().parent.parent / "src")
    script = (_SHARDED_SCRIPT.replace("__SRC__", src)
              .replace("__D__", str(d)).replace("__M__", str(m))
              .replace("__N__", str(max(n, 8))).replace("__EPOCHS__", str(epochs))
              .replace("__BLOCK__", str(block)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"  # fake-device child: never contend for a TPU
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=900, env=env)
    if proc.returncode != 0:
        emit("engine.sharded8", 0.0, f"SKIPPED:{proc.stderr[-200:]}")
        return
    _emit_pair("sharded8", json.loads(proc.stdout.strip().splitlines()[-1]),
               epochs)
