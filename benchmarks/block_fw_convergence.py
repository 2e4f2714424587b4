"""Linear-convergence solver tier: epochs-to-gap, block:k vs rank1.

The BlockFW tier's acceptance bar (and this suite's gated record): on the
paper's Table-1 problem sizes (d = m = 1024, low-rank ground truth) the
``block:k`` solver reaches a fixed duality-gap target in **>= 5x fewer
epochs** than the paper's rank-1 LMO — serial and 8-way sharded, on both
the MTLS regression task and matrix completion. The warm-start ablation
(``:cold`` re-randomizes the probe every epoch) rides along, isolating how
much of the win the carried probe buys.

Protocol per (task, worker-count) cell:

1. rank1, ``const:2`` + line search (the paper's strongest setting), run to
   an epoch budget; ``gap0`` is its first recorded duality gap and the
   target is ``frac * gap0``.
2. ``block:K:adapt`` (warm) and ``block:K:adapt:cold``, same mu/line
   search, early-stopped on ``gap_tol=target``.
3. ``epochs_to_gap`` = first history index with gap <= target, + 1.
   ``epochs_to_gap.speedup`` = rank1 / warm-block epochs — the gated
   metric (``benchmarks/baselines.json`` pins its floor >= 5x). A rank1
   run that never reaches the target within the budget counts the full
   budget — a conservative *floor* on the true speedup.

Subprocesses per cell (the device count locks at first jax init), the same
pattern as ``engine_bench.py`` / ``matrix_completion.py``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from .common import emit

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=__NDEV__"
import sys, json
sys.path.insert(0, "__SRC__")
import jax, jax.numpy as jnp
from repro.core import tasks
from repro.launch import dfw

NDEV = __NDEV__
TASK = "__TASK__"
d, m, rank, n, budget, frac, K = __D__, __M__, __RANK__, __N__, __BUDGET__, __FRAC__, __K__

key = jax.random.PRNGKey(0)
ku, kv, kx = jax.random.split(key, 3)
u = jnp.linalg.qr(jax.random.normal(ku, (d, rank)))[0]
v = jnp.linalg.qr(jax.random.normal(kv, (m, rank)))[0]
sv = jnp.linspace(1.0, 0.1, rank)
w_true = (u * (sv / jnp.sum(sv))) @ v.T  # ||W||_* = 1 (paper normalization)

if TASK == "mtls":
    task = tasks.MultiTaskLeastSquares(d=d, m=m)
    x = jax.random.normal(kx, (n, d))
    y = x @ w_true
    if NDEV > 1:
        data = (x, y)
else:
    task = tasks.MatrixCompletion(d=d, m=m)
    mask = jax.random.bernoulli(kx, __OBS__, (d, m))
    rows, cols = jnp.nonzero(mask)
    vals = w_true[rows, cols]
    if NDEV > 1:
        data = dfw.shard_observations(rows, cols, vals, NDEV, d, m=m)
    else:
        x, y = tasks.pack_observations(rows, cols, vals)


def run(solver, schedule, gap_tol=None):
    cfg = dfw.DFWConfig(mu=1.0, num_epochs=budget, schedule=schedule,
                        step_size="linesearch", solver=solver,
                        gap_tol=gap_tol, block_epochs=5,
                        verify_kernels=False)
    if NDEV == 1:
        return dfw.fit_serial(task, x, y, cfg=cfg, key=jax.random.PRNGKey(1))
    return dfw.fit(task, data[0], data[1], cfg=cfg,
                   key=jax.random.PRNGKey(1), num_workers=NDEV)


def epochs_to(history, target):
    for i, g in enumerate(history["gap"]):
        if g <= target:
            return i + 1
    return None


r1 = run("rank1", "const:2")
gap0 = r1.history["gap"][0]
target = frac * gap0
out = {"gap0": gap0, "target": target, "budget": budget}
out["rank1"] = {"epochs": epochs_to(r1.history, target),
                "gap_final": r1.history["gap"][-1]}
for label, solver in (("warm", f"block:{K}:adapt"),
                      ("cold", f"block:{K}:adapt:cold")):
    res = run(solver, "const:8", gap_tol=target)
    out[label] = {"epochs": epochs_to(res.history, target),
                  "epochs_run": res.epochs_run,
                  "gap_hist": list(res.history["gap"])}
print(json.dumps(out))
"""


def _cell(task, ndev, *, d, m, rank, n, obs, budget, frac, k, timeout):
    src = str(Path(__file__).resolve().parent.parent / "src")
    script = (
        _SCRIPT.replace("__NDEV__", str(ndev)).replace("__SRC__", src)
        .replace("__TASK__", task).replace("__D__", str(d))
        .replace("__M__", str(m)).replace("__RANK__", str(rank))
        .replace("__N__", str(n)).replace("__OBS__", str(obs))
        .replace("__BUDGET__", str(budget)).replace("__FRAC__", str(frac))
        .replace("__K__", str(k))
    )
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"  # fake-device child: never contend for a TPU
    label = "serial" if ndev == 1 else f"sharded{ndev}"
    name = f"blockfw.{task}.{label}"
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        emit(name, 0.0, f"FAILED:{proc.stderr[-200:]}")
        return
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    # rank1 missing the target inside the budget floors the speedup at
    # budget/warm_epochs (real speedup is larger) — never silently capped.
    r1_epochs = data["rank1"]["epochs"]
    r1_effective = r1_epochs if r1_epochs is not None else data["budget"]
    warm, cold = data["warm"]["epochs"], data["cold"]["epochs"]
    if warm is None:
        emit(name, 0.0, "FAILED:block solver never reached the gap target")
        return
    speedup = r1_effective / warm
    emit(
        name, 0.0,
        f"epochs_to_gap.speedup={speedup:.2f}x;"
        f"rank1_epochs={r1_epochs if r1_epochs is not None else 'budget'};"
        f"block_epochs={warm};k={k};gap0={data['gap0']:.4f};"
        f"target={data['target']:.4f}",
    )
    # Warm-start ablation: epochs-to-target ratio is coarse (both variants
    # can land in the same segment), so also compare the duality gap at the
    # last epoch both runs executed — warmth shows up as a smaller gap.
    cold_eff = cold if cold is not None else data["budget"]
    wh, ch = data["warm"]["gap_hist"], data["cold"]["gap_hist"]
    matched = min(len(wh), len(ch))
    gap_ratio = ch[matched - 1] / max(wh[matched - 1], 1e-12)
    emit(
        f"{name}.warm_vs_cold", 0.0,
        f"cold_over_warm_epochs={cold_eff / warm:.2f}x;"
        f"cold_over_warm_gap={gap_ratio:.2f}x;matched_epoch={matched};"
        f"warm_epochs={warm};"
        f"cold_epochs={cold if cold is not None else 'budget'}",
    )


def run(d=1024, m=1024, rank=32, n=2048, obs=0.05, budget=160, frac=0.1,
        k=32, timeout=1800):
    # Table-1 sizes are the point of this suite — `--fast` shrinks the
    # epoch budget/timeout upstream, never d/m (the gated record IS the
    # d=m=1024 cell).
    for task in ("mtls", "mc"):
        for ndev in (1, 8):
            _cell(task, ndev, d=d, m=m, rank=rank, n=n, obs=obs,
                  budget=budget, frac=frac, k=k, timeout=timeout)
