"""Paper Table 1: communication cost per epoch — now with a comm= axis.

Three layers, all emitted as CSV rows:

1. the paper-size *analytic* table (naive-DFW vs SVA vs DFW-TRACE),
2. an HLO-*measured* bytes-per-epoch table for one sharded DFW-TRACE epoch on
   an 8-device mesh under each ``comm=`` reducer (dense / int8 / topk:r),
   cross-checked against the reducers' own analytic ``wire_bytes`` — the
   measured row carries the analytic expectation and the relative delta, so
   a regression in either the epoch's collective count or the HLO walker
   shows up as a nonzero delta,
3. a convergence-vs-bits sweep (``run_sweep``): 8-way MTLS and
   matrix-completion fits under each reducer, reporting final loss relative
   to dense next to the measured bytes ratio — the acceptance numbers
   (int8: <= 2% loss delta at >= 3x fewer bytes) come from here.

Subprocesses own all multi-device work (the parent pytest/bench process
locks the CPU device count at first jax init); results are cached to a
versioned JSON keyed by the exact parameters.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from .common import emit

F32 = 4
_CACHE_VERSION = 3  # bump when the measured quantities change meaning

COMM_MODES = ("dense", "int8", "topk:16")


def analytic(n_workers: int, d: int, m: int, k: int):
    return {
        "naive_dfw": n_workers * d * m * F32,
        "sva": n_workers * (d + m) * F32,
        "dfw_trace": 2 * n_workers * k * (d + m) * F32,
    }


def expect_epoch_bytes(comm: str, d: int, m: int, k: int, n_workers: int) -> int:
    """Analytic per-device wire bytes of one epoch (ring all-reduce 2x,
    all-gather 1x — the same conventions as repro.analysis.hlo): K vector
    exchanges of (d,) and (m,) through the reducer plus the four exact f32
    scalar psums (loss, <W,grad>, line-search numerator/denominator)."""
    from repro.comm import make_reducer

    r = make_reducer(comm, num_workers=n_workers)
    vectors = k * (r.wire_bytes(d, n_workers) + r.wire_bytes(m, n_workers))
    scalars = 4 * 2 * F32
    return vectors + scalars


_MEASURE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "SRC")
import jax, jax.numpy as jnp
from repro.core import tasks, low_rank, frank_wolfe
from repro.analysis import hlo as hlo_analysis
from repro.launch import dfw
from repro import comm as comm_lib

P = json.loads('PARAMS')
n, d, m, K, nw = P["n"], P["d"], P["m"], P["K"], P["workers"]
mesh = jax.make_mesh((nw,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
if P.get("task", "mtls") == "mc":
    # COO completion state: p observed-entry slots per epoch; the epoch's
    # collectives ((d,)/(m,) vector reduces + 4 scalars) match MTLS's.
    task = tasks.MatrixCompletion(d=d, m=m)
    ent = jax.ShapeDtypeStruct((n,), jnp.float32)
    idx = jax.ShapeDtypeStruct((n,), jnp.int32)
    st = tasks.MCState(rows=idx, cols=idx, vals=ent, resid=ent, weight=ent)
else:
    task = tasks.MultiTaskLeastSquares(d=d, m=m)
    x = jax.ShapeDtypeStruct((n, d), jnp.float32)
    y = jax.ShapeDtypeStruct((n, m), jnp.float32)
    st = tasks.MTLSState(x=x, y=y, r=y)
it = jax.eval_shape(lambda: low_rank.init(30, d, m))
mask = jax.ShapeDtypeStruct((nw,), jnp.float32)

out = {}
for cm in P["modes"]:
    cfg = dfw.DFWConfig(mu=1.0, num_epochs=1, schedule=f"const:{K}",
                        step_size="linesearch", comm=cm)
    red = comm_lib.make_reducer(cm, num_workers=nw)
    ep = dfw.make_sharded_epoch(task, cfg, mesh, K, state_example=st,
                                reducer=red)
    carry = frank_wolfe.EpochCarry(
        state=st, iterate=it,
        comm_state=jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((nw,) + l.shape, l.dtype),
            red.init_state(d, m)),
        t=jax.ShapeDtypeStruct((), jnp.int32),
        key=jax.ShapeDtypeStruct((2,), jnp.uint32))
    comp = jax.jit(ep).lower(carry, mask).compile()
    res = hlo_analysis.analyze(comp.as_text())
    out[cm] = {"collective_bytes": res["collective_bytes_total"],
               "counts": res["collective_count"]}
print(json.dumps(out))
"""


_SWEEP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "SRC")
import dataclasses
import jax, jax.numpy as jnp
from repro.core import tasks
from repro.launch import dfw

P = json.loads('PARAMS')
nw, epochs = P["workers"], P["epochs"]
out = {}

# --- 8-way MTLS ---
n, d, m = P["n"], P["d"], P["m"]
key = jax.random.PRNGKey(0)
kx, kw = jax.random.split(key)
W = jax.random.normal(kw, (d, m)); W = W / jnp.linalg.norm(W, ord="nuc")
X = jax.random.normal(kx, (n, d)); Y = X @ W
task = tasks.MultiTaskLeastSquares(d=d, m=m)
base = dfw.DFWConfig(mu=1.0, num_epochs=epochs, schedule="const:2",
                     step_size="linesearch")
out["mtls"] = {}
for cm in P["modes"]:
    cfg = dataclasses.replace(base, comm=cm)
    res = dfw.fit(task, X, Y, cfg=cfg, key=jax.random.PRNGKey(1),
                  num_workers=nw)
    out["mtls"][cm] = res.final_loss

# --- 8-way matrix completion ---
d2, m2, rank = P["mc_d"], P["mc_m"], 5
ku, kv, ko = jax.random.split(jax.random.PRNGKey(7), 3)
U = jnp.linalg.qr(jax.random.normal(ku, (d2, rank)))[0]
V = jnp.linalg.qr(jax.random.normal(kv, (m2, rank)))[0]
sv = jnp.linspace(1.0, 0.2, rank); sv = sv / jnp.sum(sv)
Wmc = (U * sv) @ V.T
mask = jax.random.bernoulli(ko, 0.35, (d2, m2))
rows, cols = jnp.nonzero(mask)
vals = Wmc[rows, cols]
mtask = tasks.MatrixCompletion(d=d2, m=m2)
mcfg = dfw.DFWConfig(mu=1.5, num_epochs=epochs, schedule="const:2",
                     step_size="linesearch")
idx, yw = dfw.shard_observations(rows, cols, vals, nw, d2, m=m2)
out["mc"] = {}
for cm in P["modes"]:
    cfg = dataclasses.replace(mcfg, comm=cm)
    res = dfw.fit(mtask, idx, yw, cfg=cfg, key=jax.random.PRNGKey(2),
                  num_workers=nw)
    out["mc"][cm] = res.final_loss
print(json.dumps(out))
"""


def _run_subprocess(template: str, params: dict) -> dict:
    src = str(Path(__file__).resolve().parent.parent / "src")
    script = template.replace("SRC", src).replace("PARAMS", json.dumps(params))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"  # fake-device child: never contend for a TPU
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=1200, env=env)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def _cached(cache: Path, section: str, params: dict, template: str) -> dict:
    """Per-section subprocess cache, invalidated by version + exact params."""
    blob = {}
    if cache.exists():
        try:
            blob = json.loads(cache.read_text())
        except json.JSONDecodeError:
            blob = {}
    if blob.get("version") != _CACHE_VERSION:
        # Drop the whole blob: re-stamping the version while keeping other
        # sections would let their stale data masquerade as current.
        blob = {"version": _CACHE_VERSION}
    entry = blob.get(section)
    if entry is not None and entry.get("params") == params:
        return entry["data"]
    data = _run_subprocess(template, params)
    blob[section] = {"params": params, "data": data}
    cache.parent.mkdir(parents=True, exist_ok=True)
    cache.write_text(json.dumps(blob))
    return data


def _cache_path() -> Path:
    return (Path(__file__).resolve().parent.parent
            / "experiments" / "bench_cache" / "comm_cost.json")


def run():
    # paper-size analytic table (d=m=1000, N=96 logical workers, K=2)
    a = analytic(96, 1000, 1000, 2)
    emit("table1.naive_dfw.bytes", 0.0, f"bytes={a['naive_dfw']:.3e}")
    emit("table1.sva.bytes", 0.0, f"bytes={a['sva']:.3e}")
    emit("table1.dfw_trace.bytes", 0.0,
         f"bytes={a['dfw_trace']:.3e};saving_vs_naive={a['naive_dfw']/a['dfw_trace']:.0f}x")

    # measured: one DFW-TRACE epoch on 8 devices per comm mode, HLO-counted
    # wire bytes, checked against the reducers' analytic expectation
    params = {"n": 1024, "d": 256, "m": 128, "K": 2, "workers": 8,
              "modes": list(COMM_MODES)}
    try:
        meas = _cached(_cache_path(), "measure", params, _MEASURE_SCRIPT)
        dense_bytes = meas["dense"]["collective_bytes"]
        for cm in params["modes"]:
            got = meas[cm]["collective_bytes"]
            expect = expect_epoch_bytes(
                cm, params["d"], params["m"], params["K"], params["workers"])
            delta = (got - expect) / expect
            emit(
                f"table1.measured_epoch.{cm}", 0.0,
                f"hlo_bytes={got:.3e};expect_bytes={expect:.3e};"
                f"rel_delta={delta:+.3f};ratio_vs_dense={dense_bytes / got:.2f}x;"
                f"counts={meas[cm]['counts']}",
            )
    except Exception as e:  # noqa: BLE001
        emit("table1.measured_epoch", 0.0, f"SKIPPED({type(e).__name__})")


def run_sweep(fast: bool = False):
    """Convergence-vs-bits: final loss under each reducer relative to dense,
    alongside the bytes ratio HLO-measured *at that bench's own sizes* (the
    PR's acceptance numbers). Pairing a loss with a ratio from a different
    problem size would invert the conclusion for top-k, whose saving depends
    on N*r vs dim."""
    params = {
        "workers": 8,
        "epochs": 8 if fast else 15,
        "n": 800 if fast else 1600,
        "d": 40, "m": 30, "mc_d": 64, "mc_m": 48,
        "modes": list(COMM_MODES),
    }
    # HLO measurement configs matching each sweep bench's epoch exactly.
    mparams = {
        "mtls": {"task": "mtls", "n": params["n"], "d": params["d"],
                 "m": params["m"], "K": 2, "workers": 8,
                 "modes": list(COMM_MODES)},
        "mc": {"task": "mc", "n": 2048, "d": params["mc_d"],
               "m": params["mc_m"], "K": 2, "workers": 8,
               "modes": list(COMM_MODES)},
    }
    try:
        sweep = _cached(_cache_path(), "sweep_fast" if fast else "sweep",
                        params, _SWEEP_SCRIPT)
        meas = {
            bench: _cached(_cache_path(), f"measure_{bench}", mp,
                           _MEASURE_SCRIPT)
            for bench, mp in mparams.items()
        }
    except Exception as e:  # noqa: BLE001
        emit("comm_sweep", 0.0, f"SKIPPED({type(e).__name__})")
        return
    for bench in ("mtls", "mc"):
        dense_loss = sweep[bench]["dense"]
        dense_bytes = meas[bench]["dense"]["collective_bytes"]
        for cm in params["modes"]:
            loss = sweep[bench][cm]
            rel = abs(loss - dense_loss) / abs(dense_loss)
            ratio = dense_bytes / meas[bench][cm]["collective_bytes"]
            emit(
                f"comm_sweep.{bench}.{cm.replace(':', '_')}", 0.0,
                f"final_loss={loss:.6f};rel_vs_dense={rel:.4f};"
                f"bytes_ratio={ratio:.2f}x;epochs={params['epochs']}",
            )
