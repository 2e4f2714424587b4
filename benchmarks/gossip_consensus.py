"""Topology benchmark: gossip consensus quality + hier inter-group bytes.

Two measured layers, both emitted as CSV rows (and gated in
``baselines.json``):

1. **Inter-group HLO bytes** — compile one power-method vector exchange
   (d=256, 8 workers) under each topology and classify every collective's
   wire bytes against the 2-cell host partition ``[[0..3],[4..7]]`` with
   ``repro.analysis.hlo.partition_crossing_bytes`` (replica-group aware).
   ``flat`` sends everything across; ``hier:2`` keeps the exact psum inside
   the cells and only the reducer-encoded exchange crosses, so the
   ``hier:2 + int8`` composition is the headline: crossing bytes ~3.9x
   below flat/dense at identical sizes. The gated record is
   ``hier.inter_bytes`` (metric ``ratio`` = flat-dense crossing bytes over
   hier-int8 crossing bytes, floor in ``baselines.json``).

2. **Consensus error** — 8-way MTLS fits under ``flat``, ``ring`` (default
   auto-sized mixing rounds) and ``hier:2 + int8``, reporting each
   topology's final loss relative to the flat/dense master. Ring's drift is
   the PR's acceptance number (<= 1%); hier/dense is exact to standard
   tolerances and pinned bit-exact on integer grids in
   ``tests/test_topology.py``.

Subprocesses own all multi-device work (the parent locks the CPU device
count at first jax init); results are cached to a versioned JSON keyed by
the exact parameters, like ``benchmarks/comm_cost.py``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from .common import emit

_CACHE_VERSION = 1

_MEASURE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "SRC")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro import comm, compat
from repro.analysis import hlo as hlo_analysis

P_ = json.loads('PARAMS')
nw, d = P_["workers"], P_["d"]
mesh = Mesh(np.asarray(jax.devices()[:nw]), ("data",))
cells = P_["partition"]

def compile_exchange(topo):
    def body(x):
        est, _ = topo.all_reduce(x[0], (), slot="u",
                                 key=jax.random.PRNGKey(0), axis_name="data")
        return est[None]
    f = compat.shard_map_compat(body, mesh, P("data"), P("data"))
    arg = jax.ShapeDtypeStruct((nw, d), jnp.float32)
    return jax.jit(f).lower(arg).compile().as_text()

out = {}
for spec, cm in P_["modes"]:
    topo = comm.make_topology(spec, num_workers=nw, comm=cm)
    txt = compile_exchange(topo)
    res = hlo_analysis.analyze(txt)
    cross = hlo_analysis.partition_crossing_bytes(txt, cells)
    out[f"{spec}+{cm}"] = {
        "total": res["collective_bytes_total"],
        "crossing": cross["crossing"], "local": cross["local"],
        "counts": res["collective_count"],
    }
print(json.dumps(out))
"""

_CONSENSUS_SCRIPT = r"""
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
n, d, m = P["n"], P["d"], P["m"]
key = jax.random.PRNGKey(0)
kx, kw = jax.random.split(key)
W = jax.random.normal(kw, (d, m)); W = W / jnp.linalg.norm(W, ord="nuc")
X = jax.random.normal(kx, (n, d)); Y = X @ W
task = tasks.MultiTaskLeastSquares(d=d, m=m)
base = dfw.DFWConfig(mu=1.0, num_epochs=epochs, schedule="const:2",
                     step_size="linesearch")
out = {}
for spec, cm in P["modes"]:
    cfg = dataclasses.replace(base, topology=spec, comm=cm)
    res = dfw.fit(task, X, Y, cfg=cfg, key=jax.random.PRNGKey(1),
                  num_workers=nw)
    out[f"{spec}+{cm}"] = {"final_loss": res.final_loss,
                           "gap": float(res.history["gap"][-1]),
                           "epochs_run": res.epochs_run}
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


def _cached(section: str, params: dict, template: str) -> dict:
    cache = (Path(__file__).resolve().parent.parent
             / "experiments" / "bench_cache" / "gossip_consensus.json")
    blob = {}
    if cache.exists():
        try:
            blob = json.loads(cache.read_text())
        except json.JSONDecodeError:
            blob = {}
    if blob.get("version") != _CACHE_VERSION:
        blob = {"version": _CACHE_VERSION}
    entry = blob.get(section)
    if entry is not None and entry.get("params") == params:
        return entry["data"]
    data = _run_subprocess(template, params)
    blob[section] = {"params": params, "data": data}
    cache.parent.mkdir(parents=True, exist_ok=True)
    cache.write_text(json.dumps(blob))
    return data


MODES = [["flat", "dense"], ["hier:2", "dense"], ["hier:2", "int8"],
         ["ring", "dense"]]


def run(fast: bool = False):
    # --- inter-group bytes, one compiled (d,)-vector exchange per topology
    mparams = {"workers": 8, "d": 256, "partition": [[0, 1, 2, 3], [4, 5, 6, 7]],
               "modes": MODES}
    try:
        meas = _cached("measure", mparams, _MEASURE_SCRIPT)
        flat_cross = meas["flat+dense"]["crossing"]
        for spec_cm, rec in meas.items():
            emit(
                f"topology.bytes.{spec_cm.replace(':', '_')}", 0.0,
                f"crossing_bytes={rec['crossing']:.0f};"
                f"local_bytes={rec['local']:.0f};total={rec['total']:.0f};"
                f"counts={rec['counts']}",
            )
        ratio = flat_cross / meas["hier:2+int8"]["crossing"]
        emit("hier.inter_bytes", 0.0,
             f"ratio={ratio:.2f};flat_crossing={flat_cross:.0f};"
             f"hier_int8_crossing={meas['hier:2+int8']['crossing']:.0f}")
    except Exception as e:  # noqa: BLE001
        emit("hier.inter_bytes", 0.0, f"SKIPPED({type(e).__name__})")

    # --- consensus: 8-way MTLS final loss per topology vs the flat master
    cparams = {"workers": 8, "epochs": 8 if fast else 15,
               "n": 800 if fast else 1600, "d": 40, "m": 30, "modes": MODES}
    try:
        cons = _cached("consensus_fast" if fast else "consensus",
                       cparams, _CONSENSUS_SCRIPT)
    except Exception as e:  # noqa: BLE001
        emit("topology.consensus", 0.0, f"SKIPPED({type(e).__name__})")
        return
    flat_loss = cons["flat+dense"]["final_loss"]
    for spec_cm, rec in cons.items():
        rel = abs(rec["final_loss"] - flat_loss) / abs(flat_loss)
        emit(
            f"topology.consensus.{spec_cm.replace(':', '_')}", 0.0,
            f"final_loss={rec['final_loss']:.6f};rel_vs_flat={rel:.4f};"
            f"gap={rec['gap']:.4f};epochs={cparams['epochs']}",
        )
