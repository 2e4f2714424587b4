"""Paper Figure 4 analogue: scaling with the number of workers.

Wall-clock on fake CPU devices is meaningless, so the CPU-bound analogue
reports the quantities that determine the real speedup curve: per-worker
FLOPs (compute shrinks ~1/N) and per-epoch collective bytes (communication
term grows ~log N on a tree / const per device on a ring), extracted from the
compiled HLO at N = 1, 2, 4, 8 workers. A modeled time-per-epoch combines
them with the v5e constants.
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
sys.path.insert(0, "SRC")
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import tasks, frank_wolfe, low_rank
from repro.analysis import hlo as hlo_analysis
from repro.compat import shard_map_compat

NDEVN = __NDEV__
n, d, m, K = 4096, 256, 128, 2
task = tasks.MultiTaskLeastSquares(d=d, m=m)
if NDEVN == 1:
    step = frank_wolfe.make_epoch_step(task, 1.0, K, step_size="linesearch")
    wrapped = step
else:
    mesh = jax.make_mesh((NDEVN,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    ss = tasks.MTLSState(x=P("data"), y=P("data"), r=P("data"))
    isp = low_rank.FactoredIterate(u=P(), s=P(), v=P(), alpha=P(), count=P())
    asp = frank_wolfe.EpochAux(P(), P(), P(), P(), P())
    csp = frank_wolfe.EpochCarry(state=ss, iterate=isp, comm_state=(),
                                 t=P(), key=P())
    step = frank_wolfe.make_epoch_step(task, 1.0, K, step_size="linesearch",
                                       axis_name="data")
    wrapped = shard_map_compat(step, mesh, in_specs=(csp,),
                               out_specs=(csp, asp))
x = jax.ShapeDtypeStruct((n, d), jnp.float32)
y = jax.ShapeDtypeStruct((n, m), jnp.float32)
st = tasks.MTLSState(x=x, y=y, r=y)
it = jax.eval_shape(lambda: low_rank.init(30, d, m))
carry = frank_wolfe.EpochCarry(
    state=st, iterate=it, comm_state=(),
    t=jax.ShapeDtypeStruct((), jnp.int32),
    key=jax.ShapeDtypeStruct((2,), jnp.uint32))
comp = jax.jit(wrapped).lower(carry).compile()
res = hlo_analysis.analyze(comp.as_text())
print(json.dumps({"flops": res["flops"], "coll": res["collective_bytes_total"]}))
"""


def run():
    src = str(Path(__file__).resolve().parent.parent / "src")
    cache = Path(__file__).resolve().parent.parent / "experiments" / "bench_cache"
    cache.mkdir(parents=True, exist_ok=True)
    base_flops = None
    for ndev in (1, 2, 4, 8):
        f = cache / f"scaling_{ndev}.json"
        if f.exists():
            data = json.loads(f.read_text())
        else:
            script = _SCRIPT.replace("__NDEV__", str(ndev)).replace("SRC", src)
            env = dict(os.environ)
            env.pop("XLA_FLAGS", None)
            env["JAX_PLATFORMS"] = "cpu"  # fake-device child: never contend for a TPU
            out = subprocess.run([sys.executable, "-c", script],
                                 capture_output=True, text=True, timeout=600, env=env)
            if out.returncode != 0:
                emit(f"fig4.workers{ndev}", 0.0, f"SKIPPED:{out.stderr[-200:]}")
                continue
            data = json.loads(out.stdout.strip().splitlines()[-1])
            f.write_text(json.dumps(data))
        if base_flops is None:
            base_flops = data["flops"]
        # modeled epoch time on v5e: compute + collective terms
        t_model = data["flops"] / 197e12 + data["coll"] / 50e9
        emit(f"fig4.workers{ndev}", 0.0,
             f"flops_per_worker={data['flops']:.3e};coll_bytes={data['coll']:.3e};"
             f"speedup_flops={base_flops/data['flops']:.2f}x;t_model_us={t_model*1e6:.1f}")
