"""Training driver: data -> jitted train_step -> async checkpoints -> restart.

Structured for the 1000+-node regime:
  * restart-safe: the data stream is a pure function of the step counter, the
    checkpoint manifest carries step + RNG, so kill -9 at any point resumes
    bit-identically.
  * elastic: `--mesh` may differ between runs; restore re-shards (ZeRO-style
    resharding handled by CheckpointStore.restore(shardings=...)).
  * async checkpointing: the train loop never blocks on disk.

On this CPU container it drives smoke-scale configs end-to-end (see
examples/train_e2e.py for the ~100M-param run).
"""
from __future__ import annotations

import argparse
import time
from typing import Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointStore
from repro.configs import get_config
from repro.data import SyntheticLMStream
from repro.models import lm
from repro.models.config import ModelConfig, ShapeSpec
from repro.optim import adamw

from .compile_cache import enable_compile_cache
from .mesh import make_mesh
from .params import param_pspecs
from .sharding import use_mesh
from .steps import batch_pspecs, make_train_step


def build(
    cfg: ModelConfig,
    shape: ShapeSpec,
    mesh=None,
    *,
    peak_lr: float = 3e-4,
    seed: int = 0,
):
    """Returns (init_fn, step_fn, shardings) under the (optional) mesh."""
    with use_mesh(mesh):
        step = make_train_step(cfg, peak_lr=peak_lr)
        if mesh is None:
            return (
                lambda: (lm.init_params(cfg, jax.random.PRNGKey(seed)),),
                jax.jit(step),
                None,
            )
        aparams = jax.eval_shape(lambda k: lm.init_params(cfg, k), jax.random.PRNGKey(0))
        pspecs = param_pspecs(aparams)
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                           is_leaf=lambda x: isinstance(x, P))
        bsh = {k: NamedSharding(mesh, s) for k, s in batch_pspecs(cfg, shape).items()}
        init = jax.jit(
            lambda k: lm.init_params(cfg, k), out_shardings=psh
        )
        jstep = jax.jit(step, donate_argnums=(0, 1))
        return (lambda: (init(jax.random.PRNGKey(seed)),), jstep, {"params": psh, "batch": bsh})


def train(
    *,
    arch: str,
    steps: int,
    smoke: bool = True,
    seq_len: int = 128,
    global_batch: int = 8,
    mesh_shape: Optional[Tuple[int, int]] = None,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 50,
    peak_lr: float = 3e-4,
    log_every: int = 10,
    resume: bool = True,
):
    cfg = get_config(arch, smoke=smoke)
    shape = ShapeSpec("train_custom", "train", seq_len, global_batch)
    mesh = make_mesh(mesh_shape, ("data", "model")) if mesh_shape else None
    init_fn, step_fn, shardings = build(cfg, shape, mesh, peak_lr=peak_lr)
    stream = SyntheticLMStream(cfg, shape)
    store = CheckpointStore(ckpt_dir) if ckpt_dir else None

    start = 0
    with use_mesh(mesh):
        if store is not None and resume and store.latest_step() is not None:
            aparams = jax.eval_shape(lambda k: lm.init_params(cfg, k), jax.random.PRNGKey(0))
            like = {"params": aparams, "opt": jax.eval_shape(adamw.init, aparams)}
            sh = None
            if shardings is not None:
                sh = {"params": shardings["params"],
                      "opt": adamw.AdamWState(
                          step=NamedSharding(mesh, P()),
                          m=shardings["params"], v=shardings["params"])}
            start, state, extra = store.restore(like=like, shardings=sh)
            params, opt = state["params"], state["opt"]
            print(f"[train] resumed from step {start}")
        else:
            (params,) = init_fn()
            opt = adamw.init(params)

        history = []
        t0 = time.time()
        for step in range(start, steps):
            batch = stream.batch_for_step(step)
            if shardings is not None:
                batch = {k: jax.device_put(v, shardings["batch"][k]) for k, v in batch.items()}
            params, opt, metrics = step_fn(params, opt, batch)
            if (step + 1) % log_every == 0 or step == start:
                loss = float(metrics["loss"])
                print(f"[train] step={step+1:5d} loss={loss:.4f} "
                      f"({(time.time()-t0)/max(step-start+1,1)*1e3:.0f} ms/step)")
                history.append((step + 1, loss))
            if store is not None and (step + 1) % ckpt_every == 0:
                store.save_async(step + 1, {"params": params, "opt": opt})
        if store is not None:
            store.save(steps, {"params": params, "opt": opt})
    return params, opt, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--full", action="store_true", help="full (non-smoke) config")
    ap.add_argument("--mesh", default=None, help="e.g. 2x4 (data x model)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    enable_compile_cache()
    mesh_shape = tuple(int(x) for x in args.mesh.split("x")) if args.mesh else None
    train(
        arch=args.arch, steps=args.steps, smoke=not args.full,
        seq_len=args.seq_len, global_batch=args.global_batch,
        mesh_shape=mesh_shape, ckpt_dir=args.ckpt_dir, peak_lr=args.lr,
    )


if __name__ == "__main__":
    main()
