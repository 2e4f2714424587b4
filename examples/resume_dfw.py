"""Kill-and-resume: a durable distributed DFW-Trace run on 8 workers.

Phase 1 launches an 8-way fit with segment-boundary checkpointing and kills
the *process* (SIGKILL, no cleanup) partway through — the brutal version of
a preempted worker pool. Phase 2 resumes from the last durable checkpoint
on the same 8-way mesh and must reproduce the uninterrupted trajectory bit
for bit. Phase 3 resumes the same checkpoint onto a *4*-worker mesh (half
the pool evaporated): the row-blocked state is re-sharded, per-worker comm
state re-initialized, and the run still converges to the same solution.

Run:  PYTHONPATH=src python examples/resume_dfw.py
(spawns its own subprocesses; sets XLA_FLAGS itself)
"""
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")
CKPT = tempfile.mkdtemp(prefix="dfw_ckpt_")

# The worker program: one fit, checkpointed every segment. `nw` and
# `resume` come from argv so the same program plays victim and survivor.
WORKER = r"""
import json, os, sys
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax, jax.numpy as jnp
from repro.core import tasks
from repro.launch import dfw

ckpt_dir, nw, resume = sys.argv[1], int(sys.argv[2]), sys.argv[3] == "resume"
n, d, m = 4096, 64, 48
key = jax.random.PRNGKey(0)
kx, kw = jax.random.split(key)
w = jax.random.normal(kw, (d, m))
x = jax.random.normal(kx, (n, d))
y = x @ (w / jnp.linalg.norm(w, ord="nuc"))

cfg = dfw.DFWConfig(
    mu=1.0, num_epochs=40, schedule="const:2", step_size="linesearch",
    block_epochs=5,                       # checkpoint cadence = 5 epochs
    checkpoint_dir=None if resume else ckpt_dir,
    resume_from=ckpt_dir if resume else None,
)
res = dfw.fit(tasks.MultiTaskLeastSquares(d=d, m=m), x, y, cfg=cfg,
              key=jax.random.PRNGKey(1), num_workers=nw)
print("RESULT " + json.dumps({
    "final_loss": res.final_loss,
    "loss_history": res.history["loss"],
    "epochs_run": res.epochs_run,
}), flush=True)
"""


def run_worker(nw, mode, kill_after=None):
    env = {**os.environ, "PYTHONPATH": SRC}
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"  # fake-device child: never contend for a TPU
    proc = subprocess.Popen(
        [sys.executable, "-c", WORKER, CKPT, str(nw), mode],
        stdout=subprocess.PIPE, text=True, env=env,
    )
    if kill_after is not None:
        # Wait for the first checkpoints to land, then SIGKILL mid-run.
        deadline = time.time() + 300
        while time.time() < deadline:
            steps = sorted(Path(CKPT).glob("step_*"))
            if len(steps) >= kill_after and proc.poll() is None:
                proc.kill()
                proc.wait()
                return None
            if proc.poll() is not None:
                break  # finished before we got to kill it; use its result
            time.sleep(0.05)
    out, _ = proc.communicate(timeout=600)
    assert proc.returncode == 0, out
    line = [ln for ln in out.splitlines() if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


# --- uninterrupted reference (fresh checkpoint dir kept for the kill run) --
ref = run_worker(8, "fresh")
print(f"reference 8-way run: {ref['epochs_run']} epochs, "
      f"final loss {ref['final_loss']:.6f}")

# --- phase 1: same run again, SIGKILLed after two durable checkpoints ------
for p in Path(CKPT).glob("step_*"):
    for f in p.iterdir():
        f.unlink()
    p.rmdir()
killed = run_worker(8, "fresh", kill_after=2)
steps = sorted(int(p.name.split("_")[1]) for p in Path(CKPT).glob("step_*"))
assert killed is None or steps, "expected durable checkpoints"
print(f"killed mid-run; durable checkpoint steps on disk: {steps}")

# --- phase 2: resume on the same 8-way mesh → bit-exact ---------------------
resumed = run_worker(8, "resume")
assert resumed["loss_history"] == ref["loss_history"], "trajectory diverged!"
assert resumed["final_loss"] == ref["final_loss"]
print(f"8-way resume: bit-exact — {resumed['epochs_run']} total epochs, "
      f"final loss {resumed['final_loss']:.6f} (identical bits)")

# --- phase 3: elastic resume onto 4 workers --------------------------------
elastic = run_worker(4, "resume")
rel = abs(elastic["final_loss"] - ref["final_loss"]) / abs(ref["final_loss"])
print(f"elastic 8->4 resume: final loss {elastic['final_loss']:.6f} "
      f"(rel delta {rel:.2e} vs uninterrupted)")
assert rel < 1e-3
print("kill-and-resume demo OK")
