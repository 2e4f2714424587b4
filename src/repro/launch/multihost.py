"""Multi-host bring-up glue for real TPU pods (launch scripts, deliverable e).

On a v5e pod each host owns 4-8 chips; this module is the thin layer between
the cluster scheduler (GKE/QR/Ray) and the SPMD program:

    # per host, under your scheduler:
    python -m repro.launch.multihost --coordinator $COORD:8476 \
        --num-hosts 64 --host-id $RANK -- \
        train --arch qwen2.5-14b --steps 10000 --ckpt-dir gs://...

Responsibilities:
  1. jax.distributed.initialize (device mesh spans all hosts),
  2. per-host data sharding (SyntheticLMStream(host_id, num_hosts) — swap in
     your tokenized-shard reader with the same interface),
  3. the ELASTIC loop: on a host failure the scheduler restarts survivors
     with a smaller --num-hosts; restore re-shards the last checkpoint onto
     the new mesh (CheckpointStore.restore(shardings=...)),
  4. straggler policy: BSP with per-step timeout; persistent stragglers are
     reported to the scheduler for replacement (the DFW-TRACE power method
     additionally tolerates in-step dropout via worker_weight masks),
  5. comm-topology selection: ``host_topology()`` maps the process layout
     onto the ``repro.comm`` exchange graph — ``hier:<num_hosts>`` on a pod
     (exact psum stays on intra-host ICI, only the comm-encoded inter-group
     hop crosses DCN), ``flat`` single-host. The ``dfw`` subcommand runs a
     distributed DFW-Trace fit with it end to end.

Importing the module has no side effects; the single-host path is exercised
by the test-suite, and the distributed init is only taken when
--coordinator is given.
"""
from __future__ import annotations

import argparse
import sys
from typing import Optional

import jax

from .compile_cache import enable_compile_cache


def initialize(coordinator: Optional[str], num_hosts: int, host_id: int) -> None:
    """Bring up the jax distributed runtime (no-op single-host)."""
    if coordinator is None or num_hosts <= 1:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_hosts,
        process_id=host_id,
    )


def host_topology(num_hosts: Optional[int] = None) -> str:
    """The comm topology matching the process layout (``DFWConfig.topology``
    grammar): ``"hier:<num_hosts>"`` groups the mesh by host so the
    intra-group exact psum rides the fast intra-host interconnect and only
    the (compressible) inter-group hop crosses the host network;
    single-host is just ``"flat"``. ``num_hosts=None`` reads
    ``jax.process_count()`` — call after :func:`initialize`."""
    nh = jax.process_count() if num_hosts is None else int(num_hosts)
    return "flat" if nh <= 1 else f"hier:{nh}"


def _dfw_main() -> None:
    """Distributed DFW-Trace entry point: the topology API's pod consumer.

    Runs ``launch.dfw.fit`` over all visible devices with the topology
    derived from the host layout (override with --topology). The synthetic
    low-rank MTLS problem is a bring-up probe — swap in a real data loader
    for production runs; everything else (mesh, topology, comm encoding,
    checkpointing) is the production path.
    """
    import jax.numpy as jnp

    from ..core import tasks
    from . import dfw

    ap = argparse.ArgumentParser(prog="dfw")
    ap.add_argument("--epochs", type=int, default=20)
    ap.add_argument("--mu", type=float, default=1.0)
    ap.add_argument("--comm", default="dense", help="dense | int8 | topk:r")
    ap.add_argument("--topology", default="auto",
                    help="flat | ring | gossip:k | hier:g | auto (host layout)")
    ap.add_argument("--workers", type=int, default=None,
                    help="default: all visible devices")
    ap.add_argument("--samples", type=int, default=4096)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--tasks", dest="m", type=int, default=48)
    ap.add_argument("--gap-tol", type=float, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    nw = args.workers if args.workers is not None else len(jax.devices())
    topology = host_topology() if args.topology == "auto" else args.topology
    key = jax.random.PRNGKey(7)
    kw, kx = jax.random.split(key)
    w_star = jax.random.normal(kw, (args.dim, args.m))
    w_star = w_star / jnp.linalg.norm(w_star, ord="nuc")
    n = (args.samples // nw) * nw
    x = jax.random.normal(kx, (n, args.dim))
    y = x @ w_star
    task = tasks.MultiTaskLeastSquares(d=args.dim, m=args.m)
    cfg = dfw.DFWConfig(
        mu=args.mu, num_epochs=args.epochs, step_size="linesearch",
        comm=args.comm, topology=topology, gap_tol=args.gap_tol,
        checkpoint_dir=args.ckpt_dir,
    )
    res = dfw.fit(task, x, y, cfg=cfg, key=jax.random.PRNGKey(1),
                  num_workers=nw)
    if jax.process_index() == 0:
        print(  # REP006-ok: CLI subcommand summary — the terminal is the interface
            f"[multihost.dfw] workers={nw} topology={topology} "
            f"comm={args.comm} epochs_run={res.epochs_run} "
            f"final_loss={res.final_loss:.6f} "
            f"gap={res.history['gap'][-1]:.4f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--coordinator", default=None, help="host:port of process 0")
    ap.add_argument("--num-hosts", type=int, default=1)
    ap.add_argument("--host-id", type=int, default=0)
    ap.add_argument("command", choices=["train", "serve", "dryrun", "dfw"])
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args()

    enable_compile_cache()
    initialize(args.coordinator, args.num_hosts, args.host_id)
    if jax.process_index() == 0:
        print(f"[multihost] {jax.process_count()} hosts, "
              f"{len(jax.devices())} global devices "
              f"(host_topology={host_topology()})")

    sys.argv = [args.command] + [a for a in args.rest if a != "--"]
    if args.command == "train":
        from . import train as mod
    elif args.command == "serve":
        from . import serve as mod
    elif args.command == "dfw":
        _dfw_main()
        return
    else:
        from . import dryrun as mod
    mod.main()


if __name__ == "__main__":
    main()
