#!/usr/bin/env python3
"""Bring-up run of DFW-Trace on TPU through the entry points a user calls.

    python chip_smoke.py             # one chip: head, mtls, completion, serve
    python chip_smoke.py --chips 4   # four chips: the sharded head vs fit_serial

Everything runs in this one process, which holds the chip(s). Each phase
prints one line: its shapes, compile and run seconds, the device's peak
memory so far, and its correctness figure beside the limit it must meet.
A phase passes only if its figure is within the limit and every compiled
training or scoring program holds a Pallas kernel (``tpu_custom_call``):
a hot path that fell back to plain XLA fails. The last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``, printed only
when every phase passed; any failure exits non-zero. Data is generated
from ``--seed``; the mtls checkpoint that the serve phase loads is written
under ``.chip_smoke/`` in the checkout.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / ".chip_smoke"

# Correctness limits, each on the figure its phase prints.
HEAD_RTOL = 1e-3  # loss history vs the kernelize=False run
MTLS_INT8_RTOL = 2e-2  # int8 exchange vs the dense, kernel-free run
COMPLETION_RTOL = 1e-3  # loss history vs the kernelize=False run
SERVE_RTOL = 1e-4  # scores vs the dense product at Precision.HIGHEST
MULTI_DENSE_RTOL = 1e-3  # 4-chip flat/dense final loss vs fit_serial
MULTI_INT8_RTOL = 2e-2  # 4-chip hier:2/int8 final loss vs fit_serial


class SmokeFailure(RuntimeError):
    pass


def fail(msg: str) -> None:
    raise SmokeFailure(msg)


def report(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def peak_bytes(jax) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def span_seconds(tel, *names: str) -> float:
    return sum(e["dur"] for e in tel.events()
               if e.get("ph") == "X" and e["name"] in names) / 1e6


def kernel_calls(tel, event: str) -> list:
    return [e["args"]["tpu_kernel_calls"] for e in tel.events()
            if e["name"] == event]


def history_dev(got, want) -> float:
    """max_t |got_t - want_t| / |want_0|: deviation of two loss histories on
    the scale of the starting loss (a history that converges towards 0 has
    no meaningful pointwise relative error)."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        fail(f"history lengths differ: {got.shape} vs {want.shape}")
    return float(np.max(np.abs(got - want)) / abs(want[0]))


def timed_fit(fit, *args, **kw):
    """Run a fit under its own telemetry; returns (result, timings)."""
    from repro.obs import Telemetry

    tel = Telemetry(capture_hlo=True)
    cfg = kw.pop("cfg")
    t0 = time.perf_counter()
    res = fit(*args, cfg=dataclasses.replace(cfg, telemetry=tel), **kw)
    wall = time.perf_counter() - t0
    return res, dict(
        wall_s=round(wall, 3),
        compile_s=round(span_seconds(tel, "engine.compile"), 3),
        run_s=round(span_seconds(tel, "engine.dispatch", "engine.fetch"), 3),
        kernels=kernel_calls(tel, "comm.executable"),
    )


def require_kernels(phase: str, kernels: list) -> None:
    if not kernels or min(kernels) < 1:
        fail(f"{phase}: compiled program(s) hold no tpu_custom_call "
             f"(kernel call sites per program: {kernels})")


def check(phase: str, name: str, value: float, limit: float) -> None:
    if not value <= limit:  # NaN fails too
        fail(f"{phase}: {name}={value:.3e} exceeds its limit {limit:.1e}")


# ---------------------------------------------------------------------------
# Data, generated from the seed
# ---------------------------------------------------------------------------


def head_data(n: int, p: int, m: int, seed: int):
    """Gaussian features with labels from a planted rank-10 W* and 5% label
    noise (the generator of ``benchmarks/imagenet_head.py``), on the host."""
    import numpy as np

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, p), dtype=np.float32)
    wu = rng.standard_normal((p, 10), dtype=np.float32)
    wv = rng.standard_normal((10, m), dtype=np.float32)
    y = np.argmax((x @ wu) @ wv, axis=1).astype(np.int32)
    flip = rng.random(n) < 0.05
    y[flip] = rng.integers(0, m, int(flip.sum()), dtype=np.int32)
    return x, y


def mtls_data(key, n: int, d: int, m: int, rank: int):
    """Paper §5.1 generator: rank-r truth with ||W*||_* = 1, Y = X W*."""
    import jax
    import jax.numpy as jnp

    ku, kv, kx = jax.random.split(key, 3)
    u = jnp.linalg.qr(jax.random.normal(ku, (d, rank)))[0]
    v = jnp.linalg.qr(jax.random.normal(kv, (m, rank)))[0]
    s = jnp.linspace(1.0, 0.1, rank)
    w = (u * (s / jnp.sum(s))) @ v.T
    x = jax.random.normal(kx, (n, d))
    return x, x @ w


def completion_data(key, d: int, m: int, frac: float, rank: int):
    """Rank-r truth M = U diag(s) V^T; a uniform ``frac`` of its entries is
    observed. Returns host COO arrays and ||M||_* (the trace-norm radius)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ku, kv, ko = jax.random.split(key, 3)
    u = jnp.linalg.qr(jax.random.normal(ku, (d, rank)))[0]
    v = jnp.linalg.qr(jax.random.normal(kv, (m, rank)))[0]
    s = jnp.linspace(100.0, 10.0, rank)
    flat = jax.random.choice(ko, d * m, (int(frac * d * m),), replace=False)
    rows, cols = flat // m, flat % m
    vals = jnp.sum(u[rows] * s * v[cols], axis=1)
    rows, cols, vals, s = jax.device_get((rows, cols, vals, s))
    return np.asarray(rows), np.asarray(cols), np.asarray(vals), float(s.sum())


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------


def phase_head(jax, seed: int, n: int = 327_680, p: int = 2048,
               m: int = 1000, epochs: int = 5, mu: float = 10.0) -> None:
    """The paper's trace-norm multinomial head at ImageNet width: one chip's
    share (1.28M / 4 rows) of ResNet-50 features, 1000 classes."""
    from repro.core import tasks
    from repro.launch import dfw

    x, y = head_data(n, p, m, seed)
    x, y = jax.device_put((x, y))  # placed once, shared by both runs
    task = tasks.MultinomialLogistic(d=p, m=m)
    # MultinomialLogistic has no closed-form line search: 2/(t+2) steps.
    cfg = dfw.DFWConfig(mu=mu, num_epochs=epochs, schedule="const:2")
    key = jax.random.PRNGKey(seed + 1)
    res, t = timed_fit(dfw.fit, task, x, y, cfg=cfg, key=key, num_workers=1)
    ref, _ = timed_fit(dfw.fit, task, x, y, key=key, num_workers=1,
                       cfg=dataclasses.replace(cfg, kernelize=False))
    dev = history_dev(res.history["loss"], ref.history["loss"])
    loss = res.history["loss"]
    report("head", n=n, p=p, m=m, epochs=epochs, compile_s=t["compile_s"],
           run_s=t["run_s"], fit_wall_s=t["wall_s"],
           peak_bytes=peak_bytes(jax), tpu_kernel_calls=t["kernels"],
           loss_first=f"{loss[0]:.6g}", loss_last=f"{res.final_loss:.6g}",
           history_dev=f"{dev:.3e}", limit=HEAD_RTOL)
    require_kernels("head", t["kernels"])
    check("head", "history_dev", dev, HEAD_RTOL)
    if not res.final_loss < loss[0]:
        fail(f"head: loss did not fall ({loss[0]} -> {res.final_loss})")


def phase_mtls(jax, seed: int, n: int = 65_536, d: int = 1024,
               m: int = 1024, rank: int = 32, epochs: int = 10) -> Path:
    """Table-1 multi-task least squares with the block:32 solver and the
    int8 exchange (quantize kernel); its checkpoint feeds the serve phase."""
    from repro.core import tasks
    from repro.launch import dfw

    x, y = mtls_data(jax.random.PRNGKey(seed + 2), n, d, m, rank)
    task = tasks.MultiTaskLeastSquares(d=d, m=m)
    ckpt_dir = OUT / "mtls_ckpt"
    cfg = dfw.DFWConfig(mu=1.0, num_epochs=epochs, schedule="const:2",
                        step_size="linesearch", solver="block:32", comm="int8",
                        checkpoint_dir=str(ckpt_dir))
    key = jax.random.PRNGKey(seed + 3)
    res, t = timed_fit(dfw.fit, task, x, y, cfg=cfg, key=key, num_workers=1)
    ref, _ = timed_fit(
        dfw.fit, task, x, y, key=key, num_workers=1,
        cfg=dataclasses.replace(cfg, kernelize=False, comm="dense",
                                checkpoint_dir=None),
    )
    dev = history_dev(res.history["loss"], ref.history["loss"])
    loss = res.history["loss"]
    report("mtls", n=n, d=d, m=m, truth_rank=rank, solver="block:32",
           comm="int8", epochs=epochs, compile_s=t["compile_s"],
           run_s=t["run_s"], fit_wall_s=t["wall_s"],
           peak_bytes=peak_bytes(jax), tpu_kernel_calls=t["kernels"],
           loss_first=f"{loss[0]:.6g}", loss_last=f"{res.final_loss:.6g}",
           history_dev=f"{dev:.3e}", limit=MTLS_INT8_RTOL)
    require_kernels("mtls", t["kernels"])
    check("mtls", "history_dev", dev, MTLS_INT8_RTOL)
    if not res.final_loss < loss[0]:
        fail(f"mtls: loss did not fall ({loss[0]} -> {res.final_loss})")
    return ckpt_dir


def phase_completion(jax, seed: int, d: int = 1024, m: int = 1024) -> None:
    """Matrix completion with 10% of a d x m rank-10 matrix observed."""
    from repro.core import tasks
    from repro.launch import dfw

    rows, cols, vals, mu = completion_data(
        jax.random.PRNGKey(seed + 4), d, m, frac=0.1, rank=10)
    idx, yw = dfw.shard_observations(rows, cols, vals, 1, d, m=m)
    task = tasks.MatrixCompletion(d=d, m=m)
    cfg = dfw.DFWConfig(mu=mu, num_epochs=10, schedule="const:2",
                        step_size="linesearch")
    key = jax.random.PRNGKey(seed + 5)
    res, t = timed_fit(dfw.fit, task, idx, yw, cfg=cfg, key=key, num_workers=1)
    ref, _ = timed_fit(dfw.fit, task, idx, yw, key=key, num_workers=1,
                       cfg=dataclasses.replace(cfg, kernelize=False))
    dev = history_dev(res.history["loss"], ref.history["loss"])
    loss = res.history["loss"]
    report("completion", d=d, m=m, observed=len(vals), epochs=cfg.num_epochs,
           compile_s=t["compile_s"], run_s=t["run_s"], fit_wall_s=t["wall_s"],
           peak_bytes=peak_bytes(jax), tpu_kernel_calls=t["kernels"],
           loss_first=f"{loss[0]:.6g}", loss_last=f"{res.final_loss:.6g}",
           history_dev=f"{dev:.3e}", limit=COMPLETION_RTOL)
    require_kernels("completion", t["kernels"])
    check("completion", "history_dev", dev, COMPLETION_RTOL)
    if not res.final_loss < loss[0]:
        fail(f"completion: loss did not fall ({loss[0]} -> {res.final_loss})")


def phase_serve(jax, seed: int, ckpt_dir: Path) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import dfw as ckpt
    from repro.obs import Telemetry
    from repro.serve import ServeConfig, ServingEngine

    batches, max_batch = 8, 64
    tel = Telemetry(capture_hlo=True)
    t0 = time.perf_counter()
    eng = ServingEngine.from_checkpoint(
        ckpt_dir, ServeConfig(max_batch=max_batch, telemetry=tel))
    load_s = time.perf_counter() - t0
    xs = jax.random.normal(jax.random.PRNGKey(seed + 6),
                           (batches, max_batch, eng.d))
    xs_host = np.asarray(jax.device_get(xs))
    t0 = time.perf_counter()
    got = np.stack([eng.score(xb) for xb in xs_host])
    run_s = time.perf_counter() - t0

    _, packed, _ = ckpt.read_iterate_packed(ckpt_dir)
    hi = jax.lax.Precision.HIGHEST
    w = packed["alpha"] * jnp.einsum("k,kd,km->dm", packed["s"], packed["u"],
                                     packed["v"], precision=hi)
    want = np.asarray(jax.device_get(jnp.einsum("bnd,dm->bnm", xs, w,
                                                precision=hi)))
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    kernels = kernel_calls(tel, "serve.executable")
    report("serve", d=eng.d, m=eng.m, live_rank=eng.model.live_rank,
           capacity=eng.model.capacity, batches=batches, max_batch=max_batch,
           compile_s=round(span_seconds(tel, "serve.compile"), 3),
           load_s=round(load_s, 3), run_s=round(run_s, 3),
           peak_bytes=peak_bytes(jax), tpu_kernel_calls=kernels,
           rel_err=f"{err:.3e}", limit=SERVE_RTOL)
    require_kernels("serve", kernels)
    check("serve", "rel_err", err, SERVE_RTOL)


# ---------------------------------------------------------------------------
# Four-chip phase
# ---------------------------------------------------------------------------


def phase_multi(jax, seed: int, nw: int = 4, rows_per_chip: int = 65_536,
                p: int = 2048, m: int = 1000, epochs: int = 5,
                mu: float = 10.0) -> None:
    """The head row-sharded over ``nw`` chips, flat/dense and hier:2/int8,
    each against fit_serial of the same data on one chip."""
    from repro.core import tasks
    from repro.launch import dfw

    n = nw * rows_per_chip
    x, y = head_data(n, p, m, seed)
    task = tasks.MultinomialLogistic(d=p, m=m)
    key = jax.random.PRNGKey(seed + 1)
    base = dfw.DFWConfig(mu=mu, num_epochs=epochs, schedule="const:2")
    serial, ts = timed_fit(dfw.fit_serial, task, x, y, cfg=base, key=key)
    report("multi.serial", n=n, p=p, m=m, epochs=epochs,
           compile_s=ts["compile_s"], run_s=ts["run_s"],
           fit_wall_s=ts["wall_s"], tpu_kernel_calls=ts["kernels"],
           final_loss=f"{serial.final_loss:.6g}")
    require_kernels("multi.serial", ts["kernels"])
    serial.state = None  # free its device copy of the data
    for topology, comm, limit in (("flat", "dense", MULTI_DENSE_RTOL),
                                  ("hier:2", "int8", MULTI_INT8_RTOL)):
        name = f"multi.{topology}.{comm}"
        res, t = timed_fit(
            dfw.fit, task, x, y, key=key, num_workers=nw,
            cfg=dataclasses.replace(base, topology=topology, comm=comm))
        placed = {}
        for leaf in jax.tree.leaves(res.state):
            for sh in leaf.addressable_shards:
                placed.setdefault(sh.device.id, set()).add(sh.data.shape[0])
        shards_ok = (len(placed) == nw and
                     all(s == {rows_per_chip} for s in placed.values()))
        rel = abs(res.final_loss - serial.final_loss) / abs(serial.final_loss)
        report(name, workers=nw, rows_per_chip=rows_per_chip,
               compile_s=t["compile_s"], run_s=t["run_s"],
               fit_wall_s=t["wall_s"], tpu_kernel_calls=t["kernels"],
               shard_rows_by_device={k: sorted(v) for k, v in placed.items()},
               final_loss=f"{res.final_loss:.6g}",
               rel_vs_serial=f"{rel:.3e}", limit=limit)
        require_kernels(name, t["kernels"])
        if not shards_ok:
            fail(f"{name}: row shards not one per device: {placed}")
        check(name, "rel_vs_serial", rel, limit)
        del res


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package from "
              f"{ROOT / 'src'} ({e}); run from a checkout of the repository",
              file=sys.stderr)
        return 2
    import jax

    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    report("device", platform=dev.platform, kind=repr(dev.device_kind),
           count=len(devices), jax=jax.__version__)

    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True)
    try:
        if args.chips == 4:
            phase_multi(jax, args.seed)
        else:
            phase_head(jax, args.seed)
            ckpt_dir = phase_mtls(jax, args.seed)
            phase_completion(jax, args.seed)
            phase_serve(jax, args.seed, ckpt_dir)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
