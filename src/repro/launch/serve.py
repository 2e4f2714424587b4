"""Serving drivers.

Two traffic shapes live here:

* **Factor-form scoring** (``serve_factored``, the primary driver): score
  request vectors against a DFW-Trace checkpoint through
  ``repro.serve.ServingEngine`` — fused factor matvec, padded static
  batches, live-rank bucket packing, and hot-swap that follows the
  checkpoint directory as training writes new steps. This is the paper's
  deployment story: the model never exists as a dense d x m matrix, in
  training *or* in serving.
* **LM decode** (``generate``, legacy): batched incremental decoding over
  the model zoo with KV/SSM caches. Smoke-scale on CPU; the same
  serve_step is what the dry-run lowers at (16,16)/(2,16,16) for the
  decode_32k / long_500k cells.

CLI: ``python -m repro.launch.serve factor --checkpoint DIR ...`` or
``python -m repro.launch.serve lm --arch NAME ...``.
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import lm
from repro.serve import ServeConfig, ServingEngine

from .compile_cache import enable_compile_cache
from .steps import make_serve_step


# ---------------------------------------------------------------------------
# Factor-form serving (primary)
# ---------------------------------------------------------------------------


def serve_factored(
    *,
    checkpoint: str,
    max_batch: int = 64,
    rank_block: int = 32,
    transpose: bool = False,
    batches: int = 8,
    follow: int = 0,
    poll_s: float = 0.2,
    seed: int = 0,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
):
    """Serve random request traffic from a run-checkpoint directory.

    Loads the latest step, scores ``batches`` full padded batches, and — in
    ``follow`` mode — polls the directory up to ``follow`` more rounds,
    hot-swapping whenever training has written a newer step (the live
    train-and-serve topology: one process fits, this one scores). Returns a
    summary dict; prints one line per swap and a final stats line.
    """
    cfg = ServeConfig(
        max_batch=max_batch, rank_block=rank_block, transpose=transpose,
        use_pallas=use_pallas, interpret=interpret,
    )
    eng = ServingEngine.from_checkpoint(checkpoint, cfg)
    print(
        f"[serve] {eng.d}x{eng.m} model, step {eng.model.step}, "
        f"live rank {eng.model.live_rank} (bucket {eng.model.capacity}), "
        f"max_batch {max_batch}"
    )
    rng = np.random.default_rng(seed)

    def pump(n_batches: int) -> float:
        xs = rng.standard_normal((n_batches, max_batch, eng.n_in), np.float32)
        t0 = time.perf_counter()
        handles = [eng.score_async(xs[i]) for i in range(n_batches)]
        rows = sum(h.block().shape[0] for h in handles)
        dt = time.perf_counter() - t0
        print(
            f"[serve] scored {rows} requests in {dt * 1e3:.1f} ms "
            f"({rows / max(dt, 1e-9):.0f} req/s, model v{eng.model.version})"
        )
        return dt

    pump(batches)
    for _ in range(follow):
        time.sleep(poll_s)
        from repro.checkpoint import CheckpointStore

        latest = CheckpointStore(checkpoint).latest_step()
        if latest is not None and latest != eng.model.step:
            before = eng.stats["compilations"]
            model = eng.load(checkpoint, step=latest)
            print(
                f"[serve] hot-swap -> step {model.step}, live rank "
                f"{model.live_rank}, +{eng.stats['compilations'] - before} "
                "compiles"
            )
        pump(batches)
    print(f"[serve] stats: {eng.stats}")
    return {"stats": dict(eng.stats), "step": eng.model.step,
            "live_rank": eng.model.live_rank, "version": eng.model.version}


# ---------------------------------------------------------------------------
# LM decode (legacy)
# ---------------------------------------------------------------------------


def generate(
    *,
    arch: str,
    batch: int = 4,
    prompt_len: int = 16,
    max_new_tokens: int = 32,
    smoke: bool = True,
    temperature: float = 0.0,
    seed: int = 0,
):
    """Greedy/temperature sampling over the synthetic-token distribution."""
    cfg = get_config(arch, smoke=smoke)
    if cfg.encoder_only:
        raise ValueError(f"{arch} is encoder-only; no decode path")
    key = jax.random.PRNGKey(seed)
    params = lm.init_params(cfg, key)
    max_len = prompt_len + max_new_tokens
    cache = lm.init_cache(cfg, batch, max_len)
    step = jax.jit(make_serve_step(cfg))

    prompt = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)
    toks = [prompt[:, i : i + 1] for i in range(prompt_len)]
    out_tokens = []
    logits = None
    t0 = time.time()
    for t in range(max_len - 1):
        cur = toks[t] if t < prompt_len else out_tokens[-1]
        b = {"tokens": cur, "cache_pos": jnp.int32(t)}
        if cfg.family == "vlm":
            b["positions"] = jnp.full((batch, 3, 1), t, jnp.int32)
        logits, cache = step(params, cache, b)
        if t >= prompt_len - 1:
            if temperature > 0:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, logits[:, 0, :] / temperature)[:, None]
            else:
                nxt = jnp.argmax(logits[:, 0, :], axis=-1)[:, None]
            out_tokens.append(nxt)
    dt = time.time() - t0
    gen = jnp.concatenate(out_tokens, axis=1)
    print(f"[serve] {arch}: generated {gen.shape} in {dt:.2f}s "
          f"({dt / max(len(out_tokens),1) * 1e3:.1f} ms/token at batch {batch})")
    return np.asarray(gen)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="mode", required=True)

    fp = sub.add_parser("factor", help="score requests from a DFW checkpoint")
    fp.add_argument("--checkpoint", required=True)
    fp.add_argument("--max-batch", type=int, default=64)
    fp.add_argument("--rank-block", type=int, default=32)
    fp.add_argument("--transpose", action="store_true",
                    help="score x @ W^T (m -> d) instead of x @ W")
    fp.add_argument("--batches", type=int, default=8)
    fp.add_argument("--follow", type=int, default=0,
                    help="poll the checkpoint dir N more rounds, hot-swapping "
                         "onto any new step")
    fp.add_argument("--poll-s", type=float, default=0.2)
    fp.add_argument("--seed", type=int, default=0)
    fp.add_argument("--interpret", action="store_true")

    lp = sub.add_parser("lm", help="legacy LM decode driver")
    lp.add_argument("--arch", required=True)
    lp.add_argument("--batch", type=int, default=4)
    lp.add_argument("--prompt-len", type=int, default=16)
    lp.add_argument("--max-new-tokens", type=int, default=32)
    lp.add_argument("--temperature", type=float, default=0.0)

    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.mode == "factor":
        serve_factored(
            checkpoint=args.checkpoint, max_batch=args.max_batch,
            rank_block=args.rank_block, transpose=args.transpose,
            batches=args.batches, follow=args.follow, poll_s=args.poll_s,
            seed=args.seed, interpret=args.interpret,
        )
    else:
        generate(
            arch=args.arch, batch=args.batch, prompt_len=args.prompt_len,
            max_new_tokens=args.max_new_tokens, temperature=args.temperature,
        )


if __name__ == "__main__":
    main()
