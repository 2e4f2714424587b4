"""Telemetry smoke: run a short instrumented train -> serve pass, export
both sinks, and validate that the trace is loadable and covers all four
instrumented layers.

What it proves (`make trace-smoke`, also run by the CI bench-smoke job):

* an instrumented fit with segment-boundary checkpointing completes with a
  live ``Telemetry`` handle threaded end to end;
* ``TRACE_smoke.jsonl`` parses line-by-line (meta first, metrics last);
* ``TRACE_smoke.trace.json`` is Chrome-trace/Perfetto-loadable (every
  event carries name/ph/ts/pid, ph in {X, i, C}, complete spans have
  nonnegative durations);
* the span stream covers engine segments, checkpoint writes, and serving
  dispatches, and the registry counts the comm layer's reducer traffic —
  one name per instrumented layer.

Exit 0 on success, 1 with a reason on any failure.
"""
from __future__ import annotations

import json
import sys
import tempfile
from pathlib import Path

REQUIRED_SPANS = (
    "engine.segment",    # engine: one per scan segment
    "checkpoint.write",  # checkpoint: async boundary saves
    "serve.dispatch",    # serving: scored batches
)
# comm: the reducer traffic, counted analytically per segment
REQUIRED_COUNTERS = ("comm.rounds", "comm.wire_bytes")


def run_instrumented(tmp: Path):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import serve
    from repro.core import tasks
    from repro.launch import dfw
    from repro.obs import Telemetry

    tel = Telemetry()
    n, d, m = 400, 24, 18
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    w = jax.random.normal(kw, (d, m))
    w = w / jnp.linalg.norm(w, ord="nuc")
    x = jax.random.normal(kx, (n, d))
    task = tasks.MultiTaskLeastSquares(d=d, m=m)
    cfg = dfw.DFWConfig(
        mu=1.0, num_epochs=12, schedule="const:2", step_size="linesearch",
        block_epochs=4,  # several segments -> several boundary checkpoints
        checkpoint_dir=str(tmp / "ck"), telemetry=tel,
    )
    res = dfw.fit_serial(task, x, x @ w, cfg=cfg, key=jax.random.PRNGKey(1))

    # Serve from the checkpoint the run just wrote, on the same handle.
    eng = serve.ServingEngine.from_checkpoint(
        tmp / "ck",
        serve.ServeConfig(max_batch=8, verify_kernels=False, telemetry=tel),
    )
    for _ in range(3):
        eng.score(np.ones((8, d), np.float32))
    return tel, res


def validate_jsonl(path: Path) -> int:
    lines = [json.loads(s) for s in path.read_text().splitlines()]
    assert lines, f"{path} is empty"
    assert lines[0].get("type") == "meta", "first JSONL line must be meta"
    assert lines[-1].get("type") == "metrics", "last JSONL line must be metrics"
    assert lines[-1]["data"]["counters"], "metrics snapshot has no counters"
    return len(lines) - 2


def validate_chrome_trace(path: Path) -> list:
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert events, f"{path} has no traceEvents"
    for ev in events:
        missing = {"name", "ph", "ts", "pid"} - set(ev)
        assert not missing, f"event {ev} missing {missing}"
        assert ev["ph"] in ("X", "i", "C"), f"unexpected phase {ev['ph']}"
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0, f"negative duration in {ev}"
    return events


def main() -> int:
    out_jsonl = Path("TRACE_smoke.jsonl")
    out_trace = Path("TRACE_smoke.trace.json")
    with tempfile.TemporaryDirectory() as tmp:
        tel, res = run_instrumented(Path(tmp))
    tel.write_jsonl(out_jsonl)
    tel.write_chrome_trace(out_trace)

    n_events = validate_jsonl(out_jsonl)
    events = validate_chrome_trace(out_trace)
    assert n_events == len(events), (
        f"sink disagreement: {n_events} JSONL events vs {len(events)} trace"
    )

    names = {ev["name"] for ev in events}
    missing = [s for s in REQUIRED_SPANS if s not in names]
    if missing:
        print(f"trace-smoke: FAIL — missing spans {missing}; got {sorted(names)}")
        return 1
    counters = tel.registry.snapshot()["counters"]
    missing = [c for c in REQUIRED_COUNTERS if not counters.get(c)]
    if missing:
        print(f"trace-smoke: FAIL — counters {missing} missing or zero")
        return 1
    print(
        f"trace-smoke: OK — {len(events)} events, {res.epochs_run} epochs, "
        f"spans cover {', '.join(REQUIRED_SPANS)}, counters "
        f"{', '.join(REQUIRED_COUNTERS)}; wrote {out_jsonl} + {out_trace}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
