"""Resolution of a cell to its files, and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name that ``BENCHMARK.json``
gives it:

- configuration ``<c>``: the ``file`` of its ``configs`` entry
  (``bench/configs/<c>.json``), which names its ``problem``;
- problem ``<p>``: ``bench/problems/<p>.py`` (data from the seed, the
  program's task, and the plain reference);
- traffic ``<t>``: ``bench/traffic/<t>.json``, whose ``kind`` names the one
  general driver that reads it, ``bench/drive_<kind>.py``;
- per-layer metric ``<m>``: ``bench/metrics/<m>.py`` with ``read(ctx)``,
  which returns a number or ``None`` when it finds nothing to read.

Adding a configuration, mix or metric therefore means adding files and
entries, never editing one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    return make_cell(workload, root / confs[w["config"]]["file"], w["traffic"],
                     int(w["chips"]), bench, root)


def make_cell(name: str, config_file: Path, traffic: str, chips: int,
              bench: Dict[str, Any], root: Path = ROOT) -> Cell:
    """A cell from its configuration file and traffic mix by name, with the
    metrics that ``bench`` gives a cell of that name."""
    config = json.loads(Path(config_file).read_text())
    mix = json.loads((Path(root) / "bench" / "traffic" / f"{traffic}.json").read_text())
    return Cell(
        name=name, chips=chips,
        config_name=config["name"], config=config,
        traffic_name=traffic, traffic=mix,
        end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name)],
    )


def problem(name: str, root: Path = ROOT):
    return _module(Path(root) / "bench" / "problems" / f"{name}.py",
                   f"bench_problem_{name}")


def driver(kind: str, root: Path = ROOT):
    return _module(Path(root) / "bench" / f"drive_{kind}.py", f"bench_drive_{kind}")


def reader(metric: str, root: Path = ROOT):
    return _module(Path(root) / "bench" / "metrics" / f"{metric}.py",
                   "bench_metric_" + metric.replace(".", "_"))


def read_per_layer(cell: Cell, ctx: Dict[str, Any],
                   root: Path = ROOT) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric of the cell that finds something to read."""
    out = {}
    for m in cell.per_layer:
        value = reader(m["name"], root).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def report_checks(checks: List[Dict[str, Any]]) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    standard error."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr, flush=True)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Any], device: Dict[str, Any],
                checks: List[Dict[str, Any]],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    line: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    return json.dumps(line)


# ---------------------------------------------------------------------------
# Helpers the drivers share
# ---------------------------------------------------------------------------


def seed_key(seed: int):
    """A PRNG key from any whole ``--seed``: its low and high 32-bit words
    both enter, so no two seeds of up to 64 bits share a key."""
    import jax

    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


class _Both:
    """Enter a profiler annotation around a program span."""

    def __init__(self, outer, inner):
        self._outer, self._inner = outer, inner

    def __enter__(self):
        self._outer.__enter__()
        return self._inner.__enter__()

    def __exit__(self, *exc):
        try:
            return self._inner.__exit__(*exc)
        finally:
            self._outer.__exit__(*exc)


def annotating_telemetry():
    """The program's telemetry handle, whose spans are also written into the
    profiler's trace as host annotations (so idle gaps on the device can be
    labelled with what the host was doing)."""
    import jax
    from repro.obs import Telemetry

    class AnnotatingTelemetry(Telemetry):
        def span(self, name, cat="run", t0=None, **args):
            return _Both(jax.profiler.TraceAnnotation(name),
                         super().span(name, cat, t0, **args))

    return AnnotatingTelemetry(capture_hlo=False)


def annotate(name: str, on: bool):
    import contextlib

    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def span_seconds(tel, names, since_us: float = 0.0) -> float:
    return sum(e["dur"] for e in tel.events()
               if e.get("ph") == "X" and e["name"] in names
               and e["ts"] >= since_us) / 1e6


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))
