"""Reduction of a profiler trace to the benchmark's device numbers.

``capture`` records a ``jax.profiler`` trace into a temporary directory
outside the checkout, reads it with ``jax.profiler.ProfileData`` and
deletes it. ``load_xspace`` keeps only what the reduction needs, as plain
lists (so a small trace can be stored as JSON and the reduction checked on
it):

- ``devices``: for each device plane, its operations as
  ``[name, start_ns, end_ns]``, with a fourth element ``True`` for an
  all-reduce that only the event's stats, not its name, show;
- ``host``: the host annotations (``jax.profiler.TraceAnnotation`` and the
  program's spans that the benchmark annotates) as ``[name, start_ns,
  end_ns]``.

``reduce`` then gives, inside the traced window (the ``bench.window``
annotation, or the span of the device operations when it is absent):

- busy time: the union of a device's operation intervals, averaged over
  the devices, and the window's length;
- time by operation name, averaged over the devices;
- all-reduce time, and the part of it during which no other operation runs
  on that device (exposed), averaged over the devices;
- ``breakdown``: the ten operations that took most time, and the ten
  longest idle gaps of the first device, each labelled with the innermost
  host annotation that covers its middle.
"""
from __future__ import annotations

import contextlib
import glob
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Sequence

WINDOW = "bench.window"
HOST_PREFIXES = ("bench.", "engine.", "serve.", "checkpoint.", "comm.")
# A device op is an all-reduce when its name or one of its stats holds one
# of these. XLA names the instruction after the JAX primitive: a
# ``lax.psum`` compiles for the TPU to ``%psum.14 = ... all-reduce(...)``.
COLLECTIVE = ("all-reduce", "allreduce", "psum")
# Lines of a device plane that summarise others rather than hold operations.
_SUMMARY_LINES = ("XLA Modules", "Steps", "XLA TraceMe", "Framework")


@contextlib.contextmanager
def capture(out: dict):
    """Trace the body; on exit ``out["trace"]`` holds ``load_xspace``'s
    lists. The raw trace lives in a temporary directory that is removed."""
    import jax

    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
        out["trace"] = load_xspace(paths[0]) if paths else None
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _ops_line(lines) -> Optional[object]:
    by_name = {ln.name: ln for ln in lines}
    if "XLA Ops" in by_name:
        return [by_name["XLA Ops"]]
    return [ln for ln in lines if ln.name not in _SUMMARY_LINES]


def load_xspace(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "NON_CORE" not in plane.name.upper():
            ops = []
            for line in _ops_line(list(plane.lines)):
                for e in line.events:
                    if e.duration_ns > 0:
                        op = [e.name, float(e.start_ns), float(e.end_ns)]
                        if not is_collective(e.name) and _stats_name_collective(e):
                            op.append(True)
                        ops.append(op)
            if ops:
                devices[plane.name] = sorted(ops, key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append([e.name, float(e.start_ns), float(e.end_ns)])
    return {"devices": devices, "host": host}


def merge(intervals: Iterable[Sequence[float]]) -> List[List[float]]:
    """Union of [start, end] intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted((float(a), float(b)) for a, b in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _subtract(a: List[List[float]], b: List[List[float]]) -> float:
    """Length of the union ``a`` not covered by the union ``b``."""
    covered, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return _length(a) - covered


def _window(trace: dict) -> tuple:
    spans = [h for h in trace["host"] if h[0] == WINDOW]
    if spans:
        return min(h[1] for h in spans), max(h[2] for h in spans)
    ops = [o for ops in trace["devices"].values() for o in ops]
    return min(o[1] for o in ops), max(o[2] for o in ops)


def _label(trace: dict, t: float) -> str:
    best = None
    for name, s, e in trace["host"]:
        if s <= t <= e and name != WINDOW and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "host.unannotated"


def is_collective(name: str) -> bool:
    low = name.lower()
    return any(c in low for c in COLLECTIVE)


def _stats_name_collective(event) -> bool:
    """Whether one of the event's string stats (its HLO text or category,
    where the profiler records them) names an all-reduce."""
    try:
        stats = list(event.stats)
    except Exception:  # an event without readable stats says nothing
        return False
    return any(isinstance(v, str) and is_collective(v) for _, v in stats)


def _collective(op) -> bool:
    return is_collective(op[0]) or (len(op) > 3 and bool(op[3]))


def reduce(trace: dict) -> Optional[dict]:
    """The device numbers of one traced window (see the module docstring);
    ``None`` when the trace holds no device operation."""
    if not trace or not trace["devices"]:
        return None
    t0, t1 = _window(trace)
    n_dev = len(trace["devices"])
    busy = coll = exposed = 0.0
    op_ns: Dict[str, float] = {}
    gaps: List[list] = []
    for i, (_, ops) in enumerate(sorted(trace["devices"].items())):
        clipped = [[o[0], max(o[1], t0), min(o[2], t1), _collective(o)]
                   for o in ops if o[2] > t0 and o[1] < t1]
        union = merge([o[1], o[2]] for o in clipped)
        busy += _length(union)
        for n, s, e, _ in clipped:
            op_ns[n] = op_ns.get(n, 0.0) + (e - s)
        c_union = merge([s, e] for _, s, e, c in clipped if c)
        other = merge([s, e] for _, s, e, c in clipped if not c)
        coll += _length(c_union)
        exposed += _subtract(c_union, other)
        if i == 0:
            edges = [t0] + [x for iv in union for x in iv] + [t1]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    gaps.append([_label(trace, (s + e) / 2), (e - s) / 1e9])
    ops_top = sorted(([n, v / n_dev / 1e9] for n, v in op_ns.items()),
                     key=lambda kv: -kv[1])[:10]
    return {
        "devices": n_dev,
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy / n_dev / 1e9,
        "op_s": {n: v / n_dev / 1e9 for n, v in op_ns.items()},
        "collective_s": coll / n_dev / 1e9,
        "collective_exposed_s": exposed / n_dev / 1e9,
        "breakdown": {"device_ops": ops_top,
                      "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:10]},
    }


def op_seconds(reduced: dict, patterns: Sequence[str]) -> float:
    """Device seconds (per device) of the operations whose name holds any
    of ``patterns``."""
    return sum(v for n, v in reduced["op_s"].items()
               if any(p in n for p in patterns))
