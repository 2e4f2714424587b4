"""Device time under the solver's named scopes, read from a profiler trace.

The program marks the phases of an epoch with ``jax.named_scope``
(``dfw.loss``, ``dfw.power``, ``comm.exchange``, ``dfw.linesearch``,
``dfw.update``). The scopes reach the compiled program as each HLO
instruction's ``op_name`` metadata, and the profiler copies that name stack
into a string stat of every device operation. ``load`` finds the stat that
holds it and keeps, for every device operation under a scope, a row
``[device, scopes, start_ns, end_ns]``: ``scopes`` is the operation's scopes
from outermost to innermost, joined by ``/``. On the TPU that stat belongs
to the operation's event metadata, which ``ProfileData`` does not expose,
so ``load`` also reads the metadata from the trace file itself. The rows go under the key
``scopes`` of a trace loaded by ``trace_reduce.load_xspace``, and
``scope_seconds`` reduces them, inside the traced window, to the seconds of
device time under each scope: the union of the operations' intervals,
averaged over the devices, so that a loop nested in a scope, and the
operations inside the loop, are counted once.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

from bench import trace_reduce

SCOPES = ("dfw.loss", "dfw.power", "comm.exchange", "dfw.linesearch", "dfw.update")


def scopes_of(text: str) -> Tuple[str, ...]:
    """The known scopes in a name stack such as
    ``jit(step)/while/body/dfw.power/comm.exchange/psum``, outermost first."""
    return tuple(part for part in re.split(r"[/\s:;,()]+", text) if part in SCOPES)


def event_scopes(stats: Iterable) -> Tuple[Optional[str], Tuple[str, ...]]:
    """The first string stat of an event whose value names a scope, and the
    scopes it names; ``(None, ())`` for an operation under none."""
    for key, value in stats:
        if isinstance(value, str):
            found = scopes_of(value)
            if found:
                return str(key), found
    return None, ()


def _stats(event) -> list:
    try:
        return list(event.stats)
    except Exception:  # an event without readable stats names no scope
        return []


# A minimal reader of the XSpace protocol buffer (tsl/profiler/protobuf/
# xplane.proto): ``ProfileData`` gives each event its own stats only, and on
# the TPU the name stack is a stat of the event's metadata.


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of a message: an int for a
    varint, a memoryview for anything else."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, value


def _map_entry(buf) -> Tuple[int, Optional[memoryview]]:
    key, value = 0, None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def metadata_scopes(data: bytes) -> Dict[str, dict]:
    """For each device plane of a serialized XSpace: the scopes named by the
    string stats of each event metadata, keyed by its name and its display
    name, and the names of the stats the metadata carry."""
    out: Dict[str, dict] = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:
                metas.append(_map_entry(v)[1])
            elif pf == 5:
                key, sm = _map_entry(v)
                stat_names[key] = next((bytes(x).decode() for g, x in _fields(sm)
                                        if g == 2), "")
        if not (name.startswith("/device:") and "TPU" in name
                and "NON_CORE" not in name.upper()):
            continue
        by_name: Dict[str, Tuple[str, Tuple[str, ...]]] = {}
        seen_stats = set()
        for meta in metas:
            names, stats = [], []
            for mf, v in _fields(meta):
                if mf in (2, 4):
                    names.append(bytes(v).decode(errors="replace"))
                elif mf == 5:
                    sid, value = 0, None
                    for sf, sv in _fields(v):
                        if sf == 1:
                            sid = sv
                        elif sf == 5:
                            value = bytes(sv).decode(errors="replace")
                        elif sf == 7:
                            value = stat_names.get(sv)
                    seen_stats.add(stat_names.get(sid, str(sid)))
                    stats.append((stat_names.get(sid, str(sid)), value))
            key, found = event_scopes(stats)
            if found:
                for n in names:
                    by_name[n] = (key, found)
        out[name] = {"events": by_name, "stats": sorted(seen_stats)}
    return out


def load(path: str, samples: int = 4) -> dict:
    """The scoped device operations of an ``.xplane.pb`` file, on the planes
    and lines that ``trace_reduce.load_xspace`` reads: ``{"scopes": rows,
    "scope_stat": the stat that held the name stack (None when no
    operation is scoped), "stat_samples": the stats of the first
    ``samples`` operations and of the first scoped one, and "lines" and
    "metadata_stats": the names of each device plane's lines and of the
    stats its event metadata carry, to look at by hand}``. An operation's
    scopes are read from its own stats, else from its metadata's."""
    from jax.profiler import ProfileData

    with open(path, "rb") as fh:
        data = fh.read()
    meta = metadata_scopes(data)
    rows: List[list] = []
    seen: Dict[str, int] = {}
    first: List[dict] = []
    lines: Dict[str, list] = {}
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if plane.name not in meta:
            continue
        plane_lines = list(plane.lines)
        lines[plane.name] = [ln.name for ln in plane_lines]
        by_name = meta[plane.name]["events"]
        for line in trace_reduce._ops_line(plane_lines):
            for e in line.events:
                if e.duration_ns <= 0:
                    continue
                stats = _stats(e)
                key, found = event_scopes(stats)
                if not found:
                    key, found = by_name.get(e.name, (None, ()))
                if len(first) < samples or (found and not seen):
                    first.append({"name": e.name, "stats": [
                        [str(k), v if isinstance(v, (int, float)) else str(v)[:300]]
                        for k, v in stats]})
                if found:
                    seen[key] = seen.get(key, 0) + 1
                    rows.append([plane.name, "/".join(found),
                                 float(e.start_ns), float(e.end_ns)])
    stat = max(seen, key=seen.get) if seen else None
    return {"scopes": sorted(rows, key=lambda r: r[2]), "scope_stat": stat,
            "stat_samples": first, "lines": lines,
            "metadata_stats": {k: v["stats"] for k, v in meta.items()}}


def scope_seconds(trace: dict) -> Dict[str, float]:
    """Seconds of device time under each scope inside the traced window,
    averaged over the devices; empty when the trace holds no scoped
    operation."""
    rows = trace.get("scopes") or []
    if not rows or not trace.get("devices"):
        return {}
    t0, t1 = trace_reduce._window(trace)
    n_dev = len(trace["devices"])
    out: Dict[str, float] = {}
    for scope in SCOPES:
        total = 0.0
        for dev in trace["devices"]:
            spans = [[max(s, t0), min(e, t1)] for d, names, s, e in rows
                     if d == dev and scope in names.split("/") and e > t0 and s < t1]
            total += sum(e - s for s, e in trace_reduce.merge(spans))
        if total > 0:
            out[scope] = total / n_dev / 1e9
    return out
