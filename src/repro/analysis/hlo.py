"""Post-SPMD HLO text analysis: FLOPs, dot memory traffic, collective bytes.

(Formerly ``repro.launch.hlo_analysis``; it moved here when the declarative
contract checker ``repro.analysis.contracts`` was built on top of it — the
walker is a correctness tool, not an execution-layer one. The compat
re-export at the old path has been deleted; lint rule ``REP007`` keeps it
from coming back.)

Why not just ``compiled.cost_analysis()``: XLA's HloCostAnalysis visits each
while-loop (lax.scan) body ONCE, so a 48-layer scanned model reports ~1/48 of
its real FLOPs. This walker parses ``compiled.as_text()`` (the partitioned,
per-device module), builds the computation call graph, extracts loop trip
counts from the loop-condition compare constants, and multiplies.

Per-device quantities returned:
  flops            — 2*M*N*K summed over dot ops (+ trivial conv terms)
  dot_bytes        — operand+output bytes of every dot (each matmul streams
                     its tiles through VMEM once; upper bound that ignores
                     fusion, lower bound that ignores spills)
  collective_bytes — wire bytes per device by collective type, with ring
                     factors: all-reduce 2x, all-gather/reduce-scatter 1x
                     (of the large shape), all-to-all & permute 1x
  collective_count — op counts by type (executed, i.e. trip-multiplied)

The walker also parses each collective's ``replica_groups`` (both the
explicit ``{{0,1},{2,3}}`` and the iota ``[G,S]<=[N]`` HLO spellings), which
is what distinguishes a hierarchical topology's cheap intra-group psum from
its expensive inter-group exchange. ``partition_crossing_bytes`` classifies
every collective's wire bytes against a device partition (e.g. hosts):
bytes of collectives whose replica groups stay inside one cell are
``local``, the rest ``crossing`` — the measured quantity behind the
``benchmarks/gossip_consensus.py`` inter-byte gate.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)\(")
_CALLED_RE = re.compile(r"(?:condition|body|to_apply|calls)=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERANDS_RE = re.compile(r"\(\s*(%[\w.\-]+(?:\s*,\s*%[\w.\-]+)*)?\s*\)")
_CONST_RE = re.compile(r"%?([\w.\-]+)\s*=\s*s32\[\]\s+constant\((\d+)\)")
_COMPARE_RE = re.compile(r"compare\(([^)]*)\),?.*direction=(LT|LE|GT|GE)")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{(\{[^{}]*\}(?:,\{[^{}]*\})*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[(\d+)\](\S*)")
_PERMUTE_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")


def parse_replica_groups(line: str) -> Optional[List[List[int]]]:
    """The replica groups of one collective op line, or ``None`` when the op
    carries none (= one group spanning every participant).

    Handles the explicit form ``replica_groups={{0,1,2,3},{4,5,6,7}}`` and
    the iota form ``replica_groups=[G,S]<=[N]`` (G groups of S consecutive
    ids). An iota spelling with a trailing reshape/transpose suffix is not
    decoded — returned as ``None`` rather than guessed wrong. For a
    ``collective-permute`` the ``source_target_pairs`` are returned as
    2-element groups, so crossing classification sees every hop."""
    m = _GROUPS_EXPLICIT_RE.search(line)
    if m:
        groups = []
        for grp in re.findall(r"\{([^{}]*)\}", m.group(1)):
            ids = [int(v) for v in grp.split(",") if v.strip() != ""]
            if ids:
                groups.append(ids)
        return groups or None
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        g, s, n, suffix = int(m.group(1)), int(m.group(2)), int(m.group(3)), m.group(4)
        if suffix or g * s != n:
            return None
        return [[grp * s + j for j in range(s)] for grp in range(g)]
    m = _PERMUTE_PAIRS_RE.search(line)
    if m:
        pairs = re.findall(r"\{(\d+),(\d+)\}", m.group(1))
        return [[int(a), int(b)] for a, b in pairs] or None
    return None


def _groups_key(groups: Optional[List[List[int]]]) -> str:
    if groups is None:
        return "all"
    return ";".join(",".join(str(i) for i in g) for g in groups)


def _shape_bytes(shape_str: str) -> int:
    """Bytes of 'f32[1,2,3]' or a tuple '(f32[..], bf16[..])'."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(shape_str: str) -> List[int]:
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Costs:
    flops: float = 0.0
    dot_bytes: float = 0.0
    coll_bytes: Dict[str, float] = dataclasses.field(default_factory=dict)
    coll_count: Dict[str, float] = dataclasses.field(default_factory=dict)
    # (op_type, shape_str) -> [executed_count, wire_bytes_total]
    coll_detail: Dict[Tuple[str, str], List[float]] = dataclasses.field(
        default_factory=dict
    )
    # (op_type, shape_str, groups_key) -> [executed_count, wire_bytes_total]
    coll_groups: Dict[Tuple[str, str, str], List[float]] = dataclasses.field(
        default_factory=dict
    )

    def add(self, other: "Costs", mult: float = 1.0) -> None:
        self.flops += other.flops * mult
        self.dot_bytes += other.dot_bytes * mult
        for k, v in other.coll_bytes.items():
            self.coll_bytes[k] = self.coll_bytes.get(k, 0.0) + v * mult
        for k, v in other.coll_count.items():
            self.coll_count[k] = self.coll_count.get(k, 0.0) + v * mult
        for k, (c, b) in other.coll_detail.items():
            cur = self.coll_detail.setdefault(k, [0.0, 0.0])
            cur[0] += c * mult
            cur[1] += b * mult
        for k, (c, b) in other.coll_groups.items():
            cur = self.coll_groups.setdefault(k, [0.0, 0.0])
            cur[0] += c * mult
            cur[1] += b * mult


class HloModule:
    def __init__(self, text: str):
        self.computations: Dict[str, List[str]] = {}
        self.entry: Optional[str] = None
        self._parse(text)

    def _parse(self, text: str) -> None:
        cur = None
        for line in text.splitlines():
            stripped = line.strip()
            m = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*(\([^)]*\))?\s*->.*\{", stripped)
            if m and not stripped.startswith("%"):
                pass
            header = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{\s*$", stripped)
            if header:
                cur = header.group(2)
                self.computations[cur] = []
                if header.group(1):
                    self.entry = cur
                continue
            if stripped == "}":
                cur = None
                continue
            if cur is not None and stripped:
                self.computations[cur].append(stripped)

    # -- trip counts ------------------------------------------------------
    def trip_count(self, cond_comp: str) -> float:
        """Loop bound from the condition computation. XLA often hides the
        compare inside a wrapped fusion, so the robust extraction is: the
        largest scalar s32 constant in the condition body (loop bounds dwarf
        the 0/1 step constants). Falls back to 1."""
        lines = self.computations.get(cond_comp, [])
        best = 0
        for ln in lines:
            m = _CONST_RE.search(ln)
            if m:
                best = max(best, int(m.group(2)))
        return float(best) if best > 0 else 1.0

    # -- cost walk ---------------------------------------------------------
    def _own_and_children(self, comp: str) -> Tuple[Costs, List[Tuple[str, float]]]:
        costs = Costs()
        children: List[Tuple[str, float]] = []
        shapes: Dict[str, str] = {}
        lines = self.computations.get(comp, [])
        # first pass: op -> shape
        for ln in lines:
            m = _OP_RE.match(ln)
            if m:
                shapes[m.group(1)] = m.group(2)
        for ln in lines:
            m = _OP_RE.match(ln)
            if not m:
                continue
            name, shape_str, op = m.groups()
            if op == "dot":
                out_dims = _shape_dims(shape_str)
                out_elems = 1
                for d in out_dims:
                    out_elems *= d
                # contracted size: lhs elements / (out elems sans rhs free)…
                # robust route: lhs shape * rhs shape / out shape gives
                # (contract^2 * batch) — instead read contracting dims:
                k = self._dot_contract_size(ln, shapes)
                costs.flops += 2.0 * out_elems * k
                costs.dot_bytes += _shape_bytes(shape_str) + sum(
                    _shape_bytes(shapes.get(o, "")) for o in self._operands(ln)
                )
            elif op == "convolution":
                # depthwise/small convs in this codebase: bound by output*kernel
                out_elems = 1
                for d in _shape_dims(shape_str):
                    out_elems *= d
                costs.flops += 2.0 * out_elems * 8  # kernel<=4, 2 ops
            elif op in COLLECTIVES:
                nbytes = _shape_bytes(shape_str)
                if op == "all-reduce":
                    wire = 2.0 * nbytes
                elif op == "reduce-scatter":
                    ops_ = self._operands(ln)
                    wire = float(sum(_shape_bytes(shapes.get(o, "")) for o in ops_) or nbytes)
                else:  # all-gather / all-to-all / collective-permute
                    wire = float(nbytes)
                costs.coll_bytes[op] = costs.coll_bytes.get(op, 0.0) + wire
                costs.coll_count[op] = costs.coll_count.get(op, 0.0) + 1.0
                det = costs.coll_detail.setdefault((op, shape_str), [0.0, 0.0])
                det[0] += 1.0
                det[1] += wire
                gkey = _groups_key(parse_replica_groups(ln))
                grp = costs.coll_groups.setdefault(
                    (op, shape_str, gkey), [0.0, 0.0])
                grp[0] += 1.0
                grp[1] += wire
            if op == "while":
                called = _CALLED_RE.findall(ln)
                cond = body = None
                for c in called:
                    if "cond" in c or c.endswith("condition"):
                        cond = cond or c
                for mm in re.finditer(r"(condition|body)=%?([\w.\-]+)", ln):
                    if mm.group(1) == "condition":
                        cond = mm.group(2)
                    else:
                        body = mm.group(2)
                trips = self.trip_count(cond) if cond else 1.0
                if body:
                    children.append((body, trips))
            elif op in ("fusion", "call", "conditional", "reduce", "map",
                        "reduce-window", "scatter", "select-and-scatter", "sort",
                        "custom-call"):
                for c in _CALLED_RE.findall(ln):
                    children.append((c, 1.0))
                mb = _BRANCHES_RE.search(ln)
                if mb:
                    for c in mb.group(1).split(","):
                        children.append((c.strip().lstrip("%"), 1.0))
        return costs, children

    def _operands(self, line: str) -> List[str]:
        """Operand names of an op line: the %refs inside 'op(...)' only
        (never the metadata)."""
        m = _OP_RE.match(line)
        if not m:
            return []
        op = m.group(3)
        idx = line.find(op + "(", m.end(3) - len(op) - 1)
        if idx < 0:
            idx = line.find(op + "(")
        start = idx + len(op) + 1
        end = line.find(")", start)
        if end < 0:
            end = len(line)
        return re.findall(r"%([\w.\-]+)", line[start:end])

    def _dot_contract_size(self, line: str, shapes: Dict[str, str]) -> float:
        mc = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
        ops = self._operands(line)
        if not mc or not ops:
            return 1.0
        lhs_dims = _shape_dims(shapes.get(ops[0], ""))
        k = 1.0
        for d in mc.group(1).split(","):
            if d and int(d) < len(lhs_dims):
                k *= lhs_dims[int(d)]
        return k

    def total_costs(self) -> Costs:
        memo: Dict[str, Costs] = {}

        def walk(comp: str) -> Costs:
            if comp in memo:
                return memo[comp]
            memo[comp] = Costs()  # cycle guard
            own, children = self._own_and_children(comp)
            total = Costs()
            total.add(own)
            for child, mult in children:
                if child in self.computations:
                    total.add(walk(child), mult)
            memo[comp] = total
            return total

        entry = self.entry or max(self.computations, key=lambda c: len(self.computations[c]))
        return walk(entry)


def tpu_kernel_calls(hlo_text: str) -> int:
    """Static call sites of Pallas/Mosaic kernels in a compiled module: 0
    means every kernel-routed op fell back to plain XLA."""
    return hlo_text.count('custom_call_target="tpu_custom_call"')


def analyze(hlo_text: str, top_k: int = 12) -> Dict:
    mod = HloModule(hlo_text)
    c = mod.total_costs()
    top = sorted(c.coll_detail.items(), key=lambda kv: -kv[1][1])[:top_k]
    return {
        "flops": c.flops,
        "dot_bytes": c.dot_bytes,
        "collective_bytes": c.coll_bytes,
        "collective_bytes_total": float(sum(c.coll_bytes.values())),
        "collective_count": c.coll_count,
        "top_collectives": [
            {"op": op, "shape": shape, "count": cnt, "wire_bytes": b}
            for (op, shape), (cnt, b) in top
        ],
        "collective_groups": [
            {"op": op, "shape": shape, "groups": gkey,
             "count": cnt, "wire_bytes": b}
            for (op, shape, gkey), (cnt, b) in sorted(
                c.coll_groups.items(), key=lambda kv: -kv[1][1])
        ],
    }


def partition_crossing_bytes(
    hlo_text: str, partition: List[List[int]]
) -> Dict:
    """Classify every collective's wire bytes against a device partition.

    ``partition`` is a list of disjoint device-id cells (e.g. the per-host
    groups ``[[0,1,2,3],[4,5,6,7]]``). A collective whose every replica
    group stays inside one cell is ``local`` — it never touches the
    boundary; everything else (including collectives with no
    ``replica_groups``, which span all participants) is ``crossing`` and
    contributes its full wire bytes. That makes ``crossing`` an upper bound
    on inter-cell traffic — the right *relative* measure for comparing
    topologies compiled at identical sizes, which is how the
    ``gossip_consensus`` benchmark gates the hier inter-byte saving.

    Returns ``{"crossing": bytes, "local": bytes, "crossing_count": n,
    "local_count": n, "by_op": {op: crossing_bytes}}``.
    """
    cell_of: Dict[int, int] = {}
    for ci, cell in enumerate(partition):
        for dev in cell:
            cell_of[int(dev)] = ci
    c = HloModule(hlo_text).total_costs()
    out = {"crossing": 0.0, "local": 0.0,
           "crossing_count": 0.0, "local_count": 0.0}
    by_op: Dict[str, float] = {}
    for (op, _shape, gkey), (cnt, wire) in c.coll_groups.items():
        if gkey == "all":
            local = len(partition) <= 1
        else:
            local = True
            for grp in gkey.split(";"):
                cells = {cell_of.get(int(i), -1) for i in grp.split(",")}
                if len(cells) > 1:
                    local = False
                    break
        if local:
            out["local"] += wire
            out["local_count"] += cnt
        else:
            out["crossing"] += wire
            out["crossing_count"] += cnt
            by_op[op] = by_op.get(op, 0.0) + wire
    out["by_op"] = by_op
    return out
