"""Roofline analysis from the dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch x shape x mesh) cell, from experiments/dryrun/*.json:

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s      (197e12 bf16, v5e)
  memory term     = dot_bytes_per_device / HBM_bw           (819e9 B/s)
  collective term = wire_bytes_per_device / link_bw         (50e9 B/s ICI;
                    the 'pod' axis share would ride DCN — single-pod table
                    per assignment)

Sources: FLOPs and dot-bytes from the trip-count-aware HLO walker
(src/repro/analysis/hlo.py — XLA's cost_analysis visits scan bodies once, so it
is NOT usable directly); collective bytes from the partitioned HLO with ring
factors (all-reduce 2x). MODEL_FLOPS = 6ND (train) / 2ND (inference), MoE
active-params, embeddings + attention excluded (standard convention).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Optional

from repro.launch.mesh import device_peaks

# The dry-run cells model a v5e pod; per-link ICI bandwidth is this
# analysis's own assumption (not in the published per-chip table).
PEAK, HBM = device_peaks("TPU v5 lite")
ICI = 50e9

DRYRUN_DIR = Path(__file__).resolve().parent.parent / "experiments" / "dryrun"


def load_cells(dryrun_dir: Path = DRYRUN_DIR, mesh: Optional[str] = "pod16x16") -> List[Dict]:
    cells = []
    for f in sorted(dryrun_dir.glob("*.json")):
        data = json.loads(f.read_text())
        if mesh is not None and data.get("mesh") != mesh:
            continue
        cells.append(data)
    return cells


def terms(cell: Dict) -> Dict:
    n_dev = cell["n_devices"]
    flops_dev = cell["hlo"]["flops"]
    dot_bytes_dev = cell["hlo"]["dot_bytes"]
    coll_dev = cell["hlo"]["collective_bytes_total"]

    t_compute = flops_dev / PEAK
    t_memory = dot_bytes_dev / HBM
    t_coll = coll_dev / ICI
    dominant = max(
        (("compute", t_compute), ("memory", t_memory), ("collective", t_coll)),
        key=lambda kv: kv[1],
    )[0]
    model_flops = cell["model_flops"]
    hlo_total = flops_dev * n_dev
    useful = model_flops / hlo_total if hlo_total else 0.0
    # roofline fraction: useful model FLOPs per second achievable if the step
    # ran at the max of the three terms, vs the all-chips peak
    t_bound = max(t_compute, t_memory, t_coll)
    frac = (model_flops / t_bound) / (n_dev * PEAK) if t_bound else 0.0
    return {
        "arch": cell["arch"],
        "shape": cell["shape"],
        "mesh": cell["mesh"],
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops": model_flops,
        "hlo_flops_total": hlo_total,
        "useful_ratio": useful,
        "roofline_fraction": frac,
        "temp_gib": cell["memory"]["temp_bytes"] / 2**30,
        "args_gib": cell["memory"]["argument_bytes"] / 2**30,
    }


def table(dryrun_dir: Path = DRYRUN_DIR, mesh: str = "pod16x16") -> List[Dict]:
    return [terms(c) for c in load_cells(dryrun_dir, mesh)]


def markdown(rows: List[Dict]) -> str:
    hdr = ("| arch | shape | t_comp (s) | t_mem (s) | t_coll (s) | bound | "
           "useful | roofline | temp GiB |\n"
           "|---|---|---|---|---|---|---|---|---|\n")
    out = [hdr]
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.3f} | "
            f"{r['t_memory_s']:.3f} | {r['t_collective_s']:.3f} | "
            f"{r['dominant']} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_fraction']*100:.1f}% | {r['temp_gib']:.1f} |\n"
        )
    return "".join(out)


def run():
    from .common import emit

    for mesh in ("pod16x16", "pod2x16x16"):
        rows = table(mesh=mesh)
        for r in rows:
            emit(
                f"roofline.{mesh}.{r['arch']}.{r['shape']}",
                0.0,
                f"t_comp={r['t_compute_s']:.3f};t_mem={r['t_memory_s']:.3f};"
                f"t_coll={r['t_collective_s']:.3f};bound={r['dominant']};"
                f"useful={r['useful_ratio']:.2f};"
                f"roofline={r['roofline_fraction']*100:.1f}%",
            )
        if not rows:
            emit(f"roofline.{mesh}", 0.0, "NO_DRYRUN_ARTIFACTS(run launch/dryrun.py)")


if __name__ == "__main__":
    import sys

    mesh = sys.argv[1] if len(sys.argv) > 1 else "pod16x16"
    print(markdown(table(mesh=mesh)))
