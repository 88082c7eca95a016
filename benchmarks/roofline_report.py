"""Roofline table from dry-run artifacts (results/dryrun/*.json).

One row per (arch × shape × mesh): the three roofline terms, dominant
bottleneck, useful-FLOPs ratio — EXPERIMENTS.md §Roofline is generated from
this.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import List, Optional, Tuple


def load_results(outdir: str = "results/dryrun") -> List[dict]:
    rows = []
    for p in sorted(Path(outdir).glob("*.json")):
        try:
            rows.append(json.loads(p.read_text()))
        except json.JSONDecodeError:
            continue
    return rows


def table(outdir: str = "results/dryrun_final",
          mesh: Optional[str] = None) -> List[Tuple[str, float, str]]:
    if not Path(outdir).is_dir():
        raise FileNotFoundError(f"{outdir}: no dry-run results (run "
                                f"python -m repro.launch.dryrun first)")
    rows = []
    for r in load_results(outdir):
        if r.get("status") == "skipped":
            rows.append((f"dryrun_{r['arch']}_{r['shape']}_skip", 0.0,
                         "skipped: " + r.get("reason", "")[:60]))
            continue
        if r.get("status") != "ok":
            rows.append((f"dryrun_{r['arch']}_{r['shape']}_{r.get('mesh')}",
                         0.0, "FAILED"))
            continue
        if mesh and r["mesh"] != mesh:
            continue
        rf = r["roofline"]
        name = f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}"
        t_max = max(rf["t_compute_s"], rf["t_memory_s"], rf["t_collective_s"])
        derived = (f"c={rf['t_compute_s']:.3g}s m={rf['t_memory_s']:.3g}s "
                   f"x={rf['t_collective_s']:.3g}s "
                   f"dom={rf['bottleneck']} "
                   f"frac={rf['roofline_fraction']:.3f} "
                   f"useful={rf['useful_flops_ratio']:.2f}")
        rows.append((name, t_max * 1e6, derived))
    return rows


def markdown_table(outdir: str = "results/dryrun_final") -> str:
    lines = ["| arch | shape | mesh | t_compute | t_memory | t_collective | "
             "bottleneck | roofline frac | useful ratio |",
             "|---|---|---|---|---|---|---|---|---|"]
    for r in load_results(outdir):
        if r.get("status") != "ok":
            continue
        rf = r["roofline"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {rf['t_compute_s']:.4g}s | {rf['t_memory_s']:.4g}s "
            f"| {rf['t_collective_s']:.4g}s | {rf['bottleneck']} "
            f"| {rf['roofline_fraction']:.3f} "
            f"| {rf['useful_flops_ratio']:.2f} |")
    return "\n".join(lines)
