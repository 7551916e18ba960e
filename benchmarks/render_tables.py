"""Render EXPERIMENTS.md tables from results artifacts.

  PYTHONPATH=src python -m benchmarks.render_tables dryrun   # §D1 table
  PYTHONPATH=src python -m benchmarks.render_tables bench results/bench_output.txt
"""

from __future__ import annotations

import json
import os
import sys
from collections import defaultdict

HERE = os.path.dirname(__file__)
DRYRUN = os.path.join(HERE, "..", "results", "dryrun_results.json")


def _fmt_bytes(n):
    if n >= 1e9:
        return f"{n/1e9:.2f}G"
    if n >= 1e6:
        return f"{n/1e6:.1f}M"
    return f"{n/1e3:.0f}K"


def dryrun_table() -> str:
    with open(DRYRUN) as f:
        cells = json.load(f)
    by = defaultdict(dict)
    skips = set()
    for c in cells:
        if c.get("status") == "skipped":
            skips.add((c["arch"], c["shape"]))
            continue
        by[(c["arch"], c["shape"])][c.get("mesh", "-")] = c
    for key in skips:
        by.setdefault(key, {"skip": True})
    lines = [
        "| arch | shape | 16×16 | 2×16×16 | args/dev | act-peak est | CPU temp (UB) | collective/dev |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for (arch, shape), meshes in sorted(by.items()):
        if meshes.get("skip"):
            lines.append(f"| {arch} | {shape} | skip | skip | — | — | — | — |")
            continue
        sp = meshes.get("16x16", {})
        mp = meshes.get("2x16x16", {})
        s1 = "✓" if sp.get("status") == "ok" else "✗"
        s2 = "✓" if mp.get("status") == "ok" else ("—" if not mp else "✗")
        lines.append(
            f"| {arch} | {shape} | {s1} ({sp.get('compile_s','-')}s) | {s2} "
            f"({mp.get('compile_s','-')}s) | "
            f"{_fmt_bytes(sp.get('argument_size_in_bytes', 0))} | "
            f"{_fmt_bytes(sp.get('act_peak_est', 0))} | "
            f"{_fmt_bytes(sp.get('temp_size_in_bytes', 0))} | "
            f"{_fmt_bytes(sp.get('collective_total', 0))} |"
        )
    return "\n".join(lines)


def bench_table(path: str) -> str:
    lines = ["| benchmark | derived |", "|---|---|"]
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith(("name,", "#")):
                continue
            parts = line.split(",", 2)
            if len(parts) == 3:
                lines.append(f"| `{parts[0]}` | {parts[2]} |")
    return "\n".join(lines)


if __name__ == "__main__":
    what = sys.argv[1] if len(sys.argv) > 1 else "dryrun"
    if what == "dryrun":
        print(dryrun_table())
    elif what == "bench":
        print(bench_table(sys.argv[2]))
