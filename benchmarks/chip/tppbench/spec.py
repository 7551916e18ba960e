"""A cell of ``BENCHMARK.json``, and every piece of it found by name.

* configuration: the ``file`` of its entry under ``configs``;
* traffic mix: ``benchmarks/chip/traffic/<traffic>.json``;
* numbers of the cell itself (offered rate, the limit of the check):
  ``benchmarks/chip/cells/<workload>.json``, where the cell has any;
* per-layer metric: ``benchmarks/chip/metrics/<metric>.py``, whose
  ``read(run)`` returns a number or ``None`` when it finds nothing to read;
* an architecture's adapter to the program: ``tppbench/arch/<architecture>.py``;
  its plain reference: ``benchmarks/chip/references/<REFERENCE>.py``.

An adapter defines ``REFERENCE``, ``leaves(model)`` (``tppbench/weights.py``),
``model_config(model)``, ``program_params(weights)``, and ``work(model,
engine)``: the work the architecture counts, which the per-layer readers
divide by measured time. ``work`` returns an object with

* ``decode_flops(cur_lens)``: model FLOPs of one decode step over its live
  lanes, ``cur_lens`` being the tokens each lane had cached before it
  (``driver.Step.cur_lens``);
* ``attention(cur_lens) -> (flops, bytes)``: the paged attention kernel's
  counted work in that step, over every layer;
* ``migrate_bytes(pages)``: the bytes that one direction's copy programs
  (``page_gather`` or ``page_scatter``) move over every store for
  ``pages`` migrated pages.

An adapter that lacks one of these is an error that names its file.

A new cell, mix, configuration, metric or architecture is new files and new
entries; no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path("benchmarks") / "chip"
ADAPTER = ("REFERENCE", "leaves", "model_config", "program_params", "work")


@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: str
    traffic: str
    model: dict
    mix: dict
    numbers: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def engine(self) -> dict:
        return self.mix["engine"]


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load(root: Path, workload: str) -> Cell:
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    numbers_file = root / BENCH / "cells" / f"{workload}.json"
    return Cell(
        root=root, name=workload, chips=int(w["chips"]), config=w["config"],
        traffic=w["traffic"],
        model=json.loads((root / cfg["file"]).read_text()),
        mix=json.loads((root / BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        numbers=json.loads(numbers_file.read_text()) if numbers_file.exists() else {},
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
    )


def _module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ident(name: str) -> str:
    return name.replace(".", "_").replace("-", "_")


def metric_reader(root: Path, name: str):
    return _module(Path(root) / BENCH / "metrics" / f"{name}.py",
                   "tppbench_metric_" + _ident(name)).read


def reference(root: Path, name: str) -> ModuleType:
    return _module(Path(root) / BENCH / "references" / f"{name}.py", "tppbench_ref_" + name)


def architecture(root: Path, name: str) -> ModuleType:
    path = Path(root) / BENCH / "tppbench" / "arch" / f"{name}.py"
    mod = _module(path, "tppbench_arch_" + _ident(name))
    missing = [n for n in ADAPTER if not hasattr(mod, n)]
    if missing:
        raise AttributeError(f"{path}: the adapter lacks {', '.join(missing)}")
    return mod


def read_metrics(cell: Cell, run) -> Dict[str, dict]:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in cell.per_layer:
        value = metric_reader(cell.root, m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
