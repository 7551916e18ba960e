"""Helpers of the benchmark's CPU tests: a checkout of the benchmark in a
temporary directory, with a smoke-size cell added by files alone."""

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMOKE_MODEL = {
    "name": "smoke", "architecture": "dense_gqa", "hidden_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 172, "vocab_size": 256, "rope_theta": 10000.0,
    "rotary_dim": 8, "rms_norm_eps": 1e-6, "attention_bias": True, "torch_dtype": "float32",
    "matmul_precision": "default", "control": "bfloat16",
}

SMOKE_MIX = {
    "loop": "open", "arrival_seed": 5, "block": 4, "prompt_len": {"64": 0.5, "96": 0.5},
    "output_len": {"dist": "uniform", "min": 24, "max": 72},
    "warm_traffic_s": 1.0, "drain_s": 60.0, "clients": 4,
    "engine": {"page_size": 16, "num_fast": 16, "num_slow": 256, "topk_pages": 4,
               "recent_pages": 2, "policy": "tpp", "demote_budget": 8, "promote_budget": 4,
               "max_seqs": 4},
    "check": {"sample": 3, "in_lanes": 2},
}

# On the CPU the program and the reference both compute in f32 and agree
# to rounding; the gap of a sound smoke run reads 0.
SMOKE_CELL = {"rate_rps": 12.0,
              "limits": {"logit_gap": 1e-3, "page_shortfall": 1e-3, "v0_rel_err": 1e-4}}

CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}

# two decode steps of the chat cell (phi3-medium-14b-l2, 32 lanes) with the
# flush programs before each, recorded on a TPU v5e
TRACE_FIXTURE = Path(__file__).parent / "data" / "trace_chat_v5e.json"


def checkout(tmp: Path, *, model=None, mix=None, cell=None, name="smoke.cell",
             config="smoke", traffic="smoke-mix") -> Path:
    """Copy of the benchmark with one more configuration, mix and cell,
    each added as files and entries only."""
    root = Path(tmp) / "checkout"
    b = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, b, ignore=shutil.ignore_patterns("__pycache__"))
    (b / "configs" / f"{config}.json").write_text(json.dumps(model or SMOKE_MODEL))
    (b / "traffic" / f"{traffic}.json").write_text(json.dumps(mix or SMOKE_MIX))
    (b / "cells" / f"{name}.json").write_text(json.dumps(cell or SMOKE_CELL))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": config, "source": "test", "reduced": [], "why": "test",
                             "file": f"benchmarks/chip/configs/{config}.json"})
    bench["workloads"].append({"name": name, "config": config, "traffic": traffic,
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:  # measured as the chat cell is
        if "phi3m-l2.chat-tiered" in m.get("workloads", [name]):
            m.setdefault("workloads", []).append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run(root: Path, seed: int, seconds: float, traced: bool = False, name="smoke.cell"):
    import time

    from tppbench import harness, spec

    cell = spec.load(root, name)
    lines = []
    result = harness.run_cell(cell, seed, seconds, traced, time.perf_counter(), dict(CPU),
                              log=lines.append)
    return result, lines


def traced_view(root: Path, name: str, lens):
    """A finished traced window of cell ``name`` over the recorded trace:
    each step serves lanes with ``lens`` tokens cached, five more a step,
    and 70 pages migrate while it is traced."""
    from tppbench import spec, trace
    from tppbench.driver import Step
    from tppbench.harness import RunView

    tr = trace.Trace([trace.Event(**e) for e in json.loads(TRACE_FIXTURE.read_text())])
    steps = [Step(0.0, 0.05, len(lens), [t + 5 * i for t in lens])
             for i in range(len(tr.steps))]
    return RunView(spec.load(root, name), [], steps, {"decode_steps": len(steps)}, tr,
                   steps, "TPU v5 lite", {"migrated_pages": 70})
