"""A configuration, a traffic mix, a cell, a per-layer metric and an
architecture are files found by name: adding them edits no file of the
benchmark."""

import hashlib
import itertools
import json

import pytest

from bench_testlib import BENCH, SMOKE_MIX, SMOKE_MODEL, checkout, traced_view
from tppbench import counts, spec, traffic


def _digest(root):
    files = sorted(p for p in (root / "benchmarks" / "chip").rglob("*") if p.is_file()
                   and "__pycache__" not in p.parts)
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in files}


def test_new_pieces_are_found_by_name(tmp_path):
    mix = dict(SMOKE_MIX, prompt_len={"32": 0.25, "48": 0.75}, block=8)
    root = checkout(tmp_path, model=dict(SMOKE_MODEL, name="tiny", hidden_size=32),
                    mix=mix, cell={"rate_rps": 3.0}, name="tiny.cell", config="tiny",
                    traffic="tiny-mix")
    before = _digest(root)
    # a per-layer metric: its reader file and its entry
    (root / "benchmarks" / "chip" / "metrics" / "requests_counted.py").write_text(
        "def read(run):\n    return float(len(run.requests)) if run.requests else None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "requests_counted", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "front end", "moves": "tpot_p95_ms",
                               "workloads": ["tiny.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before  # nothing edited

    cell = spec.load(root, "tiny.cell")
    assert cell.model["hidden_size"] == 32 and cell.numbers["rate_rps"] == 3.0
    assert cell.mix["prompt_len"] == {"32": 0.25, "48": 0.75}
    assert [m["name"] for m in cell.per_layer][-1] == "requests_counted"
    assert spec.architecture(root, cell.model["architecture"]).REFERENCE == "dense_gqa"
    reqs = list(itertools.islice(traffic.requests(cell.mix, 1, 100), 16))
    assert sorted({len(r.prompt) for r in reqs}) == [32, 48]

    class View:  # what a finished window hands the readers
        requests = reqs[:5]
        trace = None
        counters = {"decode_steps": 0}

    # the other readers find nothing to read in this view and are left out
    assert spec.read_metrics(cell, View()) == {
        "requests_counted": {"value": 5.0, "unit": "requests"}}


def test_the_real_cells_resolve():
    for name in ("phi3m-l2.chat-tiered", "glm3-l6.batch-short"):
        cell = spec.load(BENCH.parents[1], name)
        assert cell.engine["max_seqs"] == 32
        assert cell.model["head_dim"] == 128


# An architecture that keeps one latent of ``latent_width`` per token and
# layer in a single store, and counts its work by formulas of its own.
TOY_ADAPTER = '''
from tppbench.arch.dense_gqa import REFERENCE, leaves, model_config, program_params


class Work:
    def __init__(self, m, engine):
        self.layers = int(m["num_hidden_layers"])
        self.page = int(engine["page_size"]) * int(m["latent_width"]) * 4

    def decode_flops(self, cur_lens):
        return 1e9 * len(cur_lens)

    def attention(self, cur_lens):
        return 0.0, float(self.layers * self.page * len(cur_lens))

    def migrate_bytes(self, pages):
        return 2.0 * pages * self.layers * self.page


def work(m, engine):
    return Work(m, engine)
'''

COUNTING = ("decode_mfu_pct", "paged_attention_roofline", "page_gather_roofline",
            "page_scatter_roofline")


def _adapter(root, name, text):
    path = root / "benchmarks" / "chip" / "tppbench" / "arch" / f"{name}.py"
    path.write_text(text)
    return path


def test_a_new_architecture_counts_its_own_work(tmp_path):
    model = dict(SMOKE_MODEL, name="toy", architecture="toy", latent_width=576)
    root = checkout(tmp_path, model=model, name="toy.cell", config="toy", traffic="toy-mix")
    before = _digest(root)
    added = _adapter(root, "toy", TOY_ADAPTER)
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before  # nothing edited
    assert set(after) - set(before) == {str(added.relative_to(root))}

    view = traced_view(root, "toy.cell", [100 + b for b in range(32)])
    assert type(view.work).__name__ == "Work" and view.work.page == 16 * 576 * 4
    got = {m: spec.metric_reader(root, m)(view) for m in COUNTING}

    tr, peak = view.trace, counts.peaks("TPU v5 lite")
    window = (tr.steps[-1].end - tr.steps[0].start) / 1e9
    assert got["decode_mfu_pct"] == pytest.approx(
        100 * 2 * 32e9 / (window * peak["flops_per_s"]), rel=1e-12)
    attn_s = sum(tr.step_seconds(tr.ops, lambda n: "paged_attention" in n))
    attn_bytes = 2 * 2 * 16 * 576 * 4 * 32  # two steps, two layers, one page a lane
    assert got["paged_attention_roofline"] == pytest.approx(
        100 * attn_bytes / peak["hbm_bytes_per_s"] / attn_s, rel=1e-12)
    for kernel in ("page_gather", "page_scatter"):
        seconds = sum(e.dur for e in tr.calls(tr.ops, lambda n: kernel in n)) / 1e9
        assert got[f"{kernel}_roofline"] == pytest.approx(
            100 * (2 * 70 * 2 * 16 * 576 * 4) / peak["hbm_bytes_per_s"] / seconds,
            rel=1e-12)  # 70 pages migrated, two layers, read and written


def test_an_adapter_without_work_is_an_error(tmp_path):
    model = dict(SMOKE_MODEL, name="bare", architecture="bare")
    root = checkout(tmp_path, model=model, name="bare.cell", config="bare", traffic="bare-mix")
    _adapter(root, "bare", TOY_ADAPTER[: TOY_ADAPTER.index("class Work")])
    with pytest.raises(AttributeError, match=r"bare\.py: the adapter lacks work"):
        traced_view(root, "bare.cell", [100])
