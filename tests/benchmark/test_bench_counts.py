"""The benchmark's operation and byte counts against hand-worked values, and
the four counting readers pinned to the numbers they read before the
counts moved into the architecture's adapter."""

import json

import pytest

from bench_testlib import BENCH, REPO, traced_view
from tppbench import counts, spec
from tppbench.arch import dense_gqa


def _files(config, mix):
    model = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    engine = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())["engine"]
    return model, engine


def shapes(config, mix):
    return counts.shapes(*_files(config, mix))


def work(config, mix):
    return dense_gqa.work(*_files(config, mix))


PHI3 = ("phi3-medium-14b-l2", "chat-poisson")
GLM3 = ("chatglm3-6b-l6", "batch-short")


@pytest.mark.parametrize("cfg, params", [
    # 2 x (5120*60*128 + 5120*5120 + 3*5120*17920) + 5120*32064
    (PHI3, 845_742_080),
    # 6 x (4096*36*128 + 4096*4096 + 3*4096*13696) + 4096*65024
    (GLM3, 1_490_026_496),
])
def test_matmul_params_per_token(cfg, params):
    assert counts.matmul_params_per_token(shapes(*cfg)) == params


@pytest.mark.parametrize("t, pages, tokens", [
    # 63 pages hold positions < 1000: top 4 older + 2 recent; the write
    # page (992..1007) is the last recent one and holds 992..1000
    (1000, 6, 5 * 16 + 9),
    # 64 full pages: top 4 + 2 recent, all full, plus a new write page
    (1024, 7, 6 * 16 + 1),
    # 4 pages hold 0..62: both older pages, both recent; 47..62 + 63
    (63, 4, 3 * 16 + 16),
    (64, 5, 4 * 16 + 1),
])
def test_attended_pages_and_tokens(t, pages, tokens):
    assert counts.attended(shapes(*PHI3), t) == (pages, tokens)


def test_paged_attention_call_phi3():
    flops, nbytes = work(*PHI3).attention([1000, 1024])
    # per layer: 4 * H * D * tokens; K and V of each selected page + q and
    # output; the work is of both layers
    assert flops == 2 * 4 * 40 * 128 * (89 + 97)
    page = 10 * 16 * 128 * 4
    assert nbytes == 2 * (2 * page * (6 + 7) + 2 * (2 * 40 * 128 * 4))


def test_decode_flops_glm3():
    w = work(*GLM3)
    assert w.decode_flops([1000]) == 2 * 1_490_026_496 + 6 * 4 * 32 * 128 * 89


@pytest.mark.parametrize("cfg, per_page", [
    # K and V stores x layers x kv heads x page x head x f32, read + write
    (PHI3, 2 * 2 * 2 * 10 * 16 * 128 * 4),
    (GLM3, 2 * 2 * 6 * 2 * 16 * 128 * 4),
])
def test_page_copy_bytes(cfg, per_page):
    assert work(*cfg).migrate_bytes(1) == per_page
    assert work(*cfg).migrate_bytes(64) == 64 * per_page


def test_roofline_share_names_its_bound():
    peak = counts.peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    share, bound = counts.roofline_share(0.0, 819e9, 2.0, peak)
    assert bound == "bytes" and share == pytest.approx(50.0)
    share, bound = counts.roofline_share(197e12, 1.0, 1.0, peak)
    assert bound == "flops" and share == pytest.approx(100.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("cpu")


# Cached lengths that reach every branch of ``attended`` (under one page,
# on a page boundary, fewer pages than recent + top-k, more), shifted by
# five tokens a step.
LENS = ([1, 15, 16, 17, 31, 32, 33, 63, 64, 65, 95, 96, 97, 255, 256, 1000, 1023, 1024,
         1025, 2047, 2048, 2049] + [300 + 97 * b for b in range(10)])

# What each reader returned on this view at the parent of the move, where
# the readers took ``counts`` over ``counts.shapes`` themselves.
PINNED = {
    ("phi3m-l2.chat-tiered", "decode_mfu_pct"): 0.5816454037169756,
    ("phi3m-l2.chat-tiered", "paged_attention_roofline"): 25.76370973364693,
    ("phi3m-l2.chat-tiered", "page_gather_roofline"): 39.11187119532673,
    ("phi3m-l2.chat-tiered", "page_scatter_roofline"): 36.29003901112745,
    ("glm3-l6.batch-short", "decode_mfu_pct"): 1.025393521061793,
    ("glm3-l6.batch-short", "paged_attention_roofline"): 17.61518758533069,
    ("glm3-l6.batch-short", "page_gather_roofline"): 23.467122717196034,
    ("glm3-l6.batch-short", "page_scatter_roofline"): 21.774023406676466,
}


@pytest.mark.parametrize("workload, metric", sorted(PINNED))
def test_counting_readers_read_as_before(workload, metric):
    # the recorded chat trace times the kernels; the steps' lengths are set
    view = traced_view(REPO, workload, LENS)
    assert spec.metric_reader(REPO, metric)(view) == pytest.approx(
        PINNED[workload, metric], rel=1e-12, abs=0)
