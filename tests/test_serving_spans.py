"""The serving engine's phase spans (``serve.*``), read back from the
profiler's trace on the CPU, and the engine unchanged by a profiler."""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.core import TppConfig
from repro.models.model import init_params
from repro.serving import EngineConfig, ServingEngine
from repro.serving.kv_cache import bucket

STEPS = 14
STEP_CHILDREN = ("serve.flush", "serve.score", "serve.score.wait", "serve.select",
                 "serve.tables", "serve.dispatch", "serve.tokens.wait", "serve.policy",
                 "serve.interval")


def _serve(cfg, params):
    """A batched engine that migrates pages: three prompts, steps, a finish."""
    eng = ServingEngine(cfg, params, EngineConfig(
        page_size=4, num_fast=10, num_slow=64, recent_pages=1, data_plane="batched",
        tpp=TppConfig(demote_budget=16, promote_budget=8)), seed=0)
    rng = np.random.default_rng(7)
    rids = [eng.add_request(list(rng.integers(0, cfg.vocab, n)), max_new=40)
            for n in (30, 17, 9)]
    tokens = [eng.step() for _ in range(STEPS)]
    eng.finish(rids[1])
    tokens.append(eng.step())
    return eng, rids, tokens


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = get_smoke_config("tinyllama-1.1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    plain = _serve(cfg, params)
    logdir = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(logdir):
        traced = _serve(cfg, params)
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    spans = sorted(((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                     dict(e.stats))
                    for plane in ProfileData.from_file(path).planes
                    for line in plane.lines for e in line.events
                    if e.name.startswith("serve.")), key=lambda s: (s[1], -s[2]))
    return plain, traced, spans


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(spans, outer):
    return [s for s in spans if outer[1] <= s[1] and s[2] <= outer[2] and s is not outer]


def test_steps_hold_their_phases(served):
    _, (eng, _, _), spans = served
    steps = _named(spans, "serve.step")
    assert len(steps) == STEPS + 1 == eng.steps
    assert [s[3]["lanes"] for s in steps] == [3] * STEPS + [2]
    for step in steps:
        names = [s[0] for s in _inside(spans, step)]
        for child in ("serve.select", "serve.tables", "serve.dispatch",
                      "serve.tokens.wait", "serve.policy"):
            assert names.count(child) == 1, (child, names)
        assert set(names) <= set(STEP_CHILDREN)
        assert len(names) + 1 <= 14  # phase-level only, never per lane or page
    assert len(_named(spans, "serve.interval")) == eng.steps // 4
    # every phase of a decode step lies inside one
    for s in spans:
        if s[0] in STEP_CHILDREN and s[0] != "serve.flush":
            assert any(st[1] <= s[1] and s[2] <= st[2] for st in steps), s


def test_selection_and_tables_carry_their_counts(served):
    _, _, spans = served
    for s in _named(spans, "serve.select"):
        assert s[3]["fast"] + s[3]["slow"] > 0
    for s in _named(spans, "serve.tables"):
        assert s[3]["bp"] in (2, 4) and s[3]["mpp"] in (2, 4, 8)  # power-of-two buckets


def test_prefill_carries_rid_and_tokens(served):
    _, (eng, rids, _), spans = served
    pre = _named(spans, "serve.prefill")
    assert [(s[3]["rid"], s[3]["tokens"]) for s in pre] == list(zip(rids, (30, 17, 9)))
    page = eng.ecfg.page_size
    assert [s[3]["bucket"] for s in pre] == [max(bucket(n - 1), page) for n in (30, 17, 9)]
    for p in pre:
        assert [s[0] for s in _inside(spans, p) if s[0].startswith("serve.prefill.")] == \
            ["serve.prefill.forward", "serve.prefill.write"]
    (fin,) = _named(spans, "serve.finish")
    assert fin[3]["rid"] == rids[1]


def test_flush_appears_once_migrations_are_staged(served):
    _, (eng, _, _), spans = served
    vm = eng.kv.pool.vmstat
    assert vm.pgdemote_total + vm.pgpromote_total > 0
    flushes = _named(spans, "serve.flush")
    assert flushes
    # staged copies still pending, or dropped with a finished request, are never flushed
    assert 0 < sum(s[3]["demote"] + s[3]["promote"] for s in flushes) <= eng.kv.migrated_pages
    assert all(s[3]["demote"] + s[3]["promote"] > 0 for s in flushes)


def test_profiler_changes_no_token_and_no_counter(served):
    (plain, _, plain_tokens), (traced, _, traced_tokens), _ = served
    assert plain_tokens == traced_tokens
    assert plain.kv.pool.vmstat.as_dict() == traced.kv.pool.vmstat.as_dict()
    assert plain.kv.migrated_pages == traced.kv.migrated_pages > 0
