"""The reading of the program's phase spans (``tppbench/phases.py``): on a
hand-made trace whose answers are worked out below, on the trace recorded
on a TPU v5e (which has no ``serve.*`` span, so the harness's naming and
every existing reader must read as before), and end to end on a traced
smoke run on the CPU (``phase_report.py``)."""

import importlib.util
import json
import random
from pathlib import Path

import pytest

from bench_testlib import CPU, REPO, checkout
from tppbench import phases, spec, trace
from tppbench.driver import Step
from tppbench.harness import RunView

FIXTURE = Path(__file__).parent / "data" / "trace_chat_v5e.json"
US = 1000  # the hand-made trace is written in microseconds
CHIP, HOST = "/device:TPU:0", "/host:CPU"


def _host(name, a, b):
    return trace.Event(HOST, "python3", name, a * US, (b - a) * US)


def _serve(name, a, b, **stats):
    return phases.Span(HOST, "python3", name, a * US, (b - a) * US, tuple(stats.items()))


def _op(a, b):
    return trace.Event(CHIP, trace.OPS, f"%fusion.{a} = f32[8]{{0}} fusion(", a * US,
                       (b - a) * US)


# Two decode steps with a prefill between them, as the harness and the
# program mark them; the device works in five intervals.
HARNESS = [_host("bench.generate", 0, 100), _host("bench.prefill", 100, 200),
           _host("bench.generate", 200, 300)]
PROGRAM = [
    _serve("serve.step", 2, 98, lanes=2),
    _serve("serve.flush", 4, 10, demote=3, promote=1),
    _serve("serve.score", 10, 14), _serve("serve.score.wait", 14, 30),
    _serve("serve.select", 30, 40, fast=6, slow=2),
    _serve("serve.tables", 40, 50, bp=2, mpp=8),
    _serve("serve.flush", 42, 46, demote=1, promote=0),  # an allocation settles a copy
    _serve("serve.dispatch", 50, 56), _serve("serve.tokens.wait", 56, 90),
    _serve("serve.policy", 90, 95), _serve("serve.interval", 95, 97),
    _serve("serve.prefill", 101, 199, rid=3, tokens=64),
    _serve("serve.prefill.forward", 102, 150), _serve("serve.prefill.write", 150, 198),
    _serve("serve.flush", 151, 153, demote=0, promote=2),  # the prompt's write flushes
    _serve("serve.step", 202, 298, lanes=2),
    _serve("serve.select", 210, 220, fast=8, slow=0), _serve("serve.tokens.wait", 230, 290),
]
OPS = [_op(12, 28), _op(52, 88), _op(110, 140), _op(160, 170), _op(240, 285)]


@pytest.fixture(scope="module")
def made():
    tr = trace.Trace(HARNESS + OPS)
    return tr, phases.Phases(tr, PROGRAM)


def test_step_host_ms(made):
    # step 1: 96 less its waits 16 + 34; step 2: 96 less 60
    assert made[1].step_host_ms() == pytest.approx((46 + 36) / 2 * US / 1e6)


def test_step_idle_ms(made):
    # step 1 [2, 98): busy 16 + 36; step 2 [202, 298): busy 45
    assert made[1].step_idle_ms() == pytest.approx((96 - 52 + 96 - 45) / 2 * US / 1e6)


def test_placement_host_ms_per_step(made):
    # self times: flush 6, select 10, flush 4 (inside the tables), policy 5,
    # interval 2, flush 2 (inside the prefill's write), select 10; over 2 steps
    assert made[1].placement_host_ms_per_step() == pytest.approx(39 / 2 * US / 1e6)


def test_prefill_idle_pct(made):
    # [101, 199): busy 30 + 10 of 98
    assert made[1].prefill_idle_pct() == pytest.approx(100 * 58 / 98)


def test_local_access_pct():
    assert phases.local_access_pct(30, 10) == 75.0
    assert phases.local_access_pct(0, 0) is None


def test_readings_leave_out_what_is_not_there(made):
    tr, ph = made
    assert set(phases.readings(ph, (3, 1))) == {
        "step_host_ms", "step_idle_ms", "placement_host_ms_per_step", "prefill_idle_pct",
        "local_access_pct"}
    host_only = phases.Phases(trace.Trace(HARNESS), PROGRAM)  # no device plane
    assert set(phases.readings(host_only)) == {"step_host_ms", "placement_host_ms_per_step"}
    assert phases.readings(phases.Phases(tr, [])) == {}  # a program without spans


def test_idle_gaps_name_the_innermost_span(made):
    tr, ph = made
    window = (0, 320 * US)
    got = {k: round(v * 1e9 / US, 6) for k, v in ph.idle_gaps(window, 20)}
    assert got == {
        "serve.flush": 12,  # [0, 12): middle 6 in the step's flush
        "serve.tables": 24,  # [28, 52): middle 40, where selection has ended
        "bench.generate": 22,  # [88, 110): middle 99, after the step's span
        "serve.prefill.write": 20,  # [140, 160): middle 150
        "serve.step": 70,  # [170, 240): middle 205, in no phase of the step
        "host.other": 35,  # [285, 320): middle 302, in no span
    }
    # the harness alone puts the same idle time under its own spans
    assert {k: round(v * 1e9 / US, 6) for k, v in tr.idle_gaps(window, 20)} == {
        "bench.generate": 128, "bench.prefill": 20, "host.other": 35}
    assert ph.events_per_step() == [11, 3]


def test_idle_by_phase_splits_gaps_among_phases(made):
    tr, ph = made
    got = {k: round(v * 1e9 / US, 6) for k, v in ph.idle_by_phase()}
    assert got == {
        "serve.step": 29, "serve.flush": 12, "serve.score": 2, "serve.score.wait": 2,
        "serve.select": 20, "serve.tables": 6, "serve.dispatch": 2, "serve.tokens.wait": 17,
        "serve.policy": 5, "serve.interval": 2, "serve.prefill": 2,
        "serve.prefill.forward": 18, "serve.prefill.write": 36}
    # with the 10 idle outside every program span, all of the window's idle time
    idle = sum(v for _, v in tr.idle_gaps((0, 300 * US), 20))
    assert sum(got.values()) + 10 == pytest.approx(idle * 1e9 / US)


def test_innermost_matches_a_scan_on_random_nested_spans():
    rng = random.Random(5)

    def nest(a, b, depth):
        out, t = [], a
        while depth and t < b - 2 and rng.random() < 0.8:
            s = rng.randrange(t, b - 1)
            e = rng.randrange(s + 1, b)
            out.append(_serve(f"serve.d{depth}", s, e))
            out += nest(s, e, depth - 1)
            t = e
        return out

    spans = nest(0, 2000, 4)
    n = phases.Nest(spans)
    for t in range(0, 2000 * US, 997):
        cover = [s for s in spans if s.start <= t < s.end]
        want = min(cover, key=lambda s: s.dur) if cover else None
        assert n.innermost(t) == want


def test_busy_inside_a_span_matches_a_sweep(made):
    _, ph = made
    for a, b in [(0, 300), (13, 27), (20, 60), (88, 110), (139, 161), (285, 320)]:
        want = sum(max(0, min(b, y) - max(a, x)) for x, y in
                   [(12, 28), (52, 88), (110, 140), (160, 170), (240, 285)])
        assert ph.busy_ns(a * US, b * US) == want * US


# -- the trace recorded on the chip: no program span in it --------------- #
@pytest.fixture(scope="module")
def recorded():
    return trace.Trace([trace.Event(**e) for e in json.loads(FIXTURE.read_text())])


def test_recorded_idle_gaps_read_as_before(recorded):
    win = trace.step_window(recorded)
    assert win == (262778057, 357420689)
    want = [["bench.generate", 0.0223734639999999], ["host.other", 9.187e-05]]
    assert recorded.idle_gaps(win, 10) == want
    assert phases.Phases(recorded, []).idle_gaps(win, 10) == want
    assert recorded.busy_s(win) == 0.072177298


@pytest.mark.parametrize("name,value", [
    ("decode_step_device_ms", 34.8644745),
    ("migrate_device_ms_per_step", 0.14881550000000002),
    ("decode_mfu_pct", 0.5818822276957303),
    ("paged_attention_roofline", 30.257380036027207),
    ("page_gather_roofline", 39.11187119532673),
    ("page_scatter_roofline", 36.29003901112745),
    ("migrated_pages_per_step", 35.0),
])
def test_recorded_readers_read_as_before(recorded, name, value):
    cell = spec.load(REPO, "phi3m-l2.chat-tiered")
    steps = [Step(0.0, 0.05, 32, [700 + 7 * b for b in range(32)]) for _ in recorded.steps]
    view = RunView(cell, [], steps, {"demoted": 40, "promoted": 30, "decode_steps": 2},
                   recorded, steps, "TPU v5 lite", {"migrated_pages": 70})
    assert spec.metric_reader(REPO, name)(view) == pytest.approx(value, rel=1e-12)


# -- a traced smoke run on the CPU, through phase_report.py -------------- #
def test_phase_report_reads_the_program_spans(tmp_path, monkeypatch, capsys):
    root = checkout(tmp_path)
    path = root / "benchmarks" / "chip" / "phase_report.py"
    mod_spec = importlib.util.spec_from_file_location("phase_report", path)
    report = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(report)
    from tppbench import harness

    monkeypatch.setattr(harness, "device_info", lambda chips: dict(CPU))
    saved = trace.events, harness.counters, harness.Driver
    assert report.main(["--workload", "smoke.cell", "--seeds", "6000000011",
                        "--seconds", "3", "--trace", "1", "--keep", str(tmp_path / "keep")]) == 0
    assert (trace.events, harness.counters, harness.Driver) == saved  # put back
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["result"]["correct"] is True and out["unfinished"] == []
    assert out["host_step_ms"]["n"] > 0
    assert 0 < out["local_access_pct"] <= 100
    ph = out["phases"]
    # no device plane on the CPU: the host readings only
    assert set(ph["readings"]) == {"step_host_ms", "placement_host_ms_per_step",
                                   "local_access_pct"}
    assert 0 < ph["readings"]["placement_host_ms_per_step"] < ph["readings"]["step_host_ms"]
    assert 9 <= ph["serve_spans_per_step"]["max"] <= 14
    assert ph["idle_by_phase"] == []  # no device plane: no idle time to place
    assert list((tmp_path / "keep").glob("smoke.cell.6000000011.xplane.pb"))
