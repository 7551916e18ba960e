"""One run of one cell: set-up, the measured window, the check.

Set-up makes the weights from the seed on the device, builds the program's
``ServingEngine`` (batched plane, TPP) and ``SlotEngine``, warms every shape
the cell's traffic uses, and serves ``warm_traffic_s`` seconds of the
cell's own traffic so that lanes and tiers are at steady occupancy. The
window then serves the same stream for ``seconds``. Afterwards the
program's state is freed and the plain reference judges a sample of the
requests it finished (``tppbench/check.py``).
"""

from __future__ import annotations

import collections
import gc
import statistics
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from tppbench import check, counts, spec, traffic, weights
from tppbench.driver import Driver

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE_START_SHARE = 0.3  # the traced part of the window starts here ...
TRACE_SECONDS = 5.0  # ... and lasts this long (or to the window's end)


class CompileCounter:
    """Counts lowerings (every new program, cached or not) and compiles."""

    def __init__(self) -> None:
        self.lowerings = 0
        self.compiles = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == LOWERING:
            self.lowerings += 1
        elif event == BACKEND_COMPILE:
            self.compiles += 1


def configure_jax(root: Path) -> None:
    """Persistent compilation cache at a fixed path in the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), keeping even sub-second
    compiles, such as the small programs of the prefill's write."""
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(Path(root) / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int) -> Dict:
    """The accelerator as JAX reports it; raises where it is not a TPU with
    enough chips or has no entry in the table of peaks."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < chips:
        raise RuntimeError(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    counts.peaks(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def bucket(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


def quantile(values: List[float], q: int) -> Optional[float]:
    """The q-th percentile (``statistics.quantiles``, inclusive)."""
    if not values:
        return None
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=100, method="inclusive")[q - 1]


# --------------------------------------------------------------------- #
# the program
# --------------------------------------------------------------------- #
def build(cell: spec.Cell, seed: int):
    """Weights from the seed, then the program's engine over them, at the
    matrix-product precision the configuration states."""
    import jax

    from repro.core import TppConfig
    from repro.serving import EngineConfig, ServingEngine
    from repro.traffic.slots import SlotEngine

    precision = cell.model["matmul_precision"]
    jax.config.update("jax_default_matmul_precision",
                      None if precision == "default" else precision)
    arch = spec.architecture(cell.root, cell.model["architecture"])
    e = cell.engine
    w = weights.make(arch.leaves(cell.model), seed, cell.model["torch_dtype"])
    eng = ServingEngine(
        arch.model_config(cell.model), arch.program_params(w),
        EngineConfig(page_size=e["page_size"], num_fast=e["num_fast"], num_slow=e["num_slow"],
                     topk_pages=e["topk_pages"], recent_pages=e["recent_pages"],
                     policy=e["policy"],
                     tpp=TppConfig(demote_budget=e["demote_budget"],
                                   promote_budget=e["promote_budget"]),
                     max_seqs=e["max_seqs"], data_plane="batched"),
        seed=int(seed) % (1 << 31),
    )
    # The page-summary capacity only grows, and each growth recompiles the
    # decode step: set it to the bucket the longest request reaches.
    longest = max(traffic.prompt_lengths(cell.mix)) + int(cell.mix["output_len"]["max"])
    eng._grow_summaries(bucket(-(-longest // e["page_size"])))
    return SlotEngine(eng)


def first_step_entries(plen: int, e: dict) -> int:
    """Pages the program's first decode step of a ``plen``-token prompt
    attends: the older pages up to top-k, the recent ones, and a page
    opened for the token it writes."""
    P = e["page_size"]
    pages = -(-(plen - 1) // P)
    older = max(pages - e["recent_pages"], 0)
    return (min(older, e["topk_pages"]) + min(pages, e["recent_pages"])
            + int((plen - 1) % P == 0))


def warm_shapes(slots, cell: spec.Cell, seed: int) -> None:
    """Compile (or load) every program the window can call: prefill at each
    prompt length of the mix, the decode step at every batch bucket with
    its widest block table, the page scores at every batch size, and the
    release. Lanes are filled with every prompt length, then released one
    per step down to one. In an open loop a batch can also hold only fresh
    lanes of the shortest prompt; where their block table falls into a
    smaller bucket, each batch bucket is also stepped once with such lanes."""
    lengths = traffic.prompt_lengths(cell.mix)
    rng = np.random.default_rng([int(seed) % (1 << 64), 2])
    vocab = int(cell.model["vocab_size"])
    e = cell.engine
    n = slots.n_slots

    def fill(plens, max_new):
        rids = [slots.prefill(rng.integers(0, vocab, p).tolist(), max_new=max_new)
                for p in plens]
        for i, rid in enumerate(rids):
            slots.insert(rid, i)

    fill([lengths[i % len(lengths)] for i in range(n)], n + 2)
    for i in range(n - 1, -1, -1):
        slots.generate()
        slots.release(i)
    widest = e["topk_pages"] + e["recent_pages"] + 1
    if (cell.mix["loop"] == "open"
            and bucket(first_step_entries(lengths[0], e)) < bucket(widest)):
        b = 1
        while b <= n:
            fill([lengths[0]] * b, 2)
            slots.generate()
            for i in range(b):
                slots.release(i)
            b = min(2 * b, n) if b < n else n + 1


def record_choices(engine) -> Dict[int, List[List[int]]]:
    """Keep the pages the program chooses to attend at every decode step,
    per request, as indices into the request's pages."""
    chosen: Dict[int, List[List[int]]] = collections.defaultdict(list)
    select = engine._select_pages

    def recording(seq, older_scores):
        sel = select(seq, older_scores)
        chosen[seq.rid].append([seq.pages.index(p) for p in sel])
        return sel

    engine._select_pages = recording
    return chosen


def counters(slots) -> Dict[str, int]:
    vs = slots.engine.kv.pool.vmstat
    return {"demoted": int(vs.pgdemote_total), "promoted": int(vs.pgpromote_total),
            "migrated_pages": int(slots.engine.kv.migrated_pages)}


# --------------------------------------------------------------------- #
# what a run leaves for the metric readers
# --------------------------------------------------------------------- #
class RunView:
    """A finished window, as the per-layer metric readers see it."""

    def __init__(self, cell, requests, steps, counter_delta, trace=None,
                 traced_steps=None, device_kind="", traced_counters=None,
                 paused=None) -> None:
        self.cell = cell
        arch = spec.architecture(cell.root, cell.model["architecture"])
        self.work = arch.work(cell.model, cell.engine)  # the counts the readers divide
        self.counts = counts
        self.peak = counts.peaks(device_kind) if device_kind else None
        self.requests = requests  # counted requests
        self.steps = steps  # decode steps of the window
        self.counters = counter_delta
        self.trace = trace  # tppbench.trace.Trace of the traced part, or None
        self.traced_steps = traced_steps or []  # host steps matching trace.steps
        self.traced_counters = traced_counters or {}  # counter deltas while traced
        self.paused = paused or []  # (start, end) of the profiler's start and stop


def end_to_end(cell: spec.Cell, counted, steps, window: float, setup_s: float) -> Dict:
    ttft = [1e3 * (r.first - r.due) for r in counted if r.first is not None]
    tpot = [1e3 * (r.last - r.first) / (r.tokens - 1) for r in counted if r.tokens > 1]
    tokens = sum(s.lanes for s in steps if 0.0 < s.end <= window)
    values = {"tokens_per_s": tokens / window, "ttft_p95_ms": quantile(ttft, 95),
              "tpot_p95_ms": quantile(tpot, 95), "setup_s": setup_s}
    out = {}
    for m in cell.end_to_end:
        if values.get(m["name"]) is not None:
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def _p(values, q):
    v = quantile(values, q)
    return "none" if v is None else f"{v:.6f}"


# --------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------- #
def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool, t_start: float,
             device: Dict, log: Callable[[str], None] = print,
             clock: Callable[[], float] = time.perf_counter) -> Dict:
    import jax

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    mix = cell.mix
    vocab = int(cell.model["vocab_size"])
    warm_s = float(mix["warm_traffic_s"])
    drain_s = float(mix["drain_s"])

    slots = build(cell, seed)
    warm_shapes(slots, cell, seed)
    chosen = record_choices(slots.engine)

    # in the traced run, prefill's host time ends with its K/V on the device
    sync = (lambda: jax.block_until_ready(slots.engine.kv.k_store)) if traced else None

    # Set-up leaves some million objects behind; frozen, the collector's
    # full passes in the window scan only what the window makes, instead of
    # stalling the host for tenths of a second.
    gc.collect()
    gc.freeze()
    t_zero = clock() + warm_s
    if mix["loop"] == "open":
        rate = float(cell.numbers["rate_rps"])
        schedule = traffic.open_schedule(mix, seed, vocab, rate, -warm_s, seconds + drain_s)
        drv = Driver(slots, t_zero, schedule=schedule, sync=sync, annotate=traced,
                     clock=clock)
    else:
        drv = Driver(slots, t_zero, source=traffic.requests(mix, seed, vocab),
                     clients=int(mix["clients"]), sync=sync, annotate=traced, clock=clock)
        drv.start_clients(-warm_s)
    drv.run(0.0)
    setup_s = t_zero - t_start
    lowered0, compiled0 = counter.lowerings, counter.compiles
    c0 = counters(slots)
    first_step = len(drv.steps)

    trace = None
    traced_steps: List = []
    trace_dir = None
    traced_delta: Dict[str, int] = {}
    paused: List = []  # (start, end) of the profiler's own calls, window seconds
    if traced:
        from tppbench import trace as trace_mod

        a = TRACE_START_SHARE * seconds
        drv.run(a)
        trace_dir = tempfile.mkdtemp(prefix="tppbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        i0 = len(drv.steps)
        t0 = counters(slots)
        p0 = drv.now()
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        paused.append((p0, drv.now()))
        drv.run(min(seconds, a + TRACE_SECONDS))
        p0 = drv.now()
        jax.profiler.stop_trace()
        paused.append((p0, drv.now()))
        t1 = counters(slots)
        traced_delta = {k: t1[k] - t0[k] for k in t0}
        traced_steps = drv.steps[i0:]
    drv.run(seconds)
    window = drv.now()
    c1 = counters(slots)
    steps = drv.steps[first_step:]
    delta = {k: c1[k] - c0[k] for k in c0}
    delta["decode_steps"] = len(steps)

    # Requests due in the window are counted; the load goes on after its
    # close until each has finished and a lane holds a request for the
    # check to read, for at most ``drain_s``.
    while ((drv.pending(seconds) or not any(r.tokens for r in drv.lanes.values()))
           and drv.now() < seconds + drain_s):
        drv.run(min(seconds + drain_s, drv.now() + 0.25))
    lowered = counter.lowerings - lowered0
    compiled = counter.compiles - compiled0
    jax.monitoring.unregister_event_duration_listener(counter)

    for r in drv.sent:
        r.counted = 0.0 <= r.due < seconds
    counted = [r for r in drv.sent if r.counted]
    failed = [r for r in counted if not r.done]
    metrics = end_to_end(cell, counted, steps, window, setup_s)

    ttft = [1e3 * (r.first - r.due) for r in counted if r.first is not None]
    tpot = [1e3 * (r.last - r.first) / (r.tokens - 1) for r in counted if r.tokens > 1]
    log(f"window_s {window:.6f} setup_s {setup_s:.6f} decode_steps {len(steps)} "
        f"drained_s {drv.now() - window:.6f}")
    log(f"ttft_ms p50 {_p(ttft, 50)} p95 {_p(ttft, 95)} n {len(ttft)}")
    log(f"tpot_ms p50 {_p(tpot, 50)} p95 {_p(tpot, 95)} n {len(tpot)}")
    log(f"requests sent {len(drv.sent)} counted {len(counted)} failed {len(failed)} "
        f"in_flight_at_end {len(drv.lanes)} queued_at_end {len(drv.queue)}")
    if drv.lateness:
        log(f"generator_lateness_ms p50 {_p([1e3 * x for x in drv.lateness], 50)} "
            f"max {1e3 * max(drv.lateness):.6f}")
    log(f"compiles_in_window lowered {lowered} compiled {compiled}")
    log(f"migrations_in_window demoted {delta['demoted']} promoted {delta['promoted']} "
        f"pages {delta['migrated_pages']}")

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device_out = dict(device)
    device_out["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    breakdown = None
    if traced:
        from tppbench import trace as trace_mod

        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        trace = trace_mod.Trace(trace_mod.events(str(files[-1]))) if files else None
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
        n = min(len(trace.steps), len(traced_steps)) if trace else 0
        log(f"trace generate_spans {len(trace.steps) if trace else 0} "
            f"host_steps {len(traced_steps)} profiler_held_s "
            + " ".join(f"{b - a:.6f}" for a, b in paused))
        view = RunView(cell, counted, steps, delta, trace, traced_steps[:n],
                       device["kind"], traced_delta, paused)
        metrics = spec.read_metrics(cell, view)
        win = trace_mod.step_window(trace) if trace else None
        if win and trace.chips:
            device_out["busy_s"] = trace.busy_s(win)
            device_out["window_s"] = (win[1] - win[0]) / 1e9
            breakdown = {"device_ops": trace.top_ops(10),
                         "idle_gaps": trace.idle_gaps(win, 10)}

    # the check, once the program's state is freed
    finished = [r for r in drv.sent if r.done and r.due >= 0.0]
    snaps = check.snapshot(slots, [r for r in drv.lanes.values() if r.tokens], seed,
                           int(mix["check"]["in_lanes"]))
    drv.slots = None
    del slots, drv, sync
    gc.unfreeze()
    gc.collect()
    verdict = check.judge(cell, seed, finished, snaps, chosen, log=log)

    result = {"correct": verdict["correct"], "attempted": len(counted),
              "failed": len(failed), "metrics": metrics, "device": device_out}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if "control_correct" in verdict:
        result["control_correct"] = verdict["control_correct"]
    result["check"] = verdict["numbers"]
    return result
