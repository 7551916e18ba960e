"""Runs of a cell with the program's phase spans read, many seeds in one process.

    python3 benchmarks/chip/phase_report.py --workload <name> --seeds 1,2,3 --seconds 51 \
        --trace <0|1> [--keep <dir>]

Each seed is one run of the cell as ``run.py`` makes it, check included.
Besides that result and the lines the run logs, one JSON line per seed
gives the host durations of the decode steps in the part of the window a
traced run traces (``host_step_ms``: median, count), the requests counted
but unfinished with their due time, lengths and times (seconds from the
window's start, as is the end of the last decode step), and the share of
attended pages that sat in the fast tier over the window. With
``--trace 1`` it also reads the ``serve.*`` spans of the trace
(``tppbench/phases.py``): the idle gaps named by program phase, the idle
time inside each phase, the five phase readings and the ``serve.*`` spans
per decode step; ``--keep`` copies the trace file into a directory. Over
a program without those spans the phase readings are left out.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep")
    args = ap.parse_args(argv)
    from tppbench import harness, phases, spec, trace
    from tppbench.driver import Driver

    cell = spec.load(ROOT, args.workload)
    harness.configure_jax(ROOT)
    device = harness.device_info(cell.chips)

    # What run_cell makes and drops, kept here: the trace's events and the
    # program's spans, the access counters at each reading, the driver.
    seen: dict = {}
    saved = trace.events, harness.counters, harness.Driver
    events, counters = saved[:2]

    def keeping_events(path):
        seen["program"] = phases.spans(path)
        if args.keep:
            Path(args.keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(path, Path(args.keep) / f"{args.workload}.{seen['seed']}.xplane.pb")
        seen["events"] = events(path)
        return seen["events"]

    def keeping_counters(slots):
        vs = slots.engine.kv.pool.vmstat
        seen["access"].append((int(vs.access_fast), int(vs.access_slow)))
        return counters(slots)

    class KeptDriver(Driver):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            seen["driver"] = self

    trace.events, harness.counters, harness.Driver = keeping_events, keeping_counters, KeptDriver
    try:
        lo = harness.TRACE_START_SHARE * args.seconds
        hi = min(args.seconds, lo + harness.TRACE_SECONDS)
        for seed in (int(s) for s in args.seeds.split(",")):
            seen.clear()
            seen.update(seed=seed, access=[])
            t, lines = time.perf_counter(), []
            result = harness.run_cell(cell, seed, args.seconds, bool(args.trace), t, device,
                                      log=lines.append)
            drv = seen["driver"]
            steps = [1e3 * (s.end - s.start) for s in drv.steps if lo <= s.start < hi]
            (f0, s0), (f1, s1) = seen["access"][0], seen["access"][-1]
            out = {"seed": seed, "trace": args.trace, "result": result,
                   "host_step_ms": {"median": statistics.median(steps) if steps else None,
                                    "n": len(steps)},
                   "local_access_pct": phases.local_access_pct(f1 - f0, s1 - s0),
                   "unfinished": [{"due": r.due, "prompt": len(r.prompt), "max_new": r.max_new,
                                   "tokens": r.tokens, "prefill_start": r.prefill_start,
                                   "first": r.first, "last": r.last}
                                  for r in drv.sent if r.counted and not r.done],
                   "last_step_end_s": drv.steps[-1].end if drv.steps else None,
                   "lines": lines}
            if args.trace and "events" in seen:
                tr = trace.Trace(seen["events"])
                ph = phases.Phases(tr, seen["program"])
                win = trace.step_window(tr)
                per_step = ph.events_per_step()
                out["phases"] = {
                    "readings": phases.readings(ph, (f1 - f0, s1 - s0)),
                    "idle_gaps": ph.idle_gaps(win, 20) if win else [],
                    "idle_by_phase": ph.idle_by_phase(),
                    "harness_idle_gaps": tr.idle_gaps(win, 20) if win else [],
                    "serve_spans_per_step": {"max": max(per_step, default=0),
                                             "mean": statistics.fmean(per_step) if per_step
                                             else 0.0},
                    "serve_spans": len(seen["program"]),
                }
            out["seconds"] = time.perf_counter() - t
            print(json.dumps(out), flush=True)
    finally:
        trace.events, harness.counters, harness.Driver = saved
    print(f"total_s {time.perf_counter() - T0:.1f}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
