"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (one per measurement).

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only table1,...]
                                          [--skip-slow]
                                          [--engine reference|vectorized]

``--only`` runs a comma-separated subset of suites; ``--skip-slow``
drops the long-running ones (the fast lane CI and developers iterate
on).  ``--engine`` selects the placement engine for the
simulator-backed benchmarks (results are identical by construction —
see ``tests/test_engine_parity.py``; the vectorized engine is the fast
one).
"""

from __future__ import annotations

import argparse
import inspect
import sys
import time

from repro.core.engine import ENGINES
from repro.launch.compile_cache import enable_compile_cache

# (key, module, slow, entrypoint) — slow suites are multi-minute
# end-to-end sweeps; the rest finish in seconds and form the
# --skip-slow fast lane.  ``entrypoint`` names the module function to
# call (several suites can live in one module).
MODULES = [
    ("table1", "benchmarks.table1_throughput", True, "run"),
    ("chameleon", "benchmarks.chameleon_heatmap", False, "run"),
    ("ablations", "benchmarks.fig_ablation", True, "run"),
    ("table2", "benchmarks.table2_type_aware", False, "run"),
    ("table3", "benchmarks.table3_tmo", True, "run"),
    ("expert_tier", "benchmarks.expert_tiering", True, "run"),
    ("engine", "benchmarks.engine_bench", True, "run"),
    ("qos", "benchmarks.qos_bench", False, "run"),
    ("qos_controller", "benchmarks.qos_bench", False, "run_controller"),
    ("fleet", "benchmarks.fleet_bench", False, "run"),
    ("serving", "benchmarks.serving_bench", True, "run"),
    ("traffic", "benchmarks.traffic_bench", True, "run"),
    ("kernels", "benchmarks.kernel_bench", False, "run"),
]


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: "
                         + ",".join(k for k, _, _, _ in MODULES))
    ap.add_argument("--skip-slow", action="store_true",
                    help="skip the multi-minute suites ("
                         + ",".join(k for k, _, s, _ in MODULES if s) + ")")
    ap.add_argument("--engine", default="reference", choices=list(ENGINES),
                    help="placement engine for simulator-backed benchmarks")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - {k for k, _, _, _ in MODULES}
        if unknown:
            ap.error(f"unknown suite(s) {sorted(unknown)}; choose from "
                     + ",".join(k for k, _, _, _ in MODULES))

    import importlib

    print("name,us_per_call,derived")
    t0 = time.time()
    failed: list = []
    for key, modname, slow, entrypoint in MODULES:
        if only and key not in only:
            continue
        if args.skip_slow and slow and not only:
            continue  # an explicit --only overrides --skip-slow
        try:
            mod = importlib.import_module(modname)
            fn = getattr(mod, entrypoint)
            kwargs = {"quick": args.quick}
            if "engine" in inspect.signature(fn).parameters:
                kwargs["engine"] = args.engine
            for line in fn(**kwargs):
                print(line, flush=True)
        except Exception as e:  # keep the suite going; a failure is visible
            print(f"{key}/ERROR,0.0,{type(e).__name__}:{e}", flush=True)
            failed.append(key)
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:  # after the full sweep, so one bad suite never hides others
        sys.exit(f"benchmark suite(s) failed: {','.join(failed)}")


if __name__ == "__main__":
    main()
