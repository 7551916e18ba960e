"""Serving driver: batched requests over the TPP-tiered KV cache.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
      --requests 4 --prompt-len 48 --max-new 32 --policy tpp

Drives :class:`repro.serving.ServingEngine` on its batched data plane
(continuous batching, paged two-tier KV, TPP placement; the decode step
runs ``kernels.paged_attention`` and migrations run ``page_gather`` /
``page_scatter``) and prints throughput on the device JAX reports plus
placement stats.  ``chip_smoke.py`` calls :func:`serve` the same way.
"""

from __future__ import annotations

import argparse
import time
from typing import Any, Dict, List, Tuple

import jax
import numpy as np

from repro.core import TppConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import init_params
from repro.serving import EngineConfig, ServingEngine


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--policy", default="tpp",
                    choices=["tpp", "linux", "numa_balancing", "autotiering"])
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--num-fast", type=int, default=48)
    ap.add_argument("--num-slow", type=int, default=256)
    ap.add_argument("--topk-pages", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def serve(args: argparse.Namespace) -> Tuple[ServingEngine, Dict[str, Any]]:
    """Serve ``args.requests`` random prompts to completion.

    Returns the engine and its stats, extended with the outputs, the
    tokens generated and the host-clock seconds of set-up (params, engine,
    prefill), of the first decode step (which compiles the step) and of
    the later steps (which compile again where a shape grows).
    """
    from repro.configs import get_config, get_smoke_config

    t0 = time.perf_counter()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    eng = ServingEngine(
        cfg, params,
        EngineConfig(
            page_size=args.page_size, num_fast=args.num_fast,
            num_slow=args.num_slow, topk_pages=args.topk_pages,
            policy=args.policy,
            tpp=TppConfig(demote_budget=64, promote_budget=32),
            data_plane="batched",
        ),
        seed=args.seed,
    )
    rng = np.random.default_rng(args.seed)
    rids = [
        eng.add_request(list(rng.integers(0, cfg.vocab, args.prompt_len)),
                        max_new=args.max_new)
        for _ in range(args.requests)
    ]
    t1 = time.perf_counter()
    first_step_s = 0.0
    while any(not eng.requests[r].done for r in rids):
        eng.step()  # ends in a host read of the step's tokens
        if eng.steps == 1:
            first_step_s = time.perf_counter() - t1
    t2 = time.perf_counter()
    outs: List[List[int]] = [eng.requests[r].out for r in rids]
    stats = eng.stats()
    stats.update(
        outputs=outs,
        tokens=sum(len(o) for o in outs),
        setup_s=t1 - t0,
        first_step_s=first_step_s,
        later_steps_s=t2 - t1 - first_step_s,
    )
    dev = jax.devices()[0]
    steady = stats["tokens"] - len(rids)  # the first step yields one per request
    print(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    print(f"setup {stats['setup_s']:.2f}s, first step {stats['first_step_s']:.2f}s, "
          f"{stats['tokens']} tokens in {stats['steps']} steps, later steps "
          f"{stats['later_steps_s']:.2f}s "
          f"({steady / max(stats['later_steps_s'], 1e-9):.1f} tok/s)")
    print(f"policy={args.policy} local={stats['local_fraction']:.3f} "
          f"demoted={stats['demoted']} promoted={stats['promoted']} "
          f"migrated={stats['migrated_bytes']/1e6:.1f}MB")
    return eng, stats


def main() -> None:
    enable_compile_cache()
    eng, _ = serve(build_parser().parse_args())
    eng.kv.pool.check_invariants()


if __name__ == "__main__":
    main()
