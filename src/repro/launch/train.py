"""Training driver: step builder + fault-tolerant loop + CLI.

``make_train_step`` builds the jit-able step used by the examples, the
e2e driver and the multi-pod dry-run: loss → grads (with microbatch
accumulation via ``lax.scan``) → AdamW.  Distribution comes entirely
from shardings (pjit/GSPMD); the step body is mesh-agnostic.

The loop is written for the 1000+-node failure model:
* async checkpoint every N steps (atomic, keep-k) → restart = resume
  from the newest complete manifest (crash consistency);
* **elastic**: restore re-shards onto whatever mesh the relaunch has
  (the checkpoint is topology-free);
* **straggler/fault mitigation**: per-step wall-clock watchdog — a step
  exceeding ``watchdog_factor``× the trailing median is logged and
  counted (on real fleets this feeds the job controller that evicts the
  straggler host; here it is observable state + test hook);
* NaN/overflow guard: non-finite grad-norm steps are skipped (counted),
  matching large-fleet bad-host containment practice.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import statistics
import time
from functools import partial
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import optim
from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, make_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import ModelConfig, init_params, loss_fn
from repro.optim.adamw import AdamWConfig, cosine_schedule


# --------------------------------------------------------------------- #
# step builder
# --------------------------------------------------------------------- #
def make_train_step(
    cfg: ModelConfig,
    opt_cfg: AdamWConfig,
    schedule: Optional[Callable] = None,
    accum: int = 1,
    remat: bool = False,
    impl: str = "chunked",
):
    """Returns train_step(params, opt_state, batch) → (params, opt, metrics)."""
    schedule = schedule or (lambda s: 1.0)

    def loss_of(p, mb):
        return loss_fn(p, cfg, mb, impl=impl, remat=remat)

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    def train_step(params, opt_state, batch):
        if accum == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            # microbatch accumulation: split batch leading dim into
            # ``accum`` chunks and scan (sequential; keeps peak memory at
            # 1/accum of the full batch).
            def slice_mb(i):
                return jax.tree_util.tree_map(
                    lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:])[i]
                    if x.ndim >= 1 and x.shape[0] % accum == 0
                    else x,
                    batch,
                )

            def body(carry, i):
                g_acc, l_acc = carry
                (l, met), g = grad_fn(params, slice_mb(i))
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, l_acc + l), met

            g0 = jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, jnp.float32), params
            )
            (grads, loss_sum), mets = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32)), jnp.arange(accum)
            )
            grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
            loss = loss_sum / accum
            metrics = jax.tree_util.tree_map(lambda x: x[-1], mets)

        lr_scale = schedule(opt_state.step)
        new_params, new_opt, opt_metrics = optim.update(
            grads, opt_state, params, opt_cfg, lr_scale
        )
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = loss

        # NaN containment: skip the update if grads went non-finite.
        ok = jnp.isfinite(opt_metrics["grad_norm"])
        new_params = jax.tree_util.tree_map(
            lambda n, o: jnp.where(ok, n, o), new_params, params
        )
        new_opt = jax.tree_util.tree_map(
            lambda n, o: jnp.where(ok, n, o) if hasattr(n, "dtype") else n,
            new_opt,
            opt_state,
        )
        metrics["skipped"] = (~ok).astype(jnp.int32)
        return new_params, new_opt, metrics

    return train_step


# --------------------------------------------------------------------- #
# fault-tolerant loop
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class LoopReport:
    steps_run: int
    resumed_from: Optional[int]
    losses: list
    stragglers: int
    skipped: int


def train_loop(
    cfg: ModelConfig,
    data_cfg: DataConfig,
    opt_cfg: AdamWConfig,
    steps: int,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 50,
    accum: int = 1,
    remat: bool = False,
    seed: int = 0,
    dtype=jnp.float32,
    watchdog_factor: float = 3.0,
    log_every: int = 10,
    warmup: int = 20,
) -> LoopReport:
    key = jax.random.PRNGKey(seed)
    params = init_params(key, cfg, dtype=dtype)
    opt_state = optim.init(params, opt_cfg)
    schedule = cosine_schedule(warmup, steps)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, schedule, accum=accum, remat=remat))

    manager = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start = 0
    resumed_from = None
    if manager is not None:
        got, restored = manager.restore_latest({"params": params, "opt": opt_state})
        if got is not None:
            params, opt_state = restored["params"], restored["opt"]
            start = got
            resumed_from = got

    batches = make_batches(data_cfg, cfg)
    # fast-forward the stream to the resume point (synthetic stream is
    # seeded per step, so this is exact replay)
    for _ in range(start):
        next(batches)

    losses, durations = [], []
    stragglers = skipped = 0
    for step in range(start, steps):
        batch = next(batches)
        t0 = time.time()
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        dt = time.time() - t0
        durations.append(dt)
        losses.append(loss)
        skipped += int(metrics["skipped"])
        if len(durations) >= 8:
            med = statistics.median(durations[-32:])
            if dt > watchdog_factor * med:
                stragglers += 1
        if manager is not None and (step + 1) % ckpt_every == 0:
            manager.save(step + 1, {"params": params, "opt": opt_state})
        if log_every and (step + 1) % log_every == 0:
            print(
                f"step {step+1:5d} loss {loss:.4f} "
                f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms"
            )
    if manager is not None:
        manager.save(steps, {"params": params, "opt": opt_state}, blocking=True)
    return LoopReport(
        steps_run=steps - start,
        resumed_from=resumed_from,
        losses=losses,
        stragglers=stragglers,
        skipped=skipped,
    )


# --------------------------------------------------------------------- #
# CLI
# --------------------------------------------------------------------- #
def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description="train an assigned arch")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    from repro.configs import get_config, get_smoke_config

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    data_cfg = DataConfig(seq_len=args.seq_len, global_batch=args.batch)
    report = train_loop(
        cfg,
        data_cfg,
        AdamWConfig(lr=args.lr),
        steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        accum=args.accum,
    )
    print(
        f"done: {report.steps_run} steps, resumed_from={report.resumed_from}, "
        f"final loss {report.losses[-1]:.4f}"
    )


if __name__ == "__main__":
    main()
