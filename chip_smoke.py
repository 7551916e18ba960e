"""Chip smoke test: serve TinyLlama-1.1B at its published widths on one TPU.

  python chip_smoke.py [--seed N]

One process, in order:

(a) check that JAX's first device is a TPU (there is no CPU path);
(b) check ``kernels.ops.paged_attention`` in position mode against its
    jnp reference at the model's widths, sparse pages with and without a
    window;
(c) serve 8 requests of 512-token prompts and 32 new tokens through
    ``repro.launch.serve`` on the batched data plane with TPP tiering, a
    fast tier too small for the prompts so pages demote and promote, then
    check the outputs, the migrations, the pool's invariants and that the
    compiled decode step holds one Pallas kernel per layer;
(d) print one JSON line naming the device.

Any failed check exits non-zero before (d).  Weights are random, made
from ``--seed``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
from repro.configs import get_config  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.paged_attention import PAD_PAGE_POS  # noqa: E402
from repro.launch import serve as serve_cli  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving.kv_cache import bucket  # noqa: E402

ARCH = "tinyllama-1.1b"
N_REQUESTS, PROMPT_LEN, MAX_NEW = 8, 512, 32
# Both sides of (b) compute in f32 with fp32-precision matmuls; they differ
# only in summation order (an online softmax across pages against one
# softmax over all keys) and in their exp, a few ulps per term over at most
# 128 keys, so errors stay near 1e-6 on outputs of order 1.  A kernel whose
# matmuls fell back to one bf16 pass errs near 1e-3, which this rejects.
KERNEL_TOL = 1e-4


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def check_device():
    dev = jax.devices()[0]
    print(f"jax {jax.__version__}, device_kind {dev.device_kind!r}, "
          f"{len(jax.devices())} device(s), platform {dev.platform}")
    if dev.platform != "tpu":
        fail(f"JAX's first device is {dev.platform!r}, not a TPU")
    return dev


def check_kernel(seed: int) -> None:
    a = get_config(ARCH).all_specs()[0].attn
    H, Hkv, D, P = a.n_heads, a.n_kv_heads, a.head_dim, 16
    B, MP, F, span = 8, 8, 512, 64  # span: logical pages a sequence spans
    rng = np.random.default_rng(seed)
    bt = np.stack([rng.choice(F - 1, MP, replace=False) for _ in range(B)])
    pages = np.stack([np.sort(rng.choice(span, MP, replace=False))
                      for _ in range(B)])
    page_pos = pages * P
    q_pos = page_pos[:, -1] + rng.integers(0, P, B)
    # odd rows present fewer pages: pad entries point at the last frame
    # with a start past every query, so they mask out
    page_pos[1::2, -2:-1] = PAD_PAGE_POS
    bt[1::2, -2:-1] = F - 1
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, H, D), jnp.float32)
    k_pages = jax.random.normal(kk, (F, Hkv, P, D), jnp.float32)
    v_pages = jax.random.normal(kv, (F, Hkv, P, D), jnp.float32)
    args = (q, k_pages, v_pages, jnp.asarray(bt, jnp.int32))
    pos = dict(page_pos=jnp.asarray(page_pos, jnp.int32),
               q_pos=jnp.asarray(q_pos, jnp.int32))
    with jax.default_matmul_precision("highest"):
        for window in (None, 5 * P + 3):
            out = ops.paged_attention(*args, **pos, window=window)
            want = ref.paged_attention_ref(*args, **pos, window=window)
            err = float(jnp.max(jnp.abs(out - want)))
            print(f"paged_attention H{H} Hkv{Hkv} D{D} P{P} B{B} MP{MP} "
                  f"window={window}: max |kernel - ref| = {err:.3e} "
                  f"(tolerance {KERNEL_TOL:.0e})")
            if not np.isfinite(err) or err > KERNEL_TOL:
                fail(f"paged_attention differs from its reference by {err}")


def check_serving(seed: int) -> None:
    args = serve_cli.build_parser().parse_args([
        "--arch", ARCH, "--page-size", "16", "--num-fast", "128",
        "--num-slow", "1024", "--requests", str(N_REQUESTS),
        "--prompt-len", str(PROMPT_LEN), "--max-new", str(MAX_NEW),
        "--policy", "tpp", "--seed", str(seed),
    ])
    eng, stats = serve_cli.serve(args)
    vocab = eng.cfg.vocab
    if len(stats["outputs"]) != N_REQUESTS or any(
            len(o) != MAX_NEW for o in stats["outputs"]):
        fail(f"not every request finished: {[len(o) for o in stats['outputs']]}")
    if any(not 0 <= t < vocab for o in stats["outputs"] for t in o):
        fail("a generated token lies outside the vocabulary")
    if stats["demoted"] <= 0 or stats["promoted"] <= 0:
        fail(f"TPP did not migrate: demoted={stats['demoted']} "
             f"promoted={stats['promoted']}")
    eng.kv.pool.check_invariants()
    print(f"{N_REQUESTS} requests finished, {stats['tokens']} tokens; "
          f"migrations: demoted={stats['demoted']} promoted={stats['promoted']} "
          f"({stats['migrated_bytes']} bytes)")

    # The decode step at the shapes every step above ran with: its compiled
    # text must hold one Pallas kernel (tpu_custom_call) per layer, so no
    # layer ran the jnp reference or interpret mode.
    t0 = time.perf_counter()
    Bp, MPp = bucket(N_REQUESTS), bucket(eng.ecfg.topk_pages + eng.ecfg.recent_pages + 1)
    vec, table = jnp.zeros((Bp,), jnp.int32), jnp.zeros((Bp, MPp), jnp.int32)
    text = eng._step_fn.lower(
        eng.kv.k_store, eng.kv.v_store, eng._ksum, eng._kcnt, eng.params,
        eng.layers, vec, vec, table, table, vec, vec, vec, vec,
    ).compile().as_text()
    n_kernels = text.count('custom_call_target="tpu_custom_call"')
    print(f"decode step: {n_kernels} tpu_custom_call for {eng.cfg.n_layers} "
          f"layers (lowered and compiled in {time.perf_counter() - t0:.2f}s)")
    if n_kernels != eng.cfg.n_layers:
        fail(f"decode step holds {n_kernels} Pallas kernels, "
             f"expected {eng.cfg.n_layers}")
    mem = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use {mem.get('peak_bytes_in_use', 'not reported')}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    print(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    dev = check_device()
    check_kernel(args.seed)
    check_serving(args.seed)
    print(f"chip_smoke: all phases passed in {time.perf_counter() - t0:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
