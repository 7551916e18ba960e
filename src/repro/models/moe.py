"""Mixture-of-Experts layer: top-k router, shared + routed experts.

Implements the two assigned MoE families:

* **phi3.5-moe**: 16 experts, top-2, SwiGLU experts of d_ff=6400, no
  shared experts (sparse-mixer routing approximated by softmax top-k).
* **deepseek-v2-lite**: 64 routed experts top-6 + 2 shared experts,
  expert d_ff=1408; router uses softmax over routed experts with
  normalized top-k weights.

Dispatch is the MaxText-style capacity-based gather/scatter: tokens are
ranked per expert, the top ``capacity`` tokens per expert are gathered to
``(E, C, d)``, pushed through a batched SwiGLU (einsum over the expert
dim → MXU-friendly, EP-shardable on the 'model' axis), and combined with
router weights.  Overflowed tokens fall through with zero contribution
from that expert (standard dropping semantics).  An auxiliary
load-balancing loss (Switch-style) is returned for training.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import nn

Params = Dict[str, Any]

# Expert-parallel sharding constraint axis.  Without it, the arbitrary
# token→slot gather downstream of the expert einsums makes GSPMD
# replicate the whole (E, C, ·) expert compute on every model rank
# (measured: ~16× FLOPs at axis 16 — EXPERIMENTS.md §Perf iteration C).
# The launcher sets this to "model"; single-device tests leave it None.
EP_AXIS: Optional[str] = None


def _ep(x: jax.Array) -> jax.Array:
    if EP_AXIS is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, P(EP_AXIS, *([None] * (x.ndim - 1)))
    )


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0
    d_ff_shared: int = 0  # total shared-expert width
    capacity_factor: float = 1.25
    router_noise: float = 0.0


def init_moe(key, d_model: int, cfg: MoeConfig, dtype=jnp.float32) -> Params:
    ks = nn.split_keys(key, 5)
    E, F = cfg.n_experts, cfg.d_ff_expert
    std = 1.0 / (d_model ** 0.5)
    p: Params = {
        "router": nn.dense_init(ks[0], d_model, E, dtype=jnp.float32, std=0.02),
        # batched expert weights: (E, d, F) / (E, F, d)
        "wi_gate": nn.normal_init(ks[1], (E, d_model, F), std, dtype),
        "wi_up": nn.normal_init(ks[2], (E, d_model, F), std, dtype),
        "wo": nn.normal_init(ks[3], (E, F, d_model), 1.0 / (F ** 0.5), dtype),
    }
    if cfg.n_shared > 0:
        from repro.models.ffn import init_ffn

        p["shared"] = init_ffn(ks[4], d_model, cfg.d_ff_shared, "swiglu", dtype)
    return p


def moe_fwd(
    p: Params,
    cfg: MoeConfig,
    x: jax.Array,  # (B, S, d)
    capacity: Optional[int] = None,
    n_valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y, aux_loss).

    ``n_valid`` (one sequence, ``B == 1``, ``capacity`` unset) marks the
    first ``n_valid`` tokens as real and the rest as padding: each expert
    then keeps the tokens it would keep at the real length.  Padding
    sorts after every real token, so the real tokens' outputs are those
    of the unpadded call (the auxiliary loss still averages over the
    padding)."""
    B, S, d = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.top_k
    xt = x.reshape(T, d)

    logits = nn.dense(p["router"], xt.astype(jnp.float32))  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)  # (T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance auxiliary loss.
    me = probs.mean(axis=0)  # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[gate_idx.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)

    C = capacity if capacity is not None else max(
        1, int(cfg.capacity_factor * T * K / E)
    )

    # position-in-expert via stable sort, O(N log N): grouping the (T·K)
    # assignments by expert preserves token order within each group, so
    # rank-within-group == the cumsum-based first-come position.  (The
    # one-hot cumsum over (T·K, E) lowers to an O(N²·E) reduce-window on
    # CPU — measured 37× the expert FLOPs; §Perf iteration C.)
    flat_e = gate_idx.reshape(-1)  # (T*K,)
    order = jnp.argsort(flat_e, stable=True)
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])  # (E,) tiny cumsum
    pos_sorted = jnp.arange(T * K, dtype=jnp.int32) - starts[flat_e[order]]
    pos = jnp.zeros((T * K,), jnp.int32).at[order].set(pos_sorted).reshape(T, K)
    expert = gate_idx  # (T, K)
    keep = pos < C
    if n_valid is not None:
        caps = [max(1, int(cfg.capacity_factor * n * K / E)) for n in range(T + 1)]
        keep &= pos < jnp.asarray(caps, jnp.int32)[n_valid]

    # scatter tokens into (E, C, d)
    slot = jnp.where(keep, expert * C + pos, E * C)  # overflow slot dropped
    xe = jnp.zeros((E * C + 1, d), dtype=x.dtype)
    xe = xe.at[slot.reshape(-1)].add(
        jnp.repeat(xt, K, axis=0).reshape(T * K, d)
    )
    xe = _ep(xe[: E * C].reshape(E, C, d))

    # batched SwiGLU over experts (einsum keeps E as a shardable axis)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["wi_gate"])) * jnp.einsum(
        "ecd,edf->ecf", xe, p["wi_up"]
    )
    ye = _ep(jnp.einsum("ecf,efd->ecd", _ep(h), p["wo"]))  # (E, C, d)

    # gather back with gate weights
    ye_flat = jnp.concatenate([ye.reshape(E * C, d), jnp.zeros((1, d), ye.dtype)], axis=0)
    yk = ye_flat[slot.reshape(-1)].reshape(T, K, d)
    y = jnp.sum(yk * gate_vals[..., None].astype(yk.dtype), axis=1)

    if "shared" in p:
        from repro.models.ffn import ffn_fwd

        y = y + ffn_fwd(p["shared"], xt, "swiglu")
    return y.reshape(B, S, d), aux
