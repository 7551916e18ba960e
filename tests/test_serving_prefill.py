"""The jitted prefill: one program per padded prompt length.

``ServingEngine`` pads ``prompt[:-1]`` at the end to a power of two (at
least one page) and runs the whole stack as one compiled program.  The K
and V it lands in the store must be those of a plain, unpadded, eager
forward, on both data planes, for plain GQA, sliding-window, LoRA-shared
and capacity-dropping MoE stacks.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import nn
from repro.models.attention import _rotate, make_cos_sin, reference_attention
from repro.models.ffn import ffn_fwd
from repro.models.model import init_params
from repro.models.moe import moe_fwd
from repro.serving import EngineConfig, ServingEngine
from repro.serving.engine import _flat_layers

PAGE = 4
LENGTHS = (15, 16, 17, 63, 64, 65)  # prefill tokens: one below, at and one above an edge


def _shared_lora_config():
    """Dense GQA whose one attention block is shared by both layers, each
    with its own LoRA delta (the zamba2 scheme without its Mamba layers)."""
    base = get_smoke_config("tinyllama-1.1b")
    ((pattern, reps),) = base.stacks
    block = dataclasses.replace(pattern[0], shared=True, lora_rank=4)
    return dataclasses.replace(base, name="shared-lora-gqa", stacks=(((block,), reps),))


def _dropping_moe_config():
    """The MoE smoke stack at capacity factor 1, so experts drop tokens."""
    base = get_smoke_config("phi3.5-moe-42b-a6.6b")
    ((pattern, reps),) = base.stacks
    block = dataclasses.replace(
        pattern[0], moe=dataclasses.replace(pattern[0].moe, capacity_factor=1.0))
    return dataclasses.replace(base, name="moe-cf1", stacks=(((block,), reps),))


CONFIGS = {
    "gqa": lambda: get_smoke_config("tinyllama-1.1b"),
    "windowed": lambda: get_smoke_config("gemma3-4b"),
    "lora-shared": _shared_lora_config,
    "moe-dropping": _dropping_moe_config,
}


@pytest.fixture(scope="module")
def models():
    out = {}
    for name, make in CONFIGS.items():
        cfg = make()
        params = init_params(jax.random.PRNGKey(0), cfg)
        if name == "lora-shared":  # LoRA B starts at zero; make the branch count
            for lora in params["stacks"][0]["lora"]:
                for key in ("qb", "ob"):
                    lora[key] = 0.05 * jax.random.normal(
                        jax.random.PRNGKey(1), lora[key].shape)
        out[name] = (cfg, params)
    return out


@pytest.fixture(scope="module")
def plain(models):
    """``plain_forward`` of a config's prompt, computed once for both planes."""
    memo = {}

    def kv(config, prompt):
        key = (config, tuple(prompt))
        if key not in memo:
            memo[key] = plain_forward(*models[config], prompt)
        return memo[key]
    return kv


def plain_forward(cfg, params, tokens):
    """Eager, unpadded forward over ``tokens`` → K and V, ``(L, S, Hkv, D)``."""
    toks = jnp.asarray(tokens, jnp.int32)[None, :]
    S = toks.shape[1]
    x = nn.embed(params["embed"], toks)
    pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    ks, vs = [], []
    for spec, p in zip(cfg.all_specs(), _flat_layers(params, cfg)):
        pa = p["base"] if "base" in p else p
        a = spec.attn
        h = nn.rmsnorm(pa["norm1"], x)
        q = nn.dense(pa["attn"]["wq"], h).reshape(1, S, a.n_heads, a.head_dim)
        k = nn.dense(pa["attn"]["wk"], h).reshape(1, S, a.n_kv_heads, a.head_dim)
        v = nn.dense(pa["attn"]["wv"], h).reshape(1, S, a.n_kv_heads, a.head_dim)
        cos, sin = make_cos_sin(a, pos)
        if cos is not None:
            q, k = _rotate(a, q, cos, sin), _rotate(a, k, cos, sin)
        o = reference_attention(q, k, v, causal=True, window=a.window)
        y = nn.dense(pa["attn"]["wo"], o.reshape(1, S, -1))
        if "base" in p:
            lo = p["lora"]
            y = y + nn.dense({"w": lo["ob"]}, nn.dense({"w": lo["oa"]},
                nn.dense({"w": lo["qb"]}, nn.dense({"w": lo["qa"]}, h))))
        x = x + y
        if spec.has_ffn:
            h2 = nn.rmsnorm(pa["norm2"], x)
            if spec.moe is not None:
                y2, _ = moe_fwd(pa["moe"], spec.moe, h2)
            else:
                y2 = ffn_fwd(pa["ffn"], h2, spec.ffn_kind)
            x = x + y2
        ks.append(k[0])
        vs.append(v[0])
    return np.asarray(jnp.stack(ks)), np.asarray(jnp.stack(vs))


def stored_kv(eng, rid, n):
    """The first ``n`` tokens' K and V as the store holds them, ``(L, n, Hkv, D)``."""
    pages = np.asarray(eng.kv.gather_pages(eng.seqs[rid].pages))  # (pages, L, P, W)
    a = eng.specs[0].attn
    L, W = pages.shape[1], a.n_kv_heads * a.head_dim
    kv = np.moveaxis(pages, 1, 0).reshape(L, -1, 2 * W)[:, :n]
    shape = (L, n, a.n_kv_heads, a.head_dim)
    return kv[..., :W].reshape(shape), kv[..., W:].reshape(shape)


def _engine(cfg, params, plane):
    return ServingEngine(cfg, params, EngineConfig(
        page_size=PAGE, num_fast=32, num_slow=8, topk_pages=2, recent_pages=1,
        data_plane=plane))


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("plane", ["reference", "batched"])
@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("config", list(CONFIGS))
def test_prefill_lands_the_unpadded_forward(models, plain, config, n, plane):
    cfg, params = models[config]
    prompt = np.random.default_rng(n).integers(0, cfg.vocab, n + 1).tolist()
    eng = _engine(cfg, params, plane)
    rid = eng.add_request(prompt, max_new=2)
    k, v = stored_kv(eng, rid, n)
    k_ref, v_ref = plain(config, prompt[:-1])
    assert _rel(k, k_ref) < 1e-5
    assert _rel(v, v_ref) < 1e-5


def test_moe_padding_keeps_the_real_capacity(models):
    """The case the MoE one guards: at the padded length the experts would
    keep tokens that the real length drops."""
    cfg, params = models["moe-dropping"]
    spec = cfg.all_specs()[0]
    p = _flat_layers(params, cfg)[0]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 17, cfg.d_model))
    xp = jnp.pad(x, ((0, 0), (0, 15), (0, 0)))
    want, _ = moe_fwd(p, spec.moe, x)
    roomier, _ = moe_fwd(p, spec.moe, xp)
    got, _ = moe_fwd(p, spec.moe, xp, n_valid=jnp.int32(17))
    assert not np.allclose(roomier[:, :17], want, atol=1e-5)
    np.testing.assert_allclose(got[:, :17], want, rtol=1e-5, atol=1e-6)


def _compile_probe_config():
    """A width no other test builds, so the process has no program for it."""
    base = get_smoke_config("tinyllama-1.1b")
    ((pattern, _),) = base.stacks
    return dataclasses.replace(base, name="prefill-compile-probe", vocab=211,
                               stacks=((pattern, 1),))


def test_one_compile_per_bucket_shared_by_engines():
    cfg = _compile_probe_config()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    eng = _engine(cfg, params, "batched")
    for n in (10, 13, 17):  # prefills of 9, 12, 16 tokens: one bucket of 16
        eng.add_request(rng.integers(0, cfg.vocab, n).tolist(), max_new=1)
    assert eng.prefill_compiles == 1
    eng.add_request(rng.integers(0, cfg.vocab, 20).tolist(), max_new=1)  # 19 → 32
    assert eng.prefill_compiles == 2
    other = _engine(cfg, params, "reference")
    for n in (10, 30):
        other.add_request(rng.integers(0, cfg.vocab, n).tolist(), max_new=1)
    assert other.prefill_compiles == 0
    other.add_request(rng.integers(0, cfg.vocab, 3).tolist(), max_new=1)  # 2 → 4
    assert other.prefill_compiles == 1
