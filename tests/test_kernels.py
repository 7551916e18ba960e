"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles.

Kernels run in interpret mode on CPU (the TPU lowering is exercised by
the same code path with interpret=False on real hardware).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.page_migrate import page_gather, page_scatter
from repro.kernels.paged_attention import paged_attention
from repro.kernels.router_topk import router_topk

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,Hkv,S,D,causal,window,bq,bk",
    [
        (1, 4, 4, 128, 64, True, None, 64, 64),
        (2, 8, 2, 96, 32, True, None, 32, 32),
        (1, 4, 2, 200, 64, True, 64, 64, 64),
        (1, 2, 1, 64, 128, True, None, 32, 32),
        (2, 2, 2, 40, 16, False, None, 16, 16),
        (1, 8, 4, 256, 256, True, 128, 128, 128),
    ],
)
def test_flash_attention_sweep(B, H, Hkv, S, D, causal, window, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S * D + H), 3)
    q = rand(ks[0], (B, H, S, D), dtype)
    k = rand(ks[1], (B, Hkv, S, D), dtype)
    v = rand(ks[2], (B, Hkv, S, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          bq=bq, bk=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,Hkv,P,MP,D",
    [
        (2, 4, 2, 8, 4, 32),
        (1, 8, 8, 16, 3, 64),
        (3, 4, 1, 8, 5, 16),
        (1, 16, 4, 32, 2, 128),
    ],
)
def test_paged_attention_sweep(B, H, Hkv, P, MP, D, dtype):
    F = 24
    ks = jax.random.split(jax.random.PRNGKey(B * P + MP), 4)
    q = rand(ks[0], (B, H, D), dtype)
    kp = rand(ks[1], (F, Hkv, P, D), dtype)
    vp = rand(ks[2], (F, Hkv, P, D), dtype)
    bt = jax.random.randint(ks[3], (B, MP), 0, F)
    lengths = jnp.asarray(
        np.random.default_rng(0).integers(1, MP * P + 1, B), jnp.int32
    )
    out = paged_attention(q, kp, vp, bt, lengths, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


@pytest.mark.parametrize("window", [None, 20])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_position_mode(dtype, window):
    """Position-mode masking (sparse page subsets + sliding window) —
    the batched serving plane's kernel configuration."""
    from repro.kernels.paged_attention import PAD_PAGE_POS

    B, H, Hkv, P, MP, D, F = 2, 4, 2, 8, 4, 32, 24
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = rand(ks[0], (B, H, D), dtype)
    kp = rand(ks[1], (F, Hkv, P, D), dtype)
    vp = rand(ks[2], (F, Hkv, P, D), dtype)
    bt = jax.random.randint(ks[3], (B, MP), 0, F)
    # sparse page subsets: non-contiguous starts, one padded entry
    page_pos = jnp.asarray(
        [[0, 16, 40, PAD_PAGE_POS], [8, 24, 32, 47]], jnp.int32)
    q_pos = jnp.asarray([45, 49], jnp.int32)
    out = paged_attention(q, kp, vp, bt, page_pos=page_pos, q_pos=q_pos,
                          window=window, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, page_pos=page_pos,
                                   q_pos=q_pos, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


def test_paged_attention_position_matches_length_mode():
    """On a dense page prefix the two masking modes agree exactly."""
    B, H, Hkv, P, MP, D, F = 2, 8, 4, 8, 4, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = rand(ks[0], (B, H, D), jnp.float32)
    kp = rand(ks[1], (F, Hkv, P, D), jnp.float32)
    vp = rand(ks[2], (F, Hkv, P, D), jnp.float32)
    bt = jax.random.randint(ks[3], (B, MP), 0, F)
    lengths = jnp.asarray([13, 30], jnp.int32)
    page_pos = jnp.broadcast_to(jnp.arange(MP) * P, (B, MP)).astype(jnp.int32)
    o_len = paged_attention(q, kp, vp, bt, lengths, interpret=True)
    o_pos = paged_attention(q, kp, vp, bt, page_pos=page_pos,
                            q_pos=lengths - 1, interpret=True)
    np.testing.assert_array_equal(np.asarray(o_len), np.asarray(o_pos))


@pytest.mark.parametrize(
    "n,f,seed",
    [(1, 8, 0), (4, 16, 7), (8, 24, 42), (3, 12, 100), (2, 9, 55),
     (6, 20, 13), (8, 8, 77), (5, 23, 31)],
)
def test_page_migrate_property(n, f, seed):
    """gather∘scatter round-trips arbitrary frames (deterministic sweep)."""
    rng = np.random.default_rng(seed)
    src = jnp.asarray(rng.standard_normal((f, 2, 4, 8)), jnp.float32)
    idx = jnp.asarray(rng.choice(f, size=n, replace=False), jnp.int32)
    g = page_gather(src, idx, interpret=True)
    assert jnp.allclose(g, ref.page_gather_ref(src, idx))
    dst = jnp.zeros_like(src)
    s = page_scatter(dst, idx, g, interpret=True)
    assert jnp.allclose(s, ref.page_scatter_ref(dst, idx, g))
    # untouched frames preserved
    untouched = [i for i in range(f) if i not in np.asarray(idx)]
    for i in untouched[:3]:
        assert jnp.allclose(s[i], dst[i])


@pytest.mark.parametrize("T,E,k", [(64, 16, 2), (100, 64, 6), (7, 8, 2)])
def test_router_topk(T, E, k):
    logits = jax.random.normal(jax.random.PRNGKey(T + E), (T, E))
    p, v, i = router_topk(logits, k, block_tokens=32, interpret=True)
    pr, vr, ir = ref.router_topk_ref(logits, k)
    np.testing.assert_allclose(np.asarray(p), np.asarray(pr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v), np.asarray(vr), atol=1e-6)
    assert (np.asarray(i) == np.asarray(ir)).all()


def test_flash_matches_chunked_jnp_path():
    """The model's chunked-attention (dry-run path) and the Pallas kernel
    agree — the kernel can swap in 1:1 on TPU."""
    from repro.models.attention import chunked_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 64, 8, 32))  # (B,S,H,D) layout
    k = jax.random.normal(ks[1], (2, 64, 4, 32))
    v = jax.random.normal(ks[2], (2, 64, 4, 32))
    a = chunked_attention(q, k, v, causal=True, kv_chunk=32)
    b = flash_attention(
        jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1),
        causal=True, bq=32, bk=32, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(jnp.moveaxis(b, 1, 2)), atol=2e-5
    )


# --------------------------------------------------------------------- #
# kernels.ops: the one device decision
def test_ops_runs_the_reference_off_tpu():
    from repro.kernels import ops

    assert jax.default_backend() != "tpu"
    assert ops._on_tpu() is False
    src = jnp.arange(6 * 2 * 8, dtype=jnp.float32).reshape(6, 2, 8)
    idx = jnp.asarray([4, 1], jnp.int32)
    assert "pallas_call" not in str(jax.make_jaxpr(ops.page_gather)(src, idx))
    np.testing.assert_array_equal(
        np.asarray(ops.page_gather(src, idx)),
        np.asarray(ref.page_gather_ref(src, idx)))


def test_ops_device_check_propagates_backend_errors(monkeypatch):
    from repro.kernels import ops

    def broken_backend():
        raise RuntimeError("backend failed to initialise")

    monkeypatch.setattr(jax, "default_backend", broken_backend)
    with pytest.raises(RuntimeError, match="failed to initialise"):
        ops._on_tpu()
    # a fresh trace asks the backend too, and must not fall back
    src = jnp.zeros((5, 3, 7), jnp.float32)
    with pytest.raises(RuntimeError, match="failed to initialise"):
        ops.page_gather(src, jnp.asarray([2], jnp.int32))
