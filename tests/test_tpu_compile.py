"""Compiles of the served path's Pallas kernels for a described TPU v5e.

No chip is attached.  The TPU compiler that ships with JAX compiles for a
``v5e:2x2`` topology it is only told about, which catches what interpret
mode cannot (tiling, scoped-memory limits, layouts) at no chip time.
Nothing runs, so these tests say nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
xdist worker imports every test file.  Keep these tests in this one file
so that one worker loads the library for all of them.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.page_migrate import page_gather, page_scatter
from repro.kernels.paged_attention import paged_attention
from repro.models.model import init_params
from repro.serving.engine import ServingEngine, _flat_layers, _prefill_program

KERNEL = 'custom_call_target="tpu_custom_call"'
# the served TinyLlama geometry: 16-token pages, 128 fast + 1024 slow
# frames + the trash frame, 8 decode lanes over 8-entry block tables
P, FRAMES, B, MP, FLUSH = 16, 128 + 1024 + 1, 8, 8, 64


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off (an entry written here could not be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler in this installation
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _on(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(compiled) -> int:
    return compiled.as_text().count(KERNEL)


@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "chatglm3-6b"])
def test_paged_attention_compiles_at_model_widths(one_chip, arch, window):
    a = get_config(arch).all_specs()[0].attn
    i32 = jnp.int32
    fn = jax.jit(lambda q, k, v, bt, pp, qp: paged_attention(
        q, k, v, bt, page_pos=pp, q_pos=qp, window=window))
    compiled = fn.lower(
        _on(one_chip, (B, a.n_heads, a.head_dim)),
        _on(one_chip, (FRAMES, a.n_kv_heads, P, a.head_dim)),
        _on(one_chip, (FRAMES, a.n_kv_heads, P, a.head_dim)),
        _on(one_chip, (B, MP), i32), _on(one_chip, (B, MP), i32),
        _on(one_chip, (B,), i32),
    ).compile()
    assert _kernels(compiled) == 1


def _store(one_chip):
    cfg = get_config("tinyllama-1.1b")
    a = cfg.all_specs()[0].attn
    return _on(one_chip, (FRAMES, cfg.n_layers, a.n_kv_heads, P, a.head_dim))


def test_page_gather_compiles_over_full_width_store(one_chip):
    store = _store(one_chip)
    compiled = jax.jit(page_gather).lower(
        store, _on(one_chip, (FLUSH,), jnp.int32)).compile()
    assert _kernels(compiled) == 1


def test_page_scatter_compiles_over_full_width_store(one_chip):
    store = _store(one_chip)
    pages = _on(one_chip, (FLUSH,) + store.shape[1:])
    compiled = jax.jit(page_scatter, donate_argnums=(0,)).lower(
        store, _on(one_chip, (FLUSH,), jnp.int32), pages).compile()
    assert _kernels(compiled) == 1


@pytest.fixture
def ops_on_tpu(monkeypatch):
    """Steer ``kernels.ops`` to its kernels: this process's backend is the
    CPU, but the program below is compiled for the described chip.  Jit
    caches are cleared on both sides so no trace crosses the steering."""
    jax.clear_caches()
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    yield
    jax.clear_caches()


def test_batched_decode_step_compiles_one_kernel_per_layer(one_chip, ops_on_tpu):
    full = get_config("tinyllama-1.1b")
    ((pattern, _),) = full.stacks
    cfg = dataclasses.replace(full, stacks=((pattern, 2),))
    a = cfg.all_specs()[0].attn
    max_seqs, mp_cap = 8, 64

    def place(tree):
        return jax.tree.map(lambda s: _on(one_chip, s.shape, s.dtype), tree)

    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    layers = jax.eval_shape(lambda p: _flat_layers(p, cfg), params)
    # _batched_step_impl reads only cfg and specs; a constructed engine
    # would allocate the model's weights on the host for nothing
    eng = object.__new__(ServingEngine)
    eng.cfg, eng.specs = cfg, cfg.all_specs()
    store = _on(one_chip, (FRAMES, cfg.n_layers, a.n_kv_heads, P, a.head_dim))
    vec = _on(one_chip, (B,), jnp.int32)
    table = _on(one_chip, (B, MP), jnp.int32)
    compiled = jax.jit(eng._batched_step_impl, donate_argnums=(0, 1, 2, 3)).lower(
        store, store,
        _on(one_chip, (max_seqs + 1, mp_cap, cfg.n_layers, a.n_kv_heads, a.head_dim)),
        _on(one_chip, (max_seqs + 1, mp_cap)),
        place(params), place(layers),
        vec, vec, table, table, vec, vec, vec, vec,
    ).compile()
    assert _kernels(compiled) == cfg.n_layers


def test_prefill_program_compiles_at_full_width(one_chip):
    """The jitted prefill of a 512-token bucket at TinyLlama's full width
    (two of its layers) takes its weights as arguments, never as constants
    baked into the program."""
    full = get_config("tinyllama-1.1b")
    ((pattern, _),) = full.stacks
    cfg = dataclasses.replace(full, stacks=((pattern, 2),))
    a = cfg.all_specs()[0].attn
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    layers = jax.eval_shape(lambda p: _flat_layers(p, cfg), params)

    def place(tree):
        return jax.tree.map(lambda s: _on(one_chip, s.shape, s.dtype), tree)

    compiled = _prefill_program.lower(
        cfg, place(params["embed"]), place(layers),
        _on(one_chip, (512,), jnp.int32), _on(one_chip, (), jnp.int32),
    ).compile()
    out = compiled.memory_analysis()
    # the last layer's output projection and FFN feed no K or V, so XLA
    # drops them; the embedding and the first layer are read in full
    read = sum(x.size * 4 for x in jax.tree.leaves((params["embed"], layers[0])))
    assert out.argument_size_in_bytes >= read
    k_shape = (cfg.n_layers, 512, a.n_kv_heads, a.head_dim)
    assert [o.shape for o in jax.tree.leaves(compiled.out_info)] == [k_shape, k_shape]
