"""Unit tests for the distribution layer: sharding rules, input specs,
collective parsing, config transforms.  (The heavy lower+compile path is
exercised by the dry-run itself; these are its fast invariants.)"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ALIASES, SHAPES, get_config, get_smoke_config
from repro.launch.shardings import attn_alignment, param_spec, _path_names


class FakeLeaf:
    def __init__(self, shape):
        self.shape = shape
        self.ndim = len(shape)


class Key:
    def __init__(self, key):
        self.key = key


def spec_of(path_names, shape, axis=16, q_align=True, kv_align=True):
    path = [Key(n) for n in path_names]
    return param_spec(path, FakeLeaf(shape), "model", axis,
                      q_align=q_align, kv_align=kv_align)


class TestParamSpecRules:
    def test_ffn_col_and_row(self):
        assert spec_of(["ffn", "wi_gate", "w"], (4096, 13696)) == P(None, "model")
        assert spec_of(["ffn", "wo", "w"], (13696, 4096)) == P("model", None)

    def test_attention_head_aligned(self):
        # 32 q heads × 128 → aligned at 16
        assert spec_of(["attn", "wq", "w"], (4096, 4096)) == P(None, "model")
        # kv misaligned (2 heads) → replicate even though 256 % 16 == 0
        assert spec_of(["attn", "wk", "w"], (4096, 256), kv_align=False) == P()
        # q misaligned (12 heads) → wq and wo replicate
        assert spec_of(["attn", "wq", "w"], (1536, 1536), q_align=False) == P()
        assert spec_of(["attn", "wo", "w"], (1536, 1536), q_align=False) == P()

    def test_moe_expert_parallel(self):
        assert spec_of(["moe", "wi_gate"], (16, 4096, 6400)) == P("model", None, None)
        assert spec_of(["moe", "wo"], (64, 1408, 2048)) == P("model", None, None)

    def test_embed_vocab_sharded(self):
        assert spec_of(["embed", "table"], (65024, 4096)) == P("model", None)
        # non-divisible vocab replicates
        assert spec_of(["embed", "table"], (65025, 4096)) == P()

    def test_norms_and_ssm_replicate(self):
        assert spec_of(["norm1", "scale"], (4096,)) == P()
        assert spec_of(["mixer", "in_proj", "w"], (2560, 10448)) == P()
        assert spec_of(["mixer", "wq", "w"], (2048, 2048)) == P()  # mLSTM

    def test_stacked_leading_dim_ignored(self):
        # stacked-over-repeats leaves: leading dim untouched
        assert spec_of(["ffn", "wi_gate", "w"], (22, 2048, 5632)) == P(
            None, None, "model"
        )


class TestAttnAlignment:
    @pytest.mark.parametrize("arch,q,kv", [
        ("chatglm3-6b", True, False),     # 32 q, 2 kv
        ("phi3-medium-14b", False, False),  # 40 q, 10 kv
        ("tinyllama-1.1b", True, False),  # 32 q, 4 kv
        ("gemma3-4b", False, False),      # 8 q, 4 kv
        ("musicgen-medium", False, False),  # 24 q MHA
        ("phi3.5-moe-42b-a6.6b", True, False),  # 32 q, 8 kv
        ("deepseek-v2-lite-16b", True, True),   # MLA 16 heads
        ("qwen2-vl-2b", False, False),    # 12 q, 2 kv
    ])
    def test_alignment_table(self, arch, q, kv):
        assert attn_alignment(get_config(arch), 16) == (q, kv)


class TestCollectiveParser:
    def test_parses_kinds_and_bytes(self):
        from repro.launch.dryrun import collective_bytes

        hlo = """
  %ar = f32[16,4096,2048]{2,1,0} all-reduce(%x), replica_groups={}
  %ag.1 = bf16[256,128]{1,0} all-gather(%y), dimensions={0}
  %a2a = (f32[8,8]{1,0}, f32[8,8]{1,0}) all-to-all(%a, %b)
  %cp = u32[64]{0} collective-permute-start(%z)
  %notacoll = f32[2,2]{1,0} add(%p, %q)
"""
        out = collective_bytes(hlo)
        assert out["all-reduce"] == 16 * 4096 * 2048 * 4
        assert out["all-gather"] == 256 * 128 * 2
        assert out["all-to-all"] == 2 * 8 * 8 * 4
        assert out["collective-permute"] == 64 * 4
        assert "add" not in out


class TestConfigTransforms:
    def test_unrolled_preserves_layer_sequence(self):
        from repro.launch.dryrun import unrolled

        cfg = get_config("gemma3-4b")
        u = unrolled(cfg)
        assert u.n_layers == cfg.n_layers == 34
        a = [s.attn.window for s in cfg.all_specs()]
        b = [s.attn.window for s in u.all_specs()]
        assert a == b

    def test_with_reps(self):
        from repro.launch.dryrun import with_reps

        cfg = get_config("zamba2-2.7b")
        c2 = with_reps(cfg, (2,))
        assert c2.n_layers == 12  # pattern of 6 × 2

    def test_input_specs_cover_every_cell(self):
        from repro.launch.dryrun import LONG_OK, input_specs

        for arch in ALIASES:
            cfg = get_config(arch)
            for shape, (seq, batch, kind) in SHAPES.items():
                if shape == "long_500k" and arch not in LONG_OK:
                    continue
                specs = input_specs(cfg, shape)
                assert "tokens" in specs
                tok = specs["tokens"]
                assert tok.shape[0] == batch
                if kind == "decode":
                    assert tok.shape[1] == 1
                    assert specs["cur_len"].shape == (batch,)
                else:
                    assert tok.shape[1] == seq
                if cfg.vision_stub and kind != "decode":
                    assert "patch_embeds" in specs


class TestZero1:
    def test_adds_data_axis_to_large_leaves(self):
        from jax.sharding import NamedSharding
        from repro.launch.mesh import make_host_mesh
        from repro.launch.shardings import zero1_shardings

        mesh = make_host_mesh()
        big = jax.ShapeDtypeStruct((1024, 4096), jnp.float32)
        small = jax.ShapeDtypeStruct((64,), jnp.float32)
        sh = {"a": NamedSharding(mesh, P(None, None)),
              "b": NamedSharding(mesh, P())}
        shapes = {"a": big, "b": small}
        out = zero1_shardings(sh, shapes, mesh, axis="data")
        assert out["a"].spec == P("data", None)
        assert out["b"].spec == P()  # small leaf untouched


class TestCompileCache:
    def _record_updates(self, monkeypatch):
        calls = []
        monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
        return calls

    def test_leaves_a_set_env_dir_alone(self, monkeypatch, tmp_path):
        from repro.launch.compile_cache import enable_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        calls = self._record_updates(monkeypatch)
        assert enable_compile_cache() == str(tmp_path)
        assert calls == []

    def test_defaults_to_a_fixed_path_in_the_checkout(self, monkeypatch):
        from pathlib import Path

        from repro.launch import compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        calls = self._record_updates(monkeypatch)
        root = Path(compile_cache.__file__).resolve().parents[3]
        assert (root / "pyproject.toml").is_file()
        want = str(root / ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert compile_cache.enable_compile_cache() == want
        assert calls == [("jax_compilation_cache_dir", want)] * 2


def test_serve_runs_the_batched_plane_on_the_reported_device(capsys):
    from repro.launch.serve import build_parser, serve

    args = build_parser().parse_args([
        "--arch", "tinyllama-1.1b", "--smoke", "--page-size", "4",
        "--num-fast", "8", "--num-slow", "64", "--requests", "3",
        "--prompt-len", "24", "--max-new", "6",
    ])
    eng, stats = serve(args)
    assert eng.ecfg.data_plane == "batched"
    assert [len(o) for o in stats["outputs"]] == [6, 6, 6]
    assert all(0 <= t < eng.cfg.vocab for o in stats["outputs"] for t in o)
    assert stats["tokens"] == 18 and stats["demoted"] > 0
    eng.kv.pool.check_invariants()
    dev = jax.devices()[0]
    out = capsys.readouterr().out
    assert f"device {dev.platform} {dev.device_kind}" in out
