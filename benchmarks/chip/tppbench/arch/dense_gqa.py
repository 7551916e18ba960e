"""Dense GQA decoder with SwiGLU: the program's model and parameter tree,
and the work the benchmark counts.

Builds the program's ``ModelConfig`` from a configuration file, puts the
benchmark's flat weights (``tppbench/weights.py``) into the program's
parameter tree without copying them, and counts a step's work by the dense
GQA formulas of ``tppbench/counts.py``.
"""

from __future__ import annotations

from repro.models.attention import AttnConfig
from repro.models.model import ModelConfig
from repro.models.transformer import BlockSpec

from tppbench import counts, weights

REFERENCE = "dense_gqa"


def leaves(m: dict):
    return weights.dense_gqa_leaves(m)


def model_config(m: dict) -> ModelConfig:
    hd = int(m["head_dim"])
    rotary = int(m["rotary_dim"])
    attn = AttnConfig(
        d_model=int(m["hidden_size"]),
        n_heads=int(m["num_attention_heads"]),
        n_kv_heads=int(m["num_key_value_heads"]),
        head_dim=hd,
        rope="rope" if rotary == hd else "rope2d",
        rope_base=float(m["rope_theta"]),
        rotary_dim=None if rotary == hd else rotary,
        qkv_bias=bool(m.get("attention_bias", False)),
    )
    block = BlockSpec(kind="attn", attn=attn, d_ff=int(m["intermediate_size"]),
                      ffn_kind="swiglu")
    return ModelConfig(
        name=m["name"], family="dense", d_model=int(m["hidden_size"]),
        vocab=int(m["vocab_size"]), stacks=(((block,), int(m["num_hidden_layers"])),),
        norm_eps=float(m["rms_norm_eps"]),
    )


def program_params(w: dict) -> dict:
    def lin(name):
        p = {"w": w[name]}
        bias = "b" + name[1:]
        if bias in w:
            p["b"] = w[bias]
        return p

    block = {
        "norm1": {"scale": w["norm1"]},
        "attn": {"wq": lin("wq"), "wk": lin("wk"), "wv": lin("wv"), "wo": {"w": w["wo"]}},
        "norm2": {"scale": w["norm2"]},
        "ffn": {"wi_gate": {"w": w["w_gate"]}, "wi_up": {"w": w["w_up"]},
                "wo": {"w": w["w_down"]}},
    }
    return {
        "embed": {"table": w["embed"]},
        "stacks": [{"blocks": [block], "shared": [None], "lora": [None]}],
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": {"w": w["lm_head"]},
    }


class Work:
    """Counted work of a dense GQA decoder: every layer alike, and pages of
    K and V in two stores of equal width."""

    def __init__(self, shapes: counts.Shapes) -> None:
        self.shapes = shapes

    def decode_flops(self, cur_lens) -> float:
        return counts.decode_flops(self.shapes, cur_lens)

    def attention(self, cur_lens):
        flops, nbytes = counts.paged_attention_call(self.shapes, cur_lens)
        return flops * self.shapes.layers, nbytes * self.shapes.layers

    def migrate_bytes(self, pages: int) -> float:
        return 2 * counts.page_copy_bytes(self.shapes, pages)  # the K and the V store


def work(m: dict, engine: dict) -> Work:
    return Work(counts.shapes(m, engine))
