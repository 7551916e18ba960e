"""Operations and bytes the algorithm needs, computed from shapes.

These count the work itself, whatever implements it: the pages that
top-k selection picked for live lanes, not padding lanes or pad entries of
the block table; the page payloads that migrate, each read and written
once; the matrix products of one token, not a recomputation. ``attended``
follows the served page selection, which every architecture shares;
``Shapes`` and the other formulas are dense GQA's, which its adapter
(``tppbench/arch/dense_gqa.py``) hands the readers as its ``work``. The
table of peaks (``peaks.json``) is keyed by JAX's ``device_kind``; a device
that is not in it is an error.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, NamedTuple

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}")
    return table[device_kind]


class Shapes(NamedTuple):
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    page_size: int
    topk_pages: int
    recent_pages: int
    itemsize: int  # bytes of one stored KV element


def shapes(model: dict, engine: dict) -> Shapes:
    return Shapes(
        layers=int(model["num_hidden_layers"]), d_model=int(model["hidden_size"]),
        heads=int(model["num_attention_heads"]), kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]), d_ff=int(model["intermediate_size"]),
        vocab=int(model["vocab_size"]), page_size=int(engine["page_size"]),
        topk_pages=int(engine["topk_pages"]), recent_pages=int(engine["recent_pages"]),
        itemsize=4 if model.get("torch_dtype", "float32") == "float32" else 2,
    )


def attended(s: Shapes, t: int) -> (int, int):
    """(pages, tokens) one lane attends at the step that writes position
    ``t``: the top-k older pages, the recent pages and the write page, and
    the positions up to ``t`` in them. Of ``s`` it reads only the
    engine's ``page_size``, ``topk_pages`` and ``recent_pages``."""
    P = s.page_size
    n = -(-t // P)  # pages holding positions < t
    older = max(n - s.recent_pages, 0)
    pages = min(older, s.topk_pages) + min(n, s.recent_pages)
    full = pages  # every selected page before the write page is full ...
    if t % P:
        full -= 1  # ... except the write page, when it is one of them
        return pages, full * P + t % P + 1
    return pages + 1, full * P + 1


def matmul_params_per_token(s: Shapes) -> int:
    """Weights that each decoded token multiplies: attention projections,
    SwiGLU and the output head (the embedding is a lookup)."""
    attn = s.d_model * (s.heads + 2 * s.kv_heads) * s.head_dim + s.heads * s.head_dim * s.d_model
    ffn = 3 * s.d_model * s.d_ff
    return s.layers * (attn + ffn) + s.d_model * s.vocab


def decode_flops(s: Shapes, cur_lens: Iterable[int]) -> float:
    """Model FLOPs of one decode step over live lanes."""
    total = 0.0
    for t in cur_lens:
        _, tokens = attended(s, t)
        total += 2 * matmul_params_per_token(s) + s.layers * 4 * s.heads * s.head_dim * tokens
    return total


def paged_attention_call(s: Shapes, cur_lens: Iterable[int]) -> (float, float):
    """(FLOPs, bytes) of one ``paged_attention`` call (one layer, one step):
    K and V of the selected pages of every live lane, plus q and the output."""
    flops = 0.0
    nbytes = 0.0
    page = s.kv_heads * s.page_size * s.head_dim * s.itemsize
    for t in cur_lens:
        pages, tokens = attended(s, t)
        flops += 4 * s.heads * s.head_dim * tokens
        nbytes += 2 * pages * page + 2 * s.heads * s.head_dim * 4
    return flops, nbytes


def page_copy_bytes(s: Shapes, pages: int) -> float:
    """Bytes of one ``page_gather`` or ``page_scatter`` call per store that
    moves ``pages`` pages: every layer's slice of the page read once and
    written once."""
    return 2.0 * pages * s.layers * s.kv_heads * s.page_size * s.head_dim * s.itemsize


def roofline_share(flops: float, nbytes: float, seconds: float, peak: Dict[str, float]):
    """(share in %, bound) of the least time the chip could take."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
