"""Public jit'd wrappers over the Pallas kernels.

:func:`_on_tpu` is the one place that decides what the wrappers run.  On
a TPU backend every wrapper runs its compiled Pallas kernel; on any other
backend (the CPU tests) it runs the jnp oracle from ``kernels/ref.py``.
Nothing on the chip can reach the oracle, and an error raised while the
backend starts propagates instead of reading as "not a TPU".  Tests that
want the kernels themselves off the chip call the kernel modules directly
with ``interpret=True`` (``tests/test_kernels.py``).
"""

from __future__ import annotations

import functools

import jax

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.page_migrate import page_gather as _gather, page_scatter as _scatter
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.router_topk import router_topk as _router


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("causal", "window", "scale"))
def flash_attention(q, k, v, causal=True, window=None, scale=None):
    if not _on_tpu():
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window, scale=scale)
    return _flash(q, k, v, causal=causal, window=window, scale=scale)


@functools.partial(jax.jit, static_argnames=("scale", "window"))
def paged_attention(q, k_pages, v_pages, block_table, lengths=None, scale=None,
                    page_pos=None, q_pos=None, window=None):
    if not _on_tpu():
        return _ref.paged_attention_ref(
            q, k_pages, v_pages, block_table, lengths, scale=scale,
            page_pos=page_pos, q_pos=q_pos, window=window)
    return _paged(q, k_pages, v_pages, block_table, lengths, scale=scale,
                  page_pos=page_pos, q_pos=q_pos, window=window)


@jax.jit
def page_gather(src, frames):
    if not _on_tpu():
        return _ref.page_gather_ref(src, frames)
    return _gather(src, frames)


@functools.partial(jax.jit, donate_argnums=(0,))
def page_scatter(dst, frames, pages):
    if not _on_tpu():
        return _ref.page_scatter_ref(dst, frames, pages)
    return _scatter(dst, frames, pages)


@functools.partial(jax.jit, static_argnames=("k",))
def router_topk(logits, k):
    if not _on_tpu():
        return _ref.router_topk_ref(logits, k)
    return _router(logits, k)
