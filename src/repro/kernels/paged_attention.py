"""Paged-attention decode: one new query token per sequence attends to its
KV cache scattered across fixed-size pages of a global pool (vLLM-style
PagedAttention, re-thought for TPU).

It is the ragged kernel's special case — token b is sequence b at position
ctx_lens[b] - 1 — so both share one Pallas kernel
(``ragged_paged_attention``): each sequence is one query tile, and its page
loop walks KV blocks of the block table, which is a scalar-prefetch operand,
up to its last live page only.

Layout: q (B, Hkv, G, hd); pools (L, n_pages, page, Hkv, hd) stacked over
layers, read at layer ``layer``; block_tables (B, max_pages) int32;
ctx_lens (B,) int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ragged_paged_attention import ragged_paged_attention


@functools.partial(jax.jit,
                   static_argnames=("softcap", "scale", "window",
                                    "interpret"))
def paged_attention(q, k_pool, v_pool, layer, block_tables, ctx_lens, *,
                    k_scale=None, v_scale=None,
                    softcap=None, scale=None, window=None, interpret=None):
    """q: (B, Hkv, G, hd); pools: (L, n_pages, page, Hkv, hd); layer: int32
    scalar; block_tables: (B, max_pages); ctx_lens: (B,). ``window``
    (static) keeps only the last ``window`` positions of each context
    (sliding-window attention); rows with ctx_lens == 0 are padding (output
    zeros).
    ``k_scale``/``v_scale`` (L, n_pages, Hkv) f32 dequantize low-bit pools
    in-register (both set or both None). Returns (B, Hkv, G, hd)."""
    B = q.shape[0]
    return ragged_paged_attention(
        q, k_pool, v_pool, layer, block_tables,
        jnp.arange(B, dtype=jnp.int32), ctx_lens.astype(jnp.int32) - 1,
        k_scale=k_scale, v_scale=v_scale, softcap=softcap, scale=scale, window=window, interpret=interpret)
