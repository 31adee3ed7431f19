"""Public jit'd entry points for the Pallas kernels, with pure-XLA fallbacks.

``use_pallas=False`` (or non-TPU backends where interpret mode would be
slow inside a jitted serving step) routes to the mathematically identical
XLA implementations, which are also the lowering path used by the pjit
dry-runs. The Pallas kernels are validated against ``ref.py`` in
interpret mode by the test suite.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.kv_append import kv_append
from repro.kernels.kv_quant import kv_append_quant
from repro.kernels.ragged_paged_attention import ragged_paged_attention
from repro.kernels.gla_scan import gla_scan
from repro.kernels.swap_pack import swap_pack, swap_unpack

__all__ = ["flash_attention_op",
           "ragged_paged_attention_op", "kv_append_op",
           "kv_append_quant_op",
           "swap_pack_op", "swap_unpack_op", "gla_scan_op",
           "flash_attention", "ragged_paged_attention",
           "kv_append", "kv_append_quant", "swap_pack", "swap_unpack",
           "gla_scan"]


def gla_scan_op(q, k, v, log_a, *, chunk=128, use_pallas=None,
                interpret=None):
    """Chunked gated-linear-attention (Mamba2 SSD / mLSTM core)."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return gla_scan(q, k, v, log_a, chunk=chunk, interpret=interpret)
    from repro.models.ssm import chunked_gla
    return chunked_gla(q, k, v, log_a, chunk)


def flash_attention_op(q, k, v, *, causal=True, window=None, softcap=None,
                       use_pallas=None, interpret=None):
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, interpret=interpret)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   softcap=softcap)


def ragged_paged_attention_op(q, k_pool, v_pool, layer, block_tables,
                              tok_seq, tok_pos, *, k_scale=None,
                              v_scale=None, softcap=None, window=None,
                              use_pallas=None, interpret=None):
    """Mixed-batch ragged-query attention (chunk + decode tokens flattened)
    over layer ``layer`` of the stacked pools."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return ragged_paged_attention(q, k_pool, v_pool, layer, block_tables,
                                      tok_seq, tok_pos, k_scale=k_scale,
                                      v_scale=v_scale, softcap=softcap,
                                      window=window, interpret=interpret)
    if k_scale is not None:
        return ref.ragged_paged_attention_quant_ref(
            q, k_pool, v_pool, k_scale, v_scale, layer, block_tables,
            tok_seq, tok_pos, softcap=softcap, window=window)
    return ref.ragged_paged_attention_ref(q, k_pool, v_pool, layer,
                                          block_tables, tok_seq, tok_pos,
                                          softcap=softcap, window=window)


def kv_append_op(k_pool, v_pool, layer, k_new, v_new, page_ids, offsets,
                 valid, *, use_pallas=None, interpret=None):
    """In-place scatter of new token K/V rows into pool page slots of layer
    ``layer`` of the stacked pools."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return kv_append(k_pool, v_pool, layer, k_new, v_new, page_ids,
                         offsets, valid, interpret=interpret)
    return ref.kv_append_ref(k_pool, v_pool, layer, k_new, v_new, page_ids,
                             offsets, valid)


def kv_append_quant_op(k_pool, v_pool, k_scale, v_scale, layer, k_new,
                       v_new, page_ids, offsets, valid, *, discard_pid=None,
                       use_pallas=None, interpret=None):
    """Quantized in-place scatter of new token K/V rows + per-page scale
    update into layer ``layer`` (requantize-on-append; DESIGN.md §17).
    ``discard_pid`` is required on the Pallas path (kv_append's
    write-discard contract); the XLA path drops invalid rows by OOB
    scatter and ignores it."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return kv_append_quant(k_pool, v_pool, k_scale, v_scale, layer,
                               k_new, v_new, page_ids, offsets, valid,
                               discard_pid, interpret=interpret)
    return ref.kv_append_quant_ref(k_pool, v_pool, k_scale, v_scale, layer,
                                   k_new, v_new, page_ids, offsets, valid)


def swap_pack_op(pool, page_ids, *, use_pallas=None, interpret=None):
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return swap_pack(pool, page_ids, interpret=interpret)
    return ref.swap_pack_ref(pool, page_ids)


def swap_unpack_op(pool, staging, page_ids, *, use_pallas=None,
                   interpret=None):
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return swap_unpack(pool, staging, page_ids, interpret=interpret)
    return ref.swap_unpack_ref(pool, staging, page_ids)
