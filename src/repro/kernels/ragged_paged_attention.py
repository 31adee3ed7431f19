"""Ragged-query paged-attention Pallas TPU kernel — the fused mixed-batch
iteration's attention core (DESIGN.md §10).

One scheduler iteration's *entire* query workload — every prefill chunk's
tokens and every decode's single token — arrives as one flat ragged batch of
N tokens. Token i belongs to sequence ``tok_seq[i]`` and sits at absolute
position ``tok_pos[i]``; it attends to that sequence's KV pages through
``block_tables[tok_seq[i]]`` with the causal mask ``kv position <=
tok_pos[i]``. Because every new token's K/V was appended to the pool before
this kernel runs, that single mask covers both cases at once: a decode
token (query length 1) sees its whole context including itself, and a chunk
token sees the prefix plus the earlier tokens *of its own chunk* — the
chunk-internal causal contract — while later chunk tokens and every other
sequence's pages are invisible.

``paged_attention`` (one decode token per sequence with per-sequence
ctx_lens) is the special case tok_seq = arange(B), tok_pos = ctx_lens - 1.
Padded token rows carry ``tok_pos[i] == -1``: no page is live for them,
their output is zeros, and the caller ignores it.

Layout: q (N, Hkv, G, hd); pools (n_pages, page, Hkv, hd);
block_tables (B, max_pages) int32; tok_seq/tok_pos (N,) int32.
Grid: (N, max_pages), pages innermost. Each step moves one whole page —
all Hkv heads — so every block's last two dims equal the array's (the TPU
tiling rule; a 1-wide slice of the Hkv axis is refused by the compiler),
and the heads are an unrolled loop inside the step. The block table,
tok_seq and tok_pos are scalar-prefetch operands so the HBM->VMEM DMA for
page ``block_tables[tok_seq[n], i]`` issues while page i-1 is computed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ragged_kernel(block_tables, tok_seq, tok_pos,  # scalar-prefetch operands
                   q_ref, k_ref, v_ref, *rest,
                   page: int, softcap, scale, window, quant: bool = False):
    # quantized pools (DESIGN.md §17): the page's per-head f32 scales ride
    # the same scalar-prefetch indirection as a (Hkv, 1) column and are
    # broadcast over the page in-register — the fp32 pool never exists
    if quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    del block_tables, tok_seq
    n = pl.program_id(0)
    i = pl.program_id(1)
    npages = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ctx = tok_pos[n] + 1                 # this token sees positions < ctx

    live = i * page < ctx
    if window is not None:
        # the query sits at position ctx-1; pages entirely below the
        # window's left edge contribute nothing — skip them
        live = live & ((i + 1) * page > ctx - window)

    @pl.when(live)
    def _compute():
        k_page = k_ref[0].astype(jnp.float32)             # (page, Hkv, hd)
        v_page = v_ref[0].astype(jnp.float32)
        if quant:
            k_page = k_page * ks_ref[0][None]
            v_page = v_page * vs_ref[0][None]
        for h in range(k_page.shape[1]):
            q = q_ref[0, h].astype(jnp.float32)           # (G, hd)
            k = k_page[:, h, :]                           # (page, hd)
            v = v_page[:, h, :]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + i * page
            s = jnp.where(pos < ctx, s, NEG_INF)
            if window is not None:
                s = jnp.where(pos > ctx - 1 - window, s, NEG_INF)

            m_prev = m_ref[h]                             # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[h] = m_new
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(i == npages - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("softcap", "scale", "window",
                                    "interpret"))
def ragged_paged_attention(q, k_pool, v_pool, block_tables, tok_seq,
                           tok_pos, *, k_scale=None, v_scale=None,
                           softcap=None, scale=None, window=None,
                           interpret=None):
    """q: (N, Hkv, G, hd) flat mixed-batch query tokens; pools:
    (n_pages, page, Hkv, hd); block_tables: (B, max_pages); tok_seq (N,)
    int32 names each token's sequence (block-table row); tok_pos (N,) int32
    is its absolute position (-1 marks a padded token row — output zeros).
    ``window`` (static) keeps only the last ``window`` positions visible.
    ``k_scale``/``v_scale`` (n_pages, Hkv) f32 dequantize low-bit pools
    in-register (both set or both None). Returns (N, Hkv, G, hd)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    N, Hkv, G, hd = q.shape
    n_pages, page, _, _ = k_pool.shape
    max_pages = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    quant = k_scale is not None

    kernel = functools.partial(_ragged_kernel, page=page, softcap=softcap,
                               scale=scale, window=window, quant=quant)
    pool_spec = pl.BlockSpec(
        (1, page, Hkv, hd),
        lambda n, i, bt, ts, tp: (bt[ts[n], i], 0, 0, 0))
    seq_spec = pl.BlockSpec((1, Hkv, G, hd),
                            lambda n, i, bt, ts, tp: (n, 0, 0, 0))
    in_specs = [seq_spec, pool_spec, pool_spec]
    operands = [q, k_pool, v_pool]
    if quant:
        # (n_pages, Hkv, 1): a page's scales as one legal (Hkv, 1) block
        scale_spec = pl.BlockSpec(
            (1, Hkv, 1), lambda n, i, bt, ts, tp: (bt[ts[n], i], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale[..., None], v_scale[..., None]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N, max_pages),
        in_specs=in_specs,
        out_specs=seq_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, hd), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(block_tables, tok_seq, tok_pos, *operands)
