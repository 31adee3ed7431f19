"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

The paged oracles take the kernels' stacked pools, (L, n_pages, ...) with a
leading layer axis, and the layer index they address; the append oracles
write layer ``layer`` in place (``.at[layer, ...]``), so they also serve as
the paged path's XLA lowering off the TPU."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal=True, window=None,
                        softcap=None, scale=None):
    """q: (B, Hkv, G, Tq, hd); k, v: (B, Hkv, Tk, hd). Naive O(T^2)."""
    B, Hkv, G, Tq, hd = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qpos = jnp.arange(Tq)[:, None] + (Tk - Tq)   # right-aligned positions
    kpos = jnp.arange(Tk)[None, :]
    mask = jnp.ones((Tq, Tk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_attention_ref(q, kv_pages_k, kv_pages_v, layer, block_tables,
                        ctx_lens, *, softcap=None, scale=None, window=None,
                        _k=None, _v=None):
    """Decode attention over layer ``layer`` of a paged KV pool.

    q: (B, Hkv, G, hd); pools: (L, n_pages, page, Hkv, hd);
    block_tables: (B, max_pages) int32; ctx_lens: (B,) tokens valid.
    ``window`` keeps only the last ``window`` positions of each context.
    ``_k``/``_v`` bypass the pool gather with pre-gathered (B, S, Hkv, hd)
    caches (the dequantized view the quant oracle hands in).
    """
    B, Hkv, G, hd = q.shape
    if _k is not None:
        k, v = _k, _v
        max_pages, page = block_tables.shape[1], k.shape[1] // block_tables.shape[1]
        scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    else:
        page = kv_pages_k.shape[2]
        max_pages = block_tables.shape[1]
        scale = scale if scale is not None else 1.0 / math.sqrt(hd)
        # gather to (B, max_pages*page, Hkv, hd)
        k = kv_pages_k[layer, block_tables].reshape(B, max_pages * page,
                                                    Hkv, hd)
        v = kv_pages_v[layer, block_tables].reshape(B, max_pages * page,
                                                    Hkv, hd)
    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    j = jnp.arange(max_pages * page)[None]
    valid = j < ctx_lens[:, None]
    if window is not None:
        valid &= j > ctx_lens[:, None] - 1 - window
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def ragged_paged_attention_ref(q, kv_pages_k, kv_pages_v, layer,
                               block_tables, tok_seq, tok_pos, *,
                               softcap=None, scale=None, window=None,
                               _k=None, _v=None):
    """Ragged-query attention over layer ``layer`` of a paged KV pool
    (mixed-batch oracle).

    q: (N, Hkv, G, hd) flat tokens; pools: (L, n_pages, page, Hkv, hd);
    block_tables: (B, max_pages) int32; tok_seq (N,) names each token's
    block-table row; tok_pos (N,) its absolute position (-1 = padded row,
    output garbage). Token i sees kv positions <= tok_pos[i] of its own
    sequence only; ``window`` keeps the last ``window`` of those.
    ``_k``/``_v`` bypass the pool gather with pre-gathered (N, S, Hkv, hd)
    caches (the dequantized view the quant oracle hands in).
    """
    N, Hkv, G, hd = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    if _k is not None:
        k, v = _k, _v
        max_pages = block_tables.shape[1]
        page = k.shape[1] // max_pages
    else:
        page = kv_pages_k.shape[2]
        max_pages = block_tables.shape[1]
        bt = block_tables[tok_seq]                       # (N, max_pages)
        k = kv_pages_k[layer, bt].reshape(N, max_pages * page, Hkv, hd)
        v = kv_pages_v[layer, bt].reshape(N, max_pages * page, Hkv, hd)
    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    j = jnp.arange(max_pages * page)[None]
    valid = j <= tok_pos[:, None]
    if window is not None:
        valid &= j > tok_pos[:, None] - window
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def kv_append_ref(k_pool, v_pool, layer, k_new, v_new, page_ids, offsets,
                  valid):
    """Scatter new K/V rows into pool page slots (kv_append oracle).

    Pools: (L, n_pages, page, Hkv, hd). Row i lands at (layer, page_ids[i],
    offsets[i]) iff valid[i] != 0; invalid rows are dropped entirely (they
    must never touch any page).
    """
    n_pages = k_pool.shape[1]
    pids = jnp.where(valid != 0, page_ids, n_pages)      # OOB -> dropped
    k_pool = k_pool.at[layer, pids, offsets].set(k_new.astype(k_pool.dtype),
                                                 mode="drop")
    v_pool = v_pool.at[layer, pids, offsets].set(v_new.astype(v_pool.dtype),
                                                 mode="drop")
    return k_pool, v_pool


def kv_append_quant_ref(k_pool, v_pool, k_scale, v_scale, layer, k_new,
                        v_new, page_ids, offsets, valid):
    """Quantized scatter of new K/V rows into layer ``layer`` (the
    kv_append_quant oracle).

    Pools: (L, n_pages, page, Hkv, hd) quantized storage dtype; scales:
    (L, n_pages, Hkv) f32. Same three phases as the Pallas composite —
    monotone per-(page, head) scale update, whole-page requant of every
    touched page, then the row scatter with the new rows quantized at the
    post-update scales — expressed as drop-mode XLA gathers/scatters.
    Duplicate page ids in one call scatter identical requanted payloads,
    so the unordered scatter is safe. Returns (k_pool, v_pool, k_scale,
    v_scale)."""
    from repro.kernels.kv_quant import (kv_quant_qmax, quantize_rows,
                                        requant_payload,
                                        updated_page_scales)
    n_pages = k_pool.shape[1]
    qmax = kv_quant_qmax(k_pool.dtype)
    pids = jnp.where(valid != 0, page_ids, n_pages)      # OOB -> dropped
    k_scale_l, v_scale_l = k_scale[layer], v_scale[layer]
    new_k_scale, new_v_scale = updated_page_scales(
        k_scale_l, v_scale_l, k_new, v_new, pids, qmax)

    gidx = jnp.clip(pids, 0, n_pages - 1)

    def ratio(old, new):
        o, n = old[gidx], new[gidx]
        r = jnp.where(n > 0, o / jnp.where(n > 0, n, 1.0), 0.0)
        return r[:, None, :]        # broadcast over the page-slot axis

    k_pages = requant_payload(k_pool[layer, gidx],
                              ratio(k_scale_l, new_k_scale), k_pool.dtype)
    v_pages = requant_payload(v_pool[layer, gidx],
                              ratio(v_scale_l, new_v_scale), v_pool.dtype)
    k_pool = k_pool.at[layer, pids].set(k_pages, mode="drop")
    v_pool = v_pool.at[layer, pids].set(v_pages, mode="drop")

    qk = quantize_rows(k_new, new_k_scale[gidx], k_pool.dtype)
    qv = quantize_rows(v_new, new_v_scale[gidx], v_pool.dtype)
    k_pool = k_pool.at[layer, pids, offsets].set(qk, mode="drop")
    v_pool = v_pool.at[layer, pids, offsets].set(qv, mode="drop")
    return (k_pool, v_pool, k_scale.at[layer].set(new_k_scale),
            v_scale.at[layer].set(new_v_scale))


def dequant_gathered(pages, scale_pages):
    """Dequantize a block-table gather of quantized pages.

    pages: (..., n_sel, page, Hkv, hd) quantized; scale_pages:
    (..., n_sel, Hkv) f32. Returns f32 with the per-(page, head) scale
    broadcast over page slots and the head dim."""
    return pages.astype(jnp.float32) * scale_pages[..., None, :, None]


def paged_attention_quant_ref(q, kv_pages_k, kv_pages_v, k_scale, v_scale,
                              layer, block_tables, ctx_lens, *, softcap=None,
                              scale=None, window=None):
    """paged_attention_ref over quantized pools: gather pages AND their
    scales through the block table, dequantize in f32, same math."""
    k = dequant_gathered(kv_pages_k[layer, block_tables],
                         k_scale[layer, block_tables])
    v = dequant_gathered(kv_pages_v[layer, block_tables],
                         v_scale[layer, block_tables])
    B = q.shape[0]
    page, Hkv, hd = kv_pages_k.shape[2:]
    S = block_tables.shape[1] * page
    return paged_attention_ref(q, None, None, layer, block_tables, ctx_lens,
                               softcap=softcap, scale=scale, window=window,
                               _k=k.reshape(B, S, Hkv, hd),
                               _v=v.reshape(B, S, Hkv, hd))


def ragged_paged_attention_quant_ref(q, kv_pages_k, kv_pages_v, k_scale,
                                     v_scale, layer, block_tables, tok_seq,
                                     tok_pos, *, softcap=None, scale=None,
                                     window=None):
    """ragged_paged_attention_ref over quantized pools (see above)."""
    bt = block_tables[tok_seq]
    k = dequant_gathered(kv_pages_k[layer, bt], k_scale[layer, bt])
    v = dequant_gathered(kv_pages_v[layer, bt], v_scale[layer, bt])
    N = q.shape[0]
    page, Hkv, hd = kv_pages_k.shape[2:]
    S = block_tables.shape[1] * page
    return ragged_paged_attention_ref(
        q, None, None, layer, block_tables, tok_seq, tok_pos,
        softcap=softcap, scale=scale, window=window,
        _k=k.reshape(N, S, Hkv, hd), _v=v.reshape(N, S, Hkv, hd))


def swap_pack_ref(pool, page_ids):
    """Gather scattered pages into a contiguous staging buffer.
    pool: (n_pages, page, Hkv, hd); page_ids: (n,)."""
    return pool[page_ids]


def swap_unpack_ref(pool, staging, page_ids):
    """Scatter a contiguous staging buffer back into pool pages."""
    return pool.at[page_ids].set(staging.astype(pool.dtype))
