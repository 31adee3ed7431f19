"""Attention: blocked (flash-style, XLA) full-sequence attention, GQA and MLA
projections, single-token decode and chunked-extend paths over slotted KV
caches (the plain reference and the dry-run's steps), and the fused step's
ragged pass over the engine's paged pools.

On TPU the fused step runs the Pallas kernels in ``repro.kernels``
(kv_append, ragged paged attention); elsewhere it runs their jnp mirrors.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import AttentionCfg
from repro.models.common import apply_rope, dense_init, softcap

NEG_INF = -1e30


def _paged_use_pallas() -> bool:
    """Fused-step kernel dispatch: Pallas on TPU, mirrored jnp elsewhere.

    The jnp mirror computes each token row's attention over its own
    sequence's gathered block-table view (same einsum strings, masking and
    scaling as the kernel's oracle, ``ref.ragged_paged_attention_ref``).
    """
    return jax.default_backend() == "tpu"


def _require_discard(discard_pid) -> None:
    """The GQA fused path appends through kv_append, whose contract routes
    invalid rows to a write-discard page; without one there is no correct
    Pallas path, so a missing page is an error, never a quiet jnp
    fallback."""
    if discard_pid is None:
        raise ValueError("paged GQA attention needs discard_pid, the "
                         "caller's write-discard page")


def effective_window(a: AttentionCfg, override) -> Optional[int]:
    """Resolve a window override against the block's configured window.

    override == "cfg" -> the config's sliding window; None -> force full
    attention; int w -> min(w, cfg window) (long_500k sub-quadratic policy).
    MLA always attends the full compressed latent (DESIGN.md §4).
    """
    if a.kind == "mla":
        return None
    if override == "cfg":
        return a.sliding_window
    if override is None:
        return None
    return min(override, a.sliding_window) if a.sliding_window else override


# ==========================================================================
# Blocked full-sequence attention (train / prefill)
# ==========================================================================

def blocked_attention(q, k, v, q_positions, kv_positions, *, causal=True,
                      window: Optional[int] = None,
                      softcap_val: Optional[float] = None,
                      q_chunk: int = 1024, kv_chunk: int = 1024,
                      scale: Optional[float] = None):
    """Online-softmax attention that never materializes (Tq, Tk) scores.

    q: (B, Tq, Hq, hd); k, v: (B, Tk, Hkv, hd); Hq % Hkv == 0.
    positions: (Tq,) and (Tk,) int32 absolute positions (rope-consistent).
    Returns (B, Tq, Hq, hd) in q.dtype.
    """
    B, Tq, Hq, hd = q.shape
    _, Tk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    q_chunk = min(q_chunk, Tq)
    kv_chunk = min(kv_chunk, Tk)
    nq = -(-Tq // q_chunk)
    nk = -(-Tk // kv_chunk)
    pad_q = nq * q_chunk - Tq
    pad_k = nk * kv_chunk - Tk

    qq = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kk = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vv = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v
    qpos = jnp.pad(q_positions, (0, pad_q), constant_values=-1)
    kpos = jnp.pad(kv_positions, (0, pad_k), constant_values=2**30)

    # (nq, B, Hkv, G, cq, hd)
    qq = (qq.reshape(B, nq, q_chunk, Hkv, G, hd)
            .transpose(1, 0, 3, 4, 2, 5)) * scale
    kk = kk.reshape(B, nk, kv_chunk, Hkv, hd).transpose(1, 0, 3, 2, 4)
    vvs = vv.reshape(B, nk, kv_chunk, Hkv, hd).transpose(1, 0, 3, 2, 4)
    qpos = qpos.reshape(nq, q_chunk)
    kpos_c = kpos.reshape(nk, kv_chunk)

    def q_block(args):
        qb, qp = args                                  # (B,Hkv,G,cq,hd), (cq,)

        def kv_body(carry, inp):
            acc, mx, ssum = carry
            kb, vb, kp = inp
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qb.astype(jnp.float32),
                           kb.astype(jnp.float32))
            if softcap_val is not None:
                s = softcap(s, softcap_val)
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= kp[None, :] <= qp[:, None]
            if window is not None:
                mask &= kp[None, :] > qp[:, None] - window
            mask &= (qp[:, None] >= 0) & (kp[None, :] < 2**30)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            new_mx = jnp.maximum(mx, jnp.max(s, axis=-1))
            corr = jnp.exp(mx - new_mx)
            p = jnp.exp(s - new_mx[..., None])
            ssum = ssum * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vb.astype(jnp.float32))
            return (acc, new_mx, ssum), None

        init = (jnp.zeros((B, Hkv, G, q_chunk, hd), jnp.float32),
                jnp.full((B, Hkv, G, q_chunk), NEG_INF, jnp.float32),
                jnp.zeros((B, Hkv, G, q_chunk), jnp.float32))
        (acc, _, ssum), _ = jax.lax.scan(init=init, f=kv_body,
                                         xs=(kk, vvs, kpos_c))
        return acc / jnp.maximum(ssum[..., None], 1e-37)

    out = jax.lax.map(q_block, (qq, qpos))             # (nq,B,Hkv,G,cq,hd)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * q_chunk, Hq, hd)
    return out[:, :Tq].astype(q.dtype)


# ==========================================================================
# GQA projections
# ==========================================================================

def init_gqa(key, d_model: int, a: AttentionCfg, dtype):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d_model, a.n_heads, a.head_dim), in_axis=0,
                         dtype=dtype),
        "wk": dense_init(ks[1], (d_model, a.n_kv_heads, a.head_dim),
                         in_axis=0, dtype=dtype),
        "wv": dense_init(ks[2], (d_model, a.n_kv_heads, a.head_dim),
                         in_axis=0, dtype=dtype),
        "wo": dense_init(ks[3], (a.n_heads, a.head_dim, d_model),
                         in_axis=(0, 1), dtype=dtype),
    }
    if a.qkv_bias:
        p["bq"] = jnp.zeros((a.n_heads, a.head_dim), dtype)
        p["bk"] = jnp.zeros((a.n_kv_heads, a.head_dim), dtype)
        p["bv"] = jnp.zeros((a.n_kv_heads, a.head_dim), dtype)
    return p


def gqa_qkv(p, a: AttentionCfg, x, positions):
    """x: (B, T, d); positions (T,) or (B, T). Returns roped q, k and v."""
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"])
    if a.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if positions.ndim == 1:
        positions = positions[None, :]
    q = apply_rope(q, positions, a.rope_theta)
    k = apply_rope(k, positions, a.rope_theta)
    return q, k, v


def gqa_forward(p, a: AttentionCfg, x, positions, *, window_override="cfg"):
    window = effective_window(a, window_override)
    q, k, v = gqa_qkv(p, a, x, positions)
    out = blocked_attention(q, k, v, positions, positions, causal=True,
                            window=window, softcap_val=a.logit_softcap)
    return jnp.einsum("bthk,hkd->btd", out, p["wo"]), {"k": k, "v": v}


def gqa_decode(p, a: AttentionCfg, x, cache, pos, *, window_override="cfg"):
    """x: (B, d) one token per sequence; cache {"k","v"}: (B, S, Hkv, hd);
    pos: (B,) current absolute position (the new token's index)."""
    window = effective_window(a, window_override)
    B, d = x.shape
    S = cache["k"].shape[1]
    q = jnp.einsum("bd,dhk->bhk", x, p["wq"])
    k = jnp.einsum("bd,dhk->bhk", x, p["wk"])
    v = jnp.einsum("bd,dhk->bhk", x, p["wv"])
    if a.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = apply_rope(q[:, None], pos[:, None], a.rope_theta)[:, 0]
    k = apply_rope(k[:, None], pos[:, None], a.rope_theta)[:, 0]

    slot = jnp.mod(pos, S)  # ring-buffer semantics when S < max position
    bidx = jnp.arange(B)
    k_cache = cache["k"].at[bidx, slot].set(k.astype(cache["k"].dtype))
    v_cache = cache["v"].at[bidx, slot].set(v.astype(cache["v"].dtype))

    Hkv, hd = a.n_kv_heads, a.head_dim
    G = a.n_heads // Hkv
    qh = q.reshape(B, Hkv, G, hd).astype(jnp.float32)
    s = jnp.einsum("bhgk,bshk->bhgs", qh,
                   k_cache.astype(jnp.float32)) / math.sqrt(hd)
    if a.logit_softcap is not None:
        s = softcap(s, a.logit_softcap)
    # position of the token stored in each slot (ring buffer aware)
    j = jnp.arange(S)[None, :]
    stored_pos = jnp.where(j <= slot[:, None], j,
                           j - S) + (pos - slot)[:, None]
    valid = (stored_pos >= 0) & (stored_pos <= pos[:, None])
    if window is not None:
        valid &= stored_pos > (pos[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshk->bhgk", w, v_cache.astype(jnp.float32))
    out = out.reshape(B, a.n_heads, hd).astype(x.dtype)
    return jnp.einsum("bhk,hkd->bd", out, p["wo"]), {"k": k_cache, "v": v_cache}


def gqa_extend(p, a: AttentionCfg, x, cache, start, *, window_override="cfg"):
    """Chunked-prefill/recompute: x: (B, T, d) new tokens at absolute
    positions start[b] + t; cache holds the already-computed prefix (no ring
    wrap — requires S >= start + T). Attends each new token to prefix+chunk.
    Returns (out (B, T, d), new cache)."""
    window = effective_window(a, window_override)
    B, T, d = x.shape
    S = cache["k"].shape[1]
    positions = start[:, None] + jnp.arange(T)[None, :]          # (B, T)
    q, k, v = gqa_qkv(p, a, x, positions)
    bidx = jnp.arange(B)[:, None]
    k_cache = cache["k"].at[bidx, positions].set(k.astype(cache["k"].dtype))
    v_cache = cache["v"].at[bidx, positions].set(v.astype(cache["v"].dtype))

    Hkv, hd = a.n_kv_heads, a.head_dim
    G = a.n_heads // Hkv
    qh = q.reshape(B, T, Hkv, G, hd).astype(jnp.float32)
    s = jnp.einsum("bthgk,bshk->bhgts", qh,
                   k_cache.astype(jnp.float32)) / math.sqrt(hd)
    if a.logit_softcap is not None:
        s = softcap(s, a.logit_softcap)
    j = jnp.arange(S)[None, None, :]
    qpos = positions[:, :, None]
    valid = j <= qpos
    if window is not None:
        valid &= j > qpos - window
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgts,bshk->bthgk", w, v_cache.astype(jnp.float32))
    out = out.reshape(B, T, a.n_heads, hd).astype(x.dtype)
    return jnp.einsum("bthk,hkd->btd", out, p["wo"]), {"k": k_cache,
                                                       "v": v_cache}


def mla_extend(p, a: AttentionCfg, x, cache, start, *, window_override="cfg"):
    """Absorbed chunked-prefill over the compressed latent cache."""
    window = effective_window(a, window_override)
    B, T, d = x.shape
    S = cache["c"].shape[1]
    positions = start[:, None] + jnp.arange(T)[None, :]
    qn, qr = _mla_q(p, a, x, positions)                   # (B,T,H,nope/rope)
    c_new, kr_new = _mla_latent(p, a, x, positions)
    bidx = jnp.arange(B)[:, None]
    c_cache = cache["c"].at[bidx, positions].set(c_new.astype(cache["c"].dtype))
    kr_cache = cache["kr"].at[bidx, positions].set(
        kr_new.astype(cache["kr"].dtype))

    q_lat = jnp.einsum("bthn,lhn->bthl", qn.astype(jnp.float32),
                       p["w_uk"].astype(jnp.float32))
    qk = a.qk_nope_head_dim + a.qk_rope_head_dim
    s = (jnp.einsum("bthl,bsl->bhts", q_lat, c_cache.astype(jnp.float32))
         + jnp.einsum("bthr,bsr->bhts", qr.astype(jnp.float32),
                      kr_cache.astype(jnp.float32))) / math.sqrt(qk)
    j = jnp.arange(S)[None, None, :]
    qpos = positions[:, :, None]
    valid = j <= qpos
    if window is not None:
        valid &= j > qpos - window
    s = jnp.where(valid[:, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhts,bsl->bthl", w, c_cache.astype(jnp.float32))
    out = jnp.einsum("bthl,lhv->bthv", ctx,
                     p["w_uv"].astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("bthv,hvd->btd", out, p["wo"]), {"c": c_cache,
                                                       "kr": kr_cache}


def attention_extend(p, a, x, cache, start, *, window_override="cfg"):
    fn = mla_extend if a.kind == "mla" else gqa_extend
    return fn(p, a, x, cache, start, window_override=window_override)


# ==========================================================================
# MLA (deepseek-v3)
# ==========================================================================

def init_mla(key, d_model: int, a: AttentionCfg, dtype):
    ks = jax.random.split(key, 7)
    qk = a.qk_nope_head_dim + a.qk_rope_head_dim
    return {
        "w_dq": dense_init(ks[0], (d_model, a.q_lora_rank), dtype=dtype),
        "q_norm": jnp.zeros((a.q_lora_rank,), dtype),
        "w_uq": dense_init(ks[1], (a.q_lora_rank, a.n_heads, qk), in_axis=0,
                           dtype=dtype),
        "w_dkv": dense_init(ks[2], (d_model, a.kv_lora_rank + a.qk_rope_head_dim),
                            dtype=dtype),
        "kv_norm": jnp.zeros((a.kv_lora_rank,), dtype),
        "w_uk": dense_init(ks[3], (a.kv_lora_rank, a.n_heads, a.qk_nope_head_dim),
                           in_axis=0, dtype=dtype),
        "w_uv": dense_init(ks[4], (a.kv_lora_rank, a.n_heads, a.v_head_dim),
                           in_axis=0, dtype=dtype),
        "wo": dense_init(ks[5], (a.n_heads, a.v_head_dim, d_model),
                         in_axis=(0, 1), dtype=dtype),
    }


def _mla_latent(p, a: AttentionCfg, x, positions):
    """Compute normed latent c (B,T,kv_lora) and roped shared k_rope."""
    from repro.models.common import rms_norm
    ckr = jnp.einsum("btd,dl->btl", x, p["w_dkv"])
    c, kr = jnp.split(ckr, [a.kv_lora_rank], axis=-1)
    c = rms_norm(c, p["kv_norm"])
    if positions.ndim == 1:
        positions = positions[None, :]
    kr = apply_rope(kr[:, :, None, :], positions, a.rope_theta)[:, :, 0]
    return c, kr


def _mla_q(p, a: AttentionCfg, x, positions):
    from repro.models.common import rms_norm
    cq = rms_norm(jnp.einsum("btd,dl->btl", x, p["w_dq"]), p["q_norm"])
    q = jnp.einsum("btl,lhk->bthk", cq, p["w_uq"])
    qn, qr = jnp.split(q, [a.qk_nope_head_dim], axis=-1)
    if positions.ndim == 1:
        positions = positions[None, :]
    qr = apply_rope(qr, positions, a.rope_theta)
    return qn, qr


def mla_forward(p, a: AttentionCfg, x, positions, *, window_override="cfg"):
    """Non-absorbed full-sequence path (train / prefill)."""
    window = effective_window(a, window_override)
    B, T, _ = x.shape
    qn, qr = _mla_q(p, a, x, positions)
    c, kr = _mla_latent(p, a, x, positions)
    kn = jnp.einsum("btl,lhk->bthk", c, p["w_uk"])
    v = jnp.einsum("btl,lhv->bthv", c, p["w_uv"])
    krh = jnp.broadcast_to(kr[:, :, None, :], (B, T, a.n_heads,
                                               a.qk_rope_head_dim))
    q = jnp.concatenate([qn, qr], axis=-1)
    k = jnp.concatenate([kn, krh], axis=-1)
    # pad v to qk dim so blocked_attention's uniform head_dim applies
    qk = a.qk_nope_head_dim + a.qk_rope_head_dim
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk - a.v_head_dim)))
    out = blocked_attention(q, k, vp, positions, positions, causal=True,
                            window=window, scale=1.0 / math.sqrt(qk))
    out = out[..., :a.v_head_dim]
    return jnp.einsum("bthv,hvd->btd", out, p["wo"]), {"c": c, "kr": kr}


def mla_decode(p, a: AttentionCfg, x, cache, pos, *, window_override="cfg"):
    """Absorbed decode: attends in the compressed latent space.

    cache: {"c": (B, S, kv_lora), "kr": (B, S, rope)}.
    """
    window = effective_window(a, window_override)
    B, d = x.shape
    S = cache["c"].shape[1]
    qn, qr = _mla_q(p, a, x[:, None], pos[:, None])
    qn, qr = qn[:, 0], qr[:, 0]                       # (B, H, nope/rope)
    c_new, kr_new = _mla_latent(p, a, x[:, None], pos[:, None])
    slot = jnp.mod(pos, S)
    bidx = jnp.arange(B)
    c_cache = cache["c"].at[bidx, slot].set(c_new[:, 0].astype(cache["c"].dtype))
    kr_cache = cache["kr"].at[bidx, slot].set(kr_new[:, 0].astype(cache["kr"].dtype))

    q_lat = jnp.einsum("bhn,lhn->bhl", qn.astype(jnp.float32),
                       p["w_uk"].astype(jnp.float32))
    qk = a.qk_nope_head_dim + a.qk_rope_head_dim
    s = (jnp.einsum("bhl,bsl->bhs", q_lat, c_cache.astype(jnp.float32))
         + jnp.einsum("bhr,bsr->bhs", qr.astype(jnp.float32),
                      kr_cache.astype(jnp.float32))) / math.sqrt(qk)
    j = jnp.arange(S)[None, :]
    stored_pos = jnp.where(j <= slot[:, None], j, j - S) + (pos - slot)[:, None]
    valid = (stored_pos >= 0) & (stored_pos <= pos[:, None])
    if window is not None:
        valid &= stored_pos > (pos[:, None] - window)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhs,bsl->bhl", w, c_cache.astype(jnp.float32))
    out = jnp.einsum("bhl,lhv->bhv", ctx,
                     p["w_uv"].astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("bhv,hvd->bd", out, p["wo"]), {"c": c_cache,
                                                     "kr": kr_cache}


# ==========================================================================
# Fused mixed-batch pass over the paged pools (ragged chunks + decodes in
# one dispatch; DESIGN.md §9/§10)
# ==========================================================================
# These operate on the engine's shared page pools directly: the new tokens'
# K/V are scattered into their pool page slots (kv_append kernel on TPU, a
# drop-mode scatter elsewhere) and attention reads the pool through the
# block table — no per-request contiguous cache is ever materialized. The
# pools are the layer group's stacks, (L, n_pages, ...), carried through the
# layer scan; each call addresses layer ``layer`` of them in place
# (pool[layer]) and never slices a layer's pool out.
#
# One scheduler iteration's whole token workload arrives flattened: token i
# belongs to sequence tok_seq[i] at absolute position tok_pos[i] (-1 marks a
# padded row, whose write is masked). All N tokens' K/V are appended to the
# pool in ONE kv_append call, then each token attends through its
# sequence's block table with the causal mask `kv pos <= tok_pos[i]` —
# which simultaneously gives decode tokens their full context and chunk
# tokens the prefix plus the earlier tokens of their own chunk (the
# chunk-internal causal contract).

def gqa_mixed_paged(p, a: AttentionCfg, x, pool, layer, block_tables,
                    tok_seq, tok_pos, *, window_override="cfg",
                    discard_pid=None):
    """x: (N, d) flat mixed-batch tokens; pool {"k","v"}:
    (L, n_pages, page, Hkv, hd), used at layer ``layer``; block_tables:
    (B, max_pages) int32; tok_seq/tok_pos: (N,) int32 (tok_pos == -1 marks a padded row: its K/V
    write is dropped and its output is garbage). Returns (out (N, d),
    updated pool)."""
    from repro.kernels.ops import kv_append_op, ragged_paged_attention_op
    window = effective_window(a, window_override)
    N, d = x.shape
    _, n_pages, page, Hkv, hd = pool["k"].shape
    S = block_tables.shape[1] * page
    valid = tok_pos >= 0
    pos = jnp.maximum(tok_pos, 0)
    q = jnp.einsum("bd,dhk->bhk", x, p["wq"])
    k = jnp.einsum("bd,dhk->bhk", x, p["wk"])
    v = jnp.einsum("bd,dhk->bhk", x, p["wv"])
    if a.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = apply_rope(q[:, None], pos[:, None], a.rope_theta)[:, 0]
    k = apply_rope(k[:, None], pos[:, None], a.rope_theta)[:, 0]

    bt_tok = block_tables[tok_seq]                       # (N, max_pages)
    pids = jnp.take_along_axis(bt_tok, (pos // page)[:, None], axis=1)[:, 0]
    offs = pos % page
    G = a.n_heads // Hkv
    quant = "k_scale" in pool            # quantized pool (DESIGN.md §17)
    use_pallas = _paged_use_pallas()
    _require_discard(discard_pid)
    if quant:
        from repro.kernels.ops import kv_append_quant_op
        k_pool, v_pool, k_scale, v_scale = kv_append_quant_op(
            pool["k"], pool["v"], pool["k_scale"], pool["v_scale"], layer,
            k, v,
            pids.astype(jnp.int32), offs.astype(jnp.int32),
            valid.astype(jnp.int32), discard_pid=discard_pid,
            use_pallas=use_pallas)
        new_pool = {"k": k_pool, "v": v_pool,
                    "k_scale": k_scale, "v_scale": v_scale}
    else:
        k_scale = v_scale = None
        if use_pallas:
            pids = jnp.where(valid, pids, discard_pid)
        k_pool, v_pool = kv_append_op(
            pool["k"], pool["v"], layer, k, v, pids.astype(jnp.int32),
            offs.astype(jnp.int32), valid.astype(jnp.int32),
            use_pallas=use_pallas)
        new_pool = {"k": k_pool, "v": v_pool}
    if _paged_use_pallas():
        out = ragged_paged_attention_op(
            q.reshape(N, Hkv, G, hd), k_pool, v_pool, layer, block_tables,
            tok_seq.astype(jnp.int32), tok_pos.astype(jnp.int32),
            k_scale=k_scale, v_scale=v_scale,
            softcap=a.logit_softcap, window=window, use_pallas=True)
    else:
        if quant:
            from repro.kernels.ref import dequant_gathered
            k_cache = dequant_gathered(
                k_pool[layer, bt_tok],
                k_scale[layer, bt_tok]).reshape(N, S, Hkv, hd)
            v_cache = dequant_gathered(
                v_pool[layer, bt_tok],
                v_scale[layer, bt_tok]).reshape(N, S, Hkv, hd)
        else:
            k_cache = k_pool[layer, bt_tok].reshape(N, S, Hkv, hd)
            v_cache = v_pool[layer, bt_tok].reshape(N, S, Hkv, hd)
        qh = q.reshape(N, Hkv, G, hd).astype(jnp.float32)
        s = jnp.einsum("bhgk,bshk->bhgs", qh,
                       k_cache.astype(jnp.float32)) / math.sqrt(hd)
        if a.logit_softcap is not None:
            s = softcap(s, a.logit_softcap)
        j = jnp.arange(S)[None, :]
        live = j <= tok_pos[:, None]
        if window is not None:
            live &= j > tok_pos[:, None] - window
        s = jnp.where(live[:, None, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhgs,bshk->bhgk", w, v_cache.astype(jnp.float32))
    out = out.reshape(N, a.n_heads, hd).astype(x.dtype)
    return jnp.einsum("bhk,hkd->bd", out, p["wo"]), new_pool


def mla_mixed_paged(p, a: AttentionCfg, x, pool, layer, block_tables,
                    tok_seq, tok_pos, *, window_override="cfg",
                    discard_pid=None):
    """Absorbed MLA mixed-batch pass over paged latent pools (drop-mode XLA
    scatter + O(context) latent gather per token on every backend, so
    ``discard_pid`` is unused). pool: {"c": (L, n_pages, page, kv_lora),
    "kr": (L, n_pages, page, rope)}, used at layer ``layer``."""
    window = effective_window(a, window_override)
    N, d = x.shape
    _, n_pages, page, _ = pool["c"].shape
    S = block_tables.shape[1] * page
    valid = tok_pos >= 0
    pos = jnp.maximum(tok_pos, 0)
    qn, qr = _mla_q(p, a, x[:, None], pos[:, None])
    qn, qr = qn[:, 0], qr[:, 0]
    c_new, kr_new = _mla_latent(p, a, x[:, None], pos[:, None])

    bt_tok = block_tables[tok_seq]                       # (N, max_pages)
    pids = jnp.take_along_axis(bt_tok, (pos // page)[:, None], axis=1)[:, 0]
    pids = jnp.where(valid, pids, n_pages)
    offs = pos % page
    c_pool = pool["c"].at[layer, pids, offs].set(
        c_new[:, 0].astype(pool["c"].dtype), mode="drop")
    kr_pool = pool["kr"].at[layer, pids, offs].set(
        kr_new[:, 0].astype(pool["kr"].dtype), mode="drop")

    c_cache = c_pool[layer, bt_tok].reshape(N, S, -1)
    kr_cache = kr_pool[layer, bt_tok].reshape(N, S, -1)
    q_lat = jnp.einsum("bhn,lhn->bhl", qn.astype(jnp.float32),
                       p["w_uk"].astype(jnp.float32))
    qk = a.qk_nope_head_dim + a.qk_rope_head_dim
    s = (jnp.einsum("bhl,bsl->bhs", q_lat, c_cache.astype(jnp.float32))
         + jnp.einsum("bhr,bsr->bhs", qr.astype(jnp.float32),
                      kr_cache.astype(jnp.float32))) / math.sqrt(qk)
    j = jnp.arange(S)[None, :]
    live = j <= tok_pos[:, None]
    if window is not None:
        live &= j > tok_pos[:, None] - window
    s = jnp.where(live[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    ctxv = jnp.einsum("bhs,bsl->bhl", w, c_cache.astype(jnp.float32))
    out = jnp.einsum("bhl,lhv->bhv", ctxv,
                     p["w_uv"].astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("bhv,hvd->bd", out, p["wo"]), {"c": c_pool,
                                                     "kr": kr_pool}


def attention_mixed_paged(p, a, x, pool, layer, block_tables, tok_seq,
                          tok_pos, *, window_override="cfg",
                          discard_pid=None):
    fn = mla_mixed_paged if a.kind == "mla" else gqa_mixed_paged
    return fn(p, a, x, pool, layer, block_tables, tok_seq, tok_pos,
              window_override=window_override, discard_pid=discard_pid)


# ==========================================================================
# Sequence-parallel decode attention (beyond-paper §Perf optimization)
# ==========================================================================

def gqa_decode_seqpar(p, a: AttentionCfg, x, cache, pos, *,
                      window_override="cfg", axis: str = "model",
                      batch_axis=None):
    """Flash-decoding-style decode: the KV cache stays sharded along its
    sequence axis on ``axis``; each shard computes a partial softmax
    (max / sum / weighted values) over its local slice and the partials are
    combined with O(B*H*hd) collectives — instead of XLA all-gathering the
    sharded cache (O(cache bytes)). Assumes no ring wrap (S >= pos+1),
    which holds for the slotted production cache.
    """
    window = effective_window(a, window_override)
    B, d = x.shape
    S = cache["k"].shape[1]
    q = jnp.einsum("bd,dhk->bhk", x, p["wq"])
    k = jnp.einsum("bd,dhk->bhk", x, p["wk"])
    v = jnp.einsum("bd,dhk->bhk", x, p["wv"])
    if a.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = apply_rope(q[:, None], pos[:, None], a.rope_theta)[:, 0]
    k = apply_rope(k[:, None], pos[:, None], a.rope_theta)[:, 0]

    Hkv, hd = a.n_kv_heads, a.head_dim
    G = a.n_heads // Hkv

    def shard_fn(k_cache, v_cache, q_, k_new, v_new, pos_):
        idx = jax.lax.axis_index(axis)
        B_loc, S_loc = k_cache.shape[:2]
        start = idx * S_loc
        bidx = jnp.arange(B_loc)
        slot = pos_ - start                     # local slot of the new token
        in_range = (slot >= 0) & (slot < S_loc)
        slot_c = jnp.clip(slot, 0, S_loc - 1)
        k_upd = k_cache.at[bidx, slot_c].set(
            jnp.where(in_range[:, None, None],
                      k_new.astype(k_cache.dtype), k_cache[bidx, slot_c]))
        v_upd = v_cache.at[bidx, slot_c].set(
            jnp.where(in_range[:, None, None],
                      v_new.astype(v_cache.dtype), v_cache[bidx, slot_c]))

        qh = q_.reshape(B_loc, Hkv, G, hd).astype(jnp.float32)
        s = jnp.einsum("bhgk,bshk->bhgs", qh,
                       k_upd.astype(jnp.float32)) / math.sqrt(hd)
        if a.logit_softcap is not None:
            s = softcap(s, a.logit_softcap)
        gpos = start + jnp.arange(S_loc)[None, :]
        valid = gpos <= pos_[:, None]
        if window is not None:
            valid &= gpos > (pos_[:, None] - window)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1)                         # (B,Hkv,G)
        p_loc = jnp.exp(s - m_loc[..., None])
        l_loc = jnp.sum(p_loc, axis=-1)
        acc = jnp.einsum("bhgs,bshk->bhgk", p_loc,
                         v_upd.astype(jnp.float32))
        # combine partial softmaxes across seq shards
        m_glob = jax.lax.pmax(m_loc, axis)
        corr = jnp.exp(m_loc - m_glob)
        l_glob = jax.lax.psum(l_loc * corr, axis)
        acc_glob = jax.lax.psum(acc * corr[..., None], axis)
        out = acc_glob / jnp.maximum(l_glob[..., None], 1e-37)
        return out.astype(x.dtype), k_upd, v_upd

    P = jax.sharding.PartitionSpec
    cache_spec = P(batch_axis, axis, None, None)
    vec_spec = P(batch_axis)
    out, k_cache, v_cache = jax.shard_map(
        shard_fn,
        in_specs=(cache_spec, cache_spec, vec_spec, vec_spec, vec_spec,
                  vec_spec),
        out_specs=(vec_spec, cache_spec, cache_spec),
    )(cache["k"], cache["v"], q, k, v, pos)
    out = out.reshape(B, a.n_heads, hd)
    return jnp.einsum("bhk,hkd->bd", out, p["wo"]), {"k": k_cache,
                                                     "v": v_cache}


# ==========================================================================
# Dispatch helpers
# ==========================================================================

def init_attention(key, d_model, a: AttentionCfg, dtype):
    return init_mla(key, d_model, a, dtype) if a.kind == "mla" else \
        init_gqa(key, d_model, a, dtype)


def attention_forward(p, a, x, positions, *, window_override="cfg"):
    fn = mla_forward if a.kind == "mla" else gqa_forward
    return fn(p, a, x, positions, window_override=window_override)


def attention_decode(p, a, x, cache, pos, *, window_override="cfg",
                     seq_parallel=None):
    if seq_parallel is not None and a.kind == "gqa":
        axis, batch_axis = seq_parallel
        return gqa_decode_seqpar(p, a, x, cache, pos,
                                 window_override=window_override,
                                 axis=axis, batch_axis=batch_axis)
    fn = mla_decode if a.kind == "mla" else gqa_decode
    return fn(p, a, x, cache, pos, window_override=window_override)


def init_cache_shapes(a: AttentionCfg, batch: int, max_len: int, dtype,
                      kv_dtype=None):
    """Zeroed decode cache for one attention block.

    ``kv_dtype`` (a name from repro.kernels.kv_quant.KV_QUANT_DTYPES)
    stores GQA K/V low-bit with one fp32 scale per (page, kv head) in the
    same dict — for paged pools, where ``batch`` is the page count and
    ``max_len`` the page size (DESIGN.md §17). MLA latent pools have no
    quantized kernel yet and stay in ``dtype`` (same standing gap as the
    MLA paged-decode kernel in ROADMAP.md)."""
    if a.kind == "mla":
        return {"c": jnp.zeros((batch, max_len, a.kv_lora_rank), dtype),
                "kr": jnp.zeros((batch, max_len, a.qk_rope_head_dim), dtype)}
    if kv_dtype is not None:
        from repro.kernels.kv_quant import kv_quant_jnp_dtype
        qd = kv_quant_jnp_dtype(kv_dtype)
        return {"k": jnp.zeros((batch, max_len, a.n_kv_heads, a.head_dim),
                               qd),
                "v": jnp.zeros((batch, max_len, a.n_kv_heads, a.head_dim),
                               qd),
                "k_scale": jnp.zeros((batch, a.n_kv_heads), jnp.float32),
                "v_scale": jnp.zeros((batch, a.n_kv_heads), jnp.float32)}
    return {"k": jnp.zeros((batch, max_len, a.n_kv_heads, a.head_dim), dtype),
            "v": jnp.zeros((batch, max_len, a.n_kv_heads, a.head_dim), dtype)}
