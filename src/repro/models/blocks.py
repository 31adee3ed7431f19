"""Residual blocks: init/apply dispatch over BlockCfg kinds, in
full-sequence (train/prefill), single-token (decode) and chunked-extend
modes over slotted caches, and the fused step's ragged pass over the
paged pools."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import BlockCfg, ModelConfig
from repro.models import attention, mlp, moe, ssm
from repro.models.common import rms_norm


def init_block(key, cfg: ModelConfig, blk: BlockCfg, dtype):
    d = cfg.d_model
    if blk.kind in ("attn", "shared_attn"):
        k1, k2 = jax.random.split(key)
        p = {"norm1": jnp.zeros((d,), dtype),
             "attn": attention.init_attention(k1, d, blk.attn, dtype),
             "norm2": jnp.zeros((d,), dtype)}
        if blk.ffn.kind == "moe":
            p["moe"] = moe.init_moe(k2, d, blk.ffn, dtype)
        elif blk.ffn.kind == "dense":
            p["mlp"] = mlp.init_mlp(k2, d, blk.ffn, dtype)
        if blk.post_norms:
            p["post_norm1"] = jnp.zeros((d,), dtype)
            p["post_norm2"] = jnp.zeros((d,), dtype)
        return p
    if blk.kind == "mamba2":
        return {"norm": jnp.zeros((d,), dtype),
                "cell": ssm.init_mamba2(key, d, blk.ssm, dtype)}
    if blk.kind == "mlstm":
        return {"norm": jnp.zeros((d,), dtype),
                "cell": ssm.init_mlstm(key, d, blk.ssm, dtype)}
    if blk.kind == "slstm":
        return {"norm": jnp.zeros((d,), dtype),
                "cell": ssm.init_slstm(key, d, blk.ssm, dtype)}
    raise ValueError(f"unknown block kind {blk.kind}")


def block_forward(p, cfg: ModelConfig, blk: BlockCfg, x, ctx):
    """Full-sequence pass. Returns (x, cache_entry, aux_loss)."""
    eps = cfg.norm_eps
    aux = jnp.zeros((), jnp.float32)
    if blk.kind in ("attn", "shared_attn"):
        h, kv = attention.attention_forward(
            p["attn"], blk.attn, rms_norm(x, p["norm1"], eps),
            ctx["positions"], window_override=ctx.get("window_override", "cfg"))
        if blk.post_norms:
            h = rms_norm(h, p["post_norm1"], eps)
        x = x + h.astype(x.dtype)
        if blk.ffn.kind == "moe":
            h, aux = moe.moe_forward(p["moe"], blk.ffn,
                                     rms_norm(x, p["norm2"], eps))
        else:
            h = mlp.mlp_forward(p["mlp"], blk.ffn, rms_norm(x, p["norm2"], eps))
        if blk.post_norms:
            h = rms_norm(h, p["post_norm2"], eps)
        return x + h.astype(x.dtype), kv, aux
    fwd = {"mamba2": ssm.mamba2_forward, "mlstm": ssm.mlstm_forward,
           "slstm": ssm.slstm_forward}[blk.kind]
    h, state = fwd(p["cell"], blk.ssm, cfg.d_model, rms_norm(x, p["norm"], eps))
    return x + h.astype(x.dtype), state, aux


def block_decode(p, cfg: ModelConfig, blk: BlockCfg, x, cache, ctx):
    """Single-token pass. x: (B, d). Returns (x, new_cache_entry)."""
    eps = cfg.norm_eps
    if blk.kind in ("attn", "shared_attn"):
        h, kv = attention.attention_decode(
            p["attn"], blk.attn, rms_norm(x, p["norm1"], eps), cache,
            ctx["pos"], window_override=ctx.get("window_override", "cfg"),
            seq_parallel=ctx.get("seq_parallel"))
        if blk.post_norms:
            h = rms_norm(h, p["post_norm1"], eps)
        x = x + h.astype(x.dtype)
        xin = rms_norm(x, p["norm2"], eps)
        if blk.ffn.kind == "moe":
            h, _ = moe.moe_forward(p["moe"], blk.ffn, xin[:, None])
            h = h[:, 0]
        else:
            h = mlp.mlp_forward(p["mlp"], blk.ffn, xin)
        if blk.post_norms:
            h = rms_norm(h, p["post_norm2"], eps)
        return x + h.astype(x.dtype), kv
    dec = {"mamba2": ssm.mamba2_decode, "mlstm": ssm.mlstm_decode,
           "slstm": ssm.slstm_decode}[blk.kind]
    h, state = dec(p["cell"], blk.ssm, cfg.d_model,
                   rms_norm(x, p["norm"], eps), cache)
    return x + h.astype(x.dtype), state


def block_mixed_paged(p, cfg: ModelConfig, blk: BlockCfg, x, pool, layer,
                      ctx):
    """Fused mixed-batch pass over the shared paged pool (DESIGN.md §10).

    x: (N, d) — one row per flat token of the iteration (chunk tokens and
    decode tokens alike), routed through ctx["block_tables"] by
    ctx["tok_seq"] / ctx["tok_pos"]; pool: this block's page pools
    stacked over the group's layers, addressed at ``layer``. Returns (x,
    updated pool). Attention-cache blocks only.
    """
    if blk.kind not in ("attn", "shared_attn"):
        raise ValueError(f"paged execution serves attention blocks, "
                         f"got {blk.kind}")
    eps = cfg.norm_eps
    h, pool = attention.attention_mixed_paged(
        p["attn"], blk.attn, rms_norm(x, p["norm1"], eps), pool, layer,
        ctx["block_tables"], ctx["tok_seq"], ctx["tok_pos"],
        window_override=ctx.get("window_override", "cfg"),
        discard_pid=ctx.get("discard_pid"))
    if blk.post_norms:
        h = rms_norm(h, p["post_norm1"], eps)
    x = x + h.astype(x.dtype)
    xin = rms_norm(x, p["norm2"], eps)
    if blk.ffn.kind == "moe":
        h, _ = moe.moe_forward(p["moe"], blk.ffn, xin[:, None])
        h = h[:, 0]
    else:
        h = mlp.mlp_forward(p["mlp"], blk.ffn, xin)
    if blk.post_norms:
        h = rms_norm(h, p["post_norm2"], eps)
    return x + h.astype(x.dtype), pool


def block_extend(p, cfg: ModelConfig, blk: BlockCfg, x, cache, ctx):
    """Chunked-prefill pass: x (B, T, d) appended at positions
    ctx["start"][b] + t, attending to the cached prefix. Returns
    (x, new_cache_entry, aux)."""
    eps = cfg.norm_eps
    aux = jnp.zeros((), jnp.float32)
    if blk.kind in ("attn", "shared_attn"):
        h, kv = attention.attention_extend(
            p["attn"], blk.attn, rms_norm(x, p["norm1"], eps), cache,
            ctx["start"], window_override=ctx.get("window_override", "cfg"))
        if blk.post_norms:
            h = rms_norm(h, p["post_norm1"], eps)
        x = x + h.astype(x.dtype)
        if blk.ffn.kind == "moe":
            h, aux = moe.moe_forward(p["moe"], blk.ffn,
                                     rms_norm(x, p["norm2"], eps))
        else:
            h = mlp.mlp_forward(p["mlp"], blk.ffn, rms_norm(x, p["norm2"], eps))
        if blk.post_norms:
            h = rms_norm(h, p["post_norm2"], eps)
        return x + h.astype(x.dtype), kv, aux
    fwd = {"mamba2": ssm.mamba2_forward, "mlstm": ssm.mlstm_forward,
           "slstm": ssm.slstm_forward}[blk.kind]
    h, state = fwd(p["cell"], blk.ssm, cfg.d_model,
                   rms_norm(x, p["norm"], eps), initial_state=cache)
    return x + h.astype(x.dtype), state, aux


def init_block_cache(cfg: ModelConfig, blk: BlockCfg, batch: int,
                     cache_len: int, dtype, window_override="cfg",
                     kv_dtype=None):
    """Zeroed decode cache/state for one block. ``kv_dtype`` stores GQA
    K/V low-bit with per-page scales (paged pools only; DESIGN.md §17)."""
    if blk.kind in ("attn", "shared_attn"):
        a = blk.attn
        window = attention.effective_window(a, window_override)
        n = cache_len if window is None else min(cache_len, window)
        return attention.init_cache_shapes(a, batch, n, dtype,
                                           kv_dtype=kv_dtype)
    shapes = {"mamba2": ssm.mamba2_state_shapes, "mlstm": ssm.mlstm_state_shapes,
              "slstm": ssm.slstm_state_shapes}[blk.kind]
    return shapes(blk.ssm, cfg.d_model, batch, dtype)
