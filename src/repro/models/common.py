"""Shared low-level model components (no flax — plain functional JAX)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def dense_init(key, shape, in_axis=-2, dtype=jnp.float32):
    """Truncated-normal fan-in init (lecun-style). ``in_axis`` names the
    contracted input axis, or a tuple of them (e.g. the (heads, head_dim)
    axes of an attention output projection)."""
    axes = in_axis if isinstance(in_axis, tuple) else (in_axis,)
    fan_in = math.prod(shape[a] for a in axes) if len(shape) > 1 \
        else shape[0]
    std = 1.0 / math.sqrt(max(1, fan_in))
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    """Embeddings at 1/sqrt(d) so the residual stream enters the first
    rms_norm at unit RMS — otherwise the norm's 1/rms Jacobian amplifies
    embedding gradients ~50x and global-norm clipping stalls training."""
    d = shape[-1]
    return (jax.random.normal(key, shape) / math.sqrt(d)).astype(dtype)


# --------------------------------------------------------------------------
# Norms / activations
# --------------------------------------------------------------------------

def rms_norm(x, scale, eps=1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dtype)


def softcap(x, cap):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def activation_fn(name):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# --------------------------------------------------------------------------
# RoPE (gpt-neox rotate-half convention)
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta):
    """x: (..., T, n_heads, head_dim); positions: broadcastable to (..., T)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    cos = jnp.cos(angles)[..., None, :]                        # (..., T, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Memory-lean cross entropy: never materializes (B, T, V) logits.
# --------------------------------------------------------------------------

def chunked_cross_entropy(x, w_out, labels, *, vocab_chunk=16384,
                          label_mask=None):
    """Mean next-token CE of ``x @ w_out`` against ``labels``.

    x: (B, T, d) hidden states, w_out: (d, V), labels: (B, T) int32.
    Scans over vocab chunks accumulating a streaming logsumexp plus the
    target-class logit, so peak memory is (B, T, vocab_chunk) instead of
    (B, T, V). With V up to 256k this is the difference between fitting in
    HBM and not (recorded as a beyond-paper memory optimization).
    """
    B, T, d = x.shape
    V = w_out.shape[-1]
    n_chunks = max(1, -(-V // vocab_chunk))
    pad_v = n_chunks * vocab_chunk - V
    w = jnp.pad(w_out, ((0, 0), (0, pad_v))) if pad_v else w_out
    w = w.reshape(d, n_chunks, vocab_chunk).transpose(1, 0, 2)  # (n, d, c)

    xf = x.astype(jnp.float32)

    def body(carry, wc_i):
        m, s, tgt = carry
        wc, i = wc_i
        logits = jnp.einsum("btd,dc->btc", xf, wc.astype(jnp.float32))
        if pad_v:
            col = i * vocab_chunk + jnp.arange(vocab_chunk)
            logits = jnp.where(col[None, None, :] < V, logits, -jnp.inf)
        cmax = jnp.max(logits, axis=-1)
        new_m = jnp.maximum(m, cmax)
        s = s * jnp.exp(m - new_m) + jnp.sum(
            jnp.exp(logits - new_m[..., None]), axis=-1)
        local = labels - i * vocab_chunk
        in_chunk = (local >= 0) & (local < vocab_chunk)
        picked = jnp.take_along_axis(
            logits, jnp.clip(local, 0, vocab_chunk - 1)[..., None], axis=-1
        )[..., 0]
        tgt = jnp.where(in_chunk, picked, tgt)
        return (new_m, s, tgt), None

    init = (jnp.full((B, T), -jnp.inf, jnp.float32),
            jnp.zeros((B, T), jnp.float32),
            jnp.zeros((B, T), jnp.float32))
    (m, s, tgt), _ = jax.lax.scan(body, init, (w, jnp.arange(n_chunks)))
    nll = (m + jnp.log(s)) - tgt                    # logsumexp - target logit
    if label_mask is None:
        return jnp.mean(nll)
    label_mask = label_mask.astype(jnp.float32)
    return jnp.sum(nll * label_mask) / jnp.maximum(jnp.sum(label_mask), 1.0)


def cross_entropy_logits(logits, labels, label_mask=None):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - tgt
    if label_mask is None:
        return jnp.mean(nll)
    label_mask = label_mask.astype(jnp.float32)
    return jnp.sum(nll * label_mask) / jnp.maximum(jnp.sum(label_mask), 1.0)
