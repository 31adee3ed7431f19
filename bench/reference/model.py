"""Plain float32 reference of a dense decoder of the Llama family, as
DeepSeek-LLM (arXiv:2401.02954) and Qwen2 (arXiv:2407.10671) publish it.

    x = embed[tokens]
    per layer:  h = x + Wo . attn(rope(RMSNorm(x) Wq + bq), rope(... Wk + bk),
                                   RMSNorm(x) Wv + bv)      causal, GQA
                x = h + Wdown (silu(RMSNorm(h) Wgate) * (RMSNorm(h) Wup))
    logits = RMSNorm(x) Whead

RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g. RoPE rotates the two halves of
each head (the rotate-half form of the Hugging Face implementations) with
frequencies theta^(-2j/hd). Attention scales by 1/sqrt(hd); query head h
reads key/value head h // (H / Hkv). The QKV biases are present where the
configuration says ``attention_bias`` (Qwen2).

``fp8=True`` is the control of the correctness check: the same
computation with every operand of every projection (weights per output
column, activations per row) rounded to float8_e4m3 under an absmax
scale, the precision next below the configuration's bfloat16.

Nothing of the program is imported. The weights are the benchmark's own
arrays (``bench/harness/setup.py``), stored in bfloat16 with the RMSNorm
gain as g - 1; they are upcast to float32 here, one layer at a time, and
every matrix product runs at ``highest`` precision. Sequences are padded
to a multiple of ``PAD`` tokens at the end, which causal attention never
lets an earlier position see; queries and feed-forward rows go in blocks
of ``ROWS``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PAD = 512
ROWS = 256


FP8_MAX = 448.0


def _q8(a, axes):
    """``a`` rounded to float8_e4m3 under one absmax scale per slice over
    ``axes`` (the contraction axes of the product it feeds)."""
    s = jnp.max(jnp.abs(a), axis=axes, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, pos, theta):
    """x: (T, heads, hd) float32; pos: (T,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "bias", "fp8"))
def _layer(x, w, *, eps, theta, bias, fp8=False):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    q = (lambda a, axes: _q8(a, axes)) if fp8 else (lambda a, axes: a)
    w = {k: f32(v) for k, v in w.items()}
    for k in ("wq", "wk", "wv", "w_gate", "w_up", "w_down"):
        w[k] = q(w[k], (0,))
    w["wo"] = q(w["wo"], (0, 1))
    T, d = x.shape
    H, hd = w["wq"].shape[1], w["wq"].shape[2]
    Hkv = w["wk"].shape[1]
    G = H // Hkv
    pos = jnp.arange(T)
    h = q(_rms(x, 1.0 + w["norm1"], eps), (1,))
    qh = jnp.einsum("td,dhk->thk", h, w["wq"])
    k = jnp.einsum("td,dhk->thk", h, w["wk"])
    v = jnp.einsum("td,dhk->thk", h, w["wv"])
    if bias:
        qh, k, v = qh + w["bq"], k + w["bk"], v + w["bv"]
    qh, k = _rope(qh, pos, theta), _rope(k, pos, theta)
    k = jnp.repeat(k, G, axis=1)                       # (T, H, hd)
    v = jnp.repeat(v, G, axis=1)
    nb = T // ROWS

    def attend(blk):
        qb, qpos = blk                                 # (ROWS, H, hd)
        s = jnp.einsum("qhk,thk->hqt", qb, k) / math.sqrt(hd)
        s = jnp.where((pos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v)
    att = jax.lax.map(attend, (qh.reshape(nb, ROWS, H, hd),
                               pos.reshape(nb, ROWS))).reshape(T, H, hd)
    x = x + jnp.einsum("thk,hkd->td", q(att, (1, 2)), w["wo"])
    g2 = 1.0 + w["norm2"]

    def ffn(xb):
        h2 = q(_rms(xb, g2, eps), (1,))
        a = jax.nn.silu(h2 @ w["w_gate"]) * (h2 @ w["w_up"])
        return xb + q(a, (1,)) @ w["w_down"]
    return jax.lax.map(ffn, x.reshape(nb, ROWS, d)).reshape(T, d)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, g, head, *, eps, fp8=False):
    x = _rms(x, 1.0 + g.astype(jnp.float32), eps)
    head = head.astype(jnp.float32)
    if fp8:
        x, head = _q8(x, (1,)), _q8(head, (0,))
    return x @ head


def _layer_weights(params, i: int) -> dict:
    blk = params["groups"][0]["scan"]["b0"]
    w = {"norm1": blk["norm1"][i], "norm2": blk["norm2"][i]}
    w.update({k: v[i] for k, v in blk["attn"].items()})
    w.update({k: v[i] for k, v in blk["mlp"].items()})
    return w


def logits_at(params, conf: dict, tokens, positions,
              fp8: bool = False) -> np.ndarray:
    """float32 logits (len(positions), V) of the next token after each
    position of ``tokens``."""
    eps = float(conf["rms_norm_eps"])
    theta = float(conf["rope_theta"])
    bias = bool(conf["attention_bias"])
    tokens = np.asarray(tokens, np.int32)
    T = len(tokens)
    T_pad = -(-T // PAD) * PAD
    ids = np.zeros(T_pad, np.int32)
    ids[:T] = tokens
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(ids), axis=0) \
            .astype(jnp.float32)
        for i in range(int(conf["num_hidden_layers"])):
            x = _layer(x, _layer_weights(params, i), eps=eps, theta=theta,
                       bias=bias, fp8=fp8)
        positions = np.asarray(positions, np.int32)
        n = len(positions)
        at = np.zeros(-(-n // ROWS) * ROWS, np.int32)
        at[:n] = positions
        out = _head(x[jnp.asarray(at)], params["final_norm"],
                    params["lm_head"], eps=eps, fp8=fp8)
    return np.asarray(out)[:n]
