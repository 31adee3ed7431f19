"""Model operations of the served tokens of one fused step, for ``mfu``.

A fresh token (a prompt token computed for the first time, or a decode)
costs the matrix products of every layer, 2 * (d*H*hd + 2*d*Hkv*hd +
H*hd*d + 3*d*F) per layer, plus attention over its real context, 4*H*hd
per visible position per layer. A delivered token also costs the output
head, 2*d*V. Recomputed tokens (KV rebuilt after a discard) count nothing.

``seqs``: one (fresh tokens f, context after ctx, delivered tokens) triple
per sequence; the fresh tokens are the last f positions before ctx.
"""


def flops(seqs, dims: dict) -> float:
    L, d, F, V = dims["n_layers"], dims["d_model"], dims["d_ff"], dims["vocab"]
    H, Hkv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    per_token = 2.0 * L * (2 * d * H * hd + 2 * d * Hkv * hd + 3 * d * F)
    total = 0.0
    for f, ctx, delivered in seqs:
        visible = f * ctx - f * (f - 1) // 2
        total += f * per_token + 4.0 * L * H * hd * visible
        total += delivered * 2.0 * d * V
    return total
