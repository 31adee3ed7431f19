"""Operations and bytes one ``ragged_paged_attention`` call (one layer of
a fused step) needs, from the work it was given.

``seqs``: one (new tokens n, context after ctx) pair per sequence; its
tokens sit at positions ctx-n .. ctx-1 and each attends to every position
up to its own. Operations: QK^T and PV, 2 * 2 * hd multiply-adds per
(query head, visible key). Bytes: what the algorithm needs, not what the
kernel streams: each sequence's live K and V once, the queries read and
the outputs written.
"""


def cost(seqs, dims: dict) -> tuple:
    H, Hkv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    visible = sum(n * ctx - n * (n - 1) // 2 for n, ctx in seqs)
    tokens = sum(n for n, _ in seqs)
    kv = sum(ctx for _, ctx in seqs) * 2 * Hkv * hd * dims["kv_bytes"]
    qo = tokens * 2 * H * hd * dims["act_bytes"]
    return 4.0 * H * hd * visible, float(kv + qo)
