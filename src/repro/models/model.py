"""The language model: embeddings + grouped lax.scan over layer periods +
head(s). Supports text, audio (multi-codebook), and VLM (embedding-prefix)
inputs; full-sequence forward (train / prefill) and single-token decode.

Parameter tree:
  {"embed": ..., "groups": (g0, g1, ...), "shared": {...}|None,
   "final_norm": ..., "lm_head": ...}
Each group gi = {"scan": {"b<j>": params stacked over n_periods}}.
The zamba2 "shared_attn" block's params live once under "shared" and are
closed over by every invocation; its KV caches are still per-occurrence
(stacked within the group scan like everything else).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks as B
from repro.models.common import (chunked_cross_entropy, cross_entropy_logits,
                                 embed_init, rms_norm, softcap)


@dataclasses.dataclass
class ForwardOut:
    hidden: Any            # (B, T, d) final hidden states (pre-head)
    aux_loss: Any          # scalar (MoE load balance)
    cache: Any             # decode cache pytree or None


def sample_tokens(flat_logits, temps, top_ks, top_ps, seeds, positions):
    """Per-row token sampling, shared by the fused on-device path and the
    host-side per-call oracle paths (DESIGN.md §11).

    flat_logits: (B, V) float; temps (B,) float — <= 0 means greedy argmax
    (the differential oracle); top_ks (B,) int32 — <= 0 means the full
    vocabulary; top_ps (B,) float — nucleus mass threshold, values outside
    (0, 1) disable the filter; seeds (B,) int32 per-request sampling
    seeds; positions (B,) int32 — the absolute context index the sampled
    token will occupy.

    Stochastic rows apply top-k masking, then nucleus (top-p) masking —
    the smallest set of tokens whose temperature-scaled probability mass
    reaches ``top_p``, sorted-cumulative-mass style, ties at the threshold
    kept exactly as top-k keeps ties at the kth logit — then Gumbel-max
    categorical sampling at ``temperature``. The Gumbel noise is keyed
    ONLY by (seed, position), so a request's sampled stream is a pure
    function of its context, seed, and position — independent of batch
    composition, bucketing, and scheduling policy. The §6
    policy-equivalence property therefore survives stochastic sampling,
    and the fused/unfused/gather paths stay bit-identical (they feed this
    function the same logits).
    """
    flat = flat_logits.astype(jnp.float32)
    B, V = flat.shape
    greedy = jnp.argmax(flat, axis=-1).astype(jnp.int32)
    k = jnp.where(top_ks > 0, top_ks, V)
    srt = jnp.flip(jnp.sort(flat, axis=-1), axis=-1)        # descending
    kth = jnp.take_along_axis(
        srt, jnp.clip(k - 1, 0, V - 1)[:, None], axis=-1)   # (B, 1)
    masked = jnp.where(flat >= kth, flat, -jnp.inf)         # ties kept
    # nucleus: on the temperature-scaled distribution, keep tokens whose
    # PRECEDING cumulative mass (descending order) is < top_p — the
    # smallest prefix reaching the threshold, top-1 always survives; the
    # smallest kept sorted logit becomes a value threshold so threshold
    # ties are kept. Disabled rows get threshold 2.0 (> any reachable
    # cumulative mass, immune to cumsum rounding hitting 1.0 early), so
    # every token survives and ``masked`` is bit-identical to the
    # top-k-only graph
    t = jnp.maximum(temps, 1e-6).astype(jnp.float32)[:, None]
    p = jnp.where((top_ps > 0) & (top_ps < 1), top_ps, 2.0)[:, None]
    probs = jax.nn.softmax(srt / t, axis=-1)
    keep = (jnp.cumsum(probs, axis=-1) - probs) < p
    pth = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1, keepdims=True)
    masked = jnp.where(flat >= pth, masked, -jnp.inf)

    def gumbel_row(seed, pos):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
        return jax.random.gumbel(key, (V,), jnp.float32)

    noise = jax.vmap(gumbel_row)(seeds, positions)
    stoch = jnp.argmax(masked / t + noise, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, stoch, greedy)


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------
    # Init
    # ------------------------------------------------------------------
    def init(self, key, dtype=None):
        cfg = self.cfg
        dtype = jnp.dtype(dtype or cfg.dtype)
        keys = jax.random.split(key, len(cfg.groups) + 4)
        if cfg.n_codebooks:
            embed = embed_init(keys[0], (cfg.n_codebooks, cfg.vocab_size,
                                         cfg.d_model), dtype)
        else:
            embed = embed_init(keys[0], (cfg.vocab_size, cfg.d_model), dtype)
        params = {"embed": embed,
                  "final_norm": jnp.zeros((cfg.d_model,), dtype)}

        shared_blk = self._shared_block()
        if shared_blk is not None:
            params["shared"] = B.init_block(keys[1], cfg, shared_blk, dtype)

        groups = []
        for gi, g in enumerate(cfg.groups):
            gkey = keys[2 + gi]
            pkeys = jax.random.split(gkey, g.n_periods * len(g.period)
                                     ).reshape(g.n_periods, len(g.period), 2)

            def init_period(pk, g=g):
                out = {}
                for j, blk in enumerate(g.period):
                    if blk.kind == "shared_attn":
                        continue
                    out[f"b{j}"] = B.init_block(pk[j], cfg, blk, dtype)
                return out

            groups.append({"scan": jax.vmap(init_period)(pkeys)})
        params["groups"] = tuple(groups)

        if not cfg.tie_embeddings:
            if cfg.n_codebooks:
                params["lm_head"] = embed_init(
                    keys[-1], (cfg.n_codebooks, cfg.d_model, cfg.vocab_size),
                    dtype)
            else:
                params["lm_head"] = embed_init(
                    keys[-1], (cfg.d_model, cfg.vocab_size), dtype)
        return params

    def _shared_block(self):
        for blk in self.cfg.blocks:
            if blk.kind == "shared_attn":
                return blk
        return None

    # ------------------------------------------------------------------
    # Embedding / head
    # ------------------------------------------------------------------
    def embed(self, params, tokens, embeds=None):
        """tokens: (B, T) int32, or (B, T, K) for audio. embeds: optional
        (B, P, d) modality prefix prepended to the token embeddings."""
        cfg = self.cfg
        if cfg.n_codebooks:
            tok_k = tokens.transpose(2, 0, 1)              # (K, B, T)
            emb = jax.vmap(lambda e, t: jnp.take(e, t, axis=0))(
                params["embed"], tok_k)                    # (K, B, T, d)
            x = jnp.sum(emb, axis=0)
        else:
            x = jnp.take(params["embed"], tokens, axis=0)
        if embeds is not None:
            x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
        return x

    def head_matrix(self, params):
        cfg = self.cfg
        if cfg.tie_embeddings:
            return params["embed"].T                       # (d, V)
        return params["lm_head"]                           # (d, V) or (K, d, V)

    def logits(self, params, hidden):
        cfg = self.cfg
        w = self.head_matrix(params)
        if cfg.n_codebooks:
            out = jnp.einsum("...d,kdv->...kv", hidden, w)
        else:
            out = jnp.einsum("...d,dv->...v", hidden, w)
        return softcap(out, cfg.final_logit_softcap)

    # ------------------------------------------------------------------
    # Full-sequence forward
    # ------------------------------------------------------------------
    def forward(self, params, tokens=None, embeds=None, *, x=None,
                positions=None, remat=False, window_override="cfg",
                return_cache_len: Optional[int] = None) -> ForwardOut:
        cfg = self.cfg
        if x is None:
            x = self.embed(params, tokens, embeds)
        Bsz, T, _ = x.shape
        if positions is None:
            positions = jnp.arange(T, dtype=jnp.int32)
        ctx = {"positions": positions, "window_override": window_override}
        shared = params.get("shared")
        aux = jnp.zeros((), jnp.float32)
        caches = []

        for gi, g in enumerate(cfg.groups):
            period = g.period

            def body(carry, per_params, period=period):
                xx, aa = carry
                cache_out = {}
                for j, blk in enumerate(period):
                    pj = shared if blk.kind == "shared_attn" \
                        else per_params[f"b{j}"]
                    xx, cj, auxj = B.block_forward(pj, cfg, blk, xx, ctx)
                    cache_out[f"b{j}"] = cj
                    aa = aa + auxj
                return (xx, aa), cache_out

            if remat:
                body = jax.checkpoint(body)
            (x, aux), cache_g = jax.lax.scan(body, (x, aux),
                                             params["groups"][gi]["scan"])
            caches.append(cache_g)

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)

        cache = None
        if return_cache_len is not None:
            cache = self._materialize_cache(tuple(caches), Bsz, T,
                                            return_cache_len, window_override)
        return ForwardOut(hidden=x, aux_loss=aux, cache=cache)

    def _materialize_cache(self, raw_caches, batch, T, cache_len,
                           window_override):
        """Convert per-block forward outputs (full-seq KV / final SSM state)
        into decode-ready slotted caches of length cache_len."""
        cfg = self.cfg
        out = []
        for gi, g in enumerate(cfg.groups):
            entry = {}
            for j, blk in enumerate(g.period):
                cj = raw_caches[gi][f"b{j}"]
                if blk.kind in ("attn", "shared_attn"):
                    tmpl = B.init_block_cache(cfg, blk, batch, cache_len,
                                              _leaf_dtype(cj),
                                              window_override)

                    def fill(z, kv):
                        S = z.shape[2]           # (periods, B, S, ...)
                        n = min(T, S)
                        src = kv[:, :, T - n:]
                        slots = jnp.mod(jnp.arange(T - n, T), S)
                        return z.at[:, :, slots].set(src.astype(z.dtype))

                    stacked_tmpl = jax.tree.map(
                        lambda z: jnp.broadcast_to(
                            z, (g.n_periods,) + z.shape).copy(), tmpl)
                    entry[f"b{j}"] = jax.tree.map(fill, stacked_tmpl, cj)
                else:
                    entry[f"b{j}"] = cj          # SSM final state, ready
            out.append(entry)
        return tuple(out)

    # ------------------------------------------------------------------
    # Decode
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, cache_len: int, *, dtype=None,
                   window_override="cfg", kv_dtype=None):
        cfg = self.cfg
        dtype = jnp.dtype(dtype or cfg.dtype)
        out = []
        for g in cfg.groups:
            entry = {}
            for j, blk in enumerate(g.period):
                tmpl = B.init_block_cache(cfg, blk, batch, cache_len, dtype,
                                          window_override,
                                          kv_dtype=kv_dtype)
                entry[f"b{j}"] = jax.tree.map(
                    lambda z: jnp.broadcast_to(
                        z, (g.n_periods,) + z.shape).copy(), tmpl)
            out.append(entry)
        return tuple(out)

    def decode_step(self, params, tokens, pos, cache, *, embeds=None,
                    window_override="cfg", seq_parallel=None):
        """tokens: (B,) int32 (or (B, K) audio; or None with embeds (B, d)).
        pos: (B,) absolute position of the new token. Returns
        (logits (B, V) / (B, K, V), new_cache)."""
        cfg = self.cfg
        if tokens is not None:
            tok = tokens[:, None] if tokens.ndim == 1 else tokens[:, None, :]
            x = self.embed(params, tok)[:, 0]
        else:
            x = embeds
        ctx = {"pos": pos, "window_override": window_override,
               "seq_parallel": seq_parallel}
        shared = params.get("shared")
        new_caches = []

        for gi, g in enumerate(cfg.groups):
            period = g.period

            def body(xx, inp, period=period):
                per_params, cache_p = inp
                new_c = {}
                for j, blk in enumerate(period):
                    pj = shared if blk.kind == "shared_attn" \
                        else per_params[f"b{j}"]
                    xx, cj = B.block_decode(pj, cfg, blk, xx,
                                            cache_p[f"b{j}"], ctx)
                    new_c[f"b{j}"] = cj
                return xx, new_c

            x, cache_g = jax.lax.scan(
                body, x, (params["groups"][gi]["scan"], cache[gi]))
            new_caches.append(cache_g)

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return self.logits(params, x), tuple(new_caches)

    def decode_step_paged(self, params, tokens, ctx_lens, pools,
                          block_tables, *, embeds=None,
                          window_override="cfg", discard_pid=None):
        """In-place paged decode (DESIGN.md §9): one new token per sequence
        written directly into the shared page pools and attended through
        per-request block tables — no contiguous per-request cache exists.

        tokens: (B,) int32 (or (B, K) audio; or None with embeds (B, d));
        ctx_lens: (B,) int32 context length INCLUDING the new token (0
        marks a padding row — nothing is written, logits are garbage);
        pools: the pytree from init_cache(n_pages, page_size);
        block_tables: (B, max_pages) int32; discard_pid (required for GQA
        blocks) names the caller's write-discard page for masked appends
        on the Pallas path.
        Returns (logits, new_pools).
        """
        cfg = self.cfg
        if tokens is not None:
            tok = tokens[:, None] if tokens.ndim == 1 else tokens[:, None, :]
            x = self.embed(params, tok)[:, 0]
        else:
            x = embeds
        ctx = {"block_tables": block_tables, "ctx_lens": ctx_lens,
               "window_override": window_override,
               "discard_pid": discard_pid}
        x, new_pools = self._paged_layers(params, x, pools,
                                          B.block_decode_paged, ctx)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return self.logits(params, x), new_pools

    def forward_mixed_paged(self, params, tokens, tok_seq, tok_pos, q_last,
                            pools, block_tables, sampling=None, *,
                            embeds=None, window_override="cfg",
                            discard_pid=None):
        """Fused mixed-batch iteration (DESIGN.md §10): every prefill
        chunk's tokens and every decode's single token of one scheduler
        iteration, flattened into a single ragged batch and executed in ONE
        dispatch — one kv_append scatter per layer covering all new tokens,
        one ragged paged-attention pass, and sampling on device so only
        int32 token ids need to cross the host boundary.

        ``sampling`` is None for pure-greedy batches (argmax, the
        differential oracle) or a (temps (B,), top_ks (B,), top_ps (B,),
        seeds (B,)) tuple applied per sequence row by ``sample_tokens`` —
        the sampled token's position is derived on device as
        tok_pos[q_last] + 1 (DESIGN.md §11).

        tokens: (N,) int32 flat new-token ids (or (N, K) audio; or None
        with embeds (N, d)); tok_seq (N,) int32 names each token's
        sequence (block-table row); tok_pos (N,) int32 its absolute
        position (-1 marks a padded token row); q_last (B,) int32 is the
        flat index of each sequence's last real token (0 for padded
        sequence rows); pools / block_tables / discard_pid as in
        decode_step_paged. Causality inside a chunk comes from the
        per-token mask `kv pos <= tok_pos[i]` — all appends land before
        attention reads, and later chunk tokens sit at higher positions.

        Returns (sampled (B,) int32 greedy ids at each sequence's last
        token, logits (B, V) / (B, K, V) — retrievable but not fetched by
        the serving hot path — and the new pools).
        """
        cfg = self.cfg
        if tokens is not None:
            tok = tokens[:, None] if tokens.ndim == 1 else tokens[:, None, :]
            x = self.embed(params, tok)[:, 0]
        else:
            x = embeds
        ctx = {"block_tables": block_tables, "tok_seq": tok_seq,
               "tok_pos": tok_pos, "window_override": window_override,
               "discard_pid": discard_pid}
        x, new_pools = self._paged_layers(params, x, pools,
                                          B.block_mixed_paged, ctx)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        last = x[q_last]                                  # (B, d)
        logits = self.logits(params, last)
        # sampling on device over the last codebook's row — greedy is
        # exactly the engine's host-side np.argmax(...reshape(-1, V)[-1])
        flat = logits.reshape(logits.shape[0], -1, cfg.vocab_size)[:, -1]
        if sampling is None:
            sampled = jnp.argmax(flat, axis=-1).astype(jnp.int32)
        else:
            temps, top_ks, top_ps, seeds = sampling
            sampled = sample_tokens(flat, temps, top_ks, top_ps, seeds,
                                    tok_pos[q_last] + 1)
        return sampled, logits, new_pools

    def extend_step_paged(self, params, tokens, start, n_new, pools,
                          block_tables, *, embeds=None,
                          window_override="cfg", logits_index=None,
                          discard_pid=None):
        """In-place paged chunked prefill: the chunk's K/V pages are written
        as they are computed; tokens past n_new[b] are bucket padding whose
        writes are dropped. All written positions must fit the block table
        (start + T <= max_pages * page_size). Returns (logits at
        logits_index — default the last position — and the new pools)."""
        cfg = self.cfg
        x = self.embed(params, tokens) if tokens is not None else embeds
        ctx = {"block_tables": block_tables, "start": start, "n_new": n_new,
               "window_override": window_override,
               "discard_pid": discard_pid}
        x, new_pools = self._paged_layers(params, x, pools,
                                          B.block_extend_paged, ctx)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if logits_index is None:
            last = x[:, -1]
        else:
            last = x[jnp.arange(x.shape[0]), logits_index]
        return self.logits(params, last), new_pools

    def _paged_layers(self, params, x, pools, block_fn, ctx):
        """The layer groups over the shared paged pools (DESIGN.md §9).

        Each group's pools, stacked over its layers, ride its layer scan as
        part of the carry, and ``block_fn`` addresses them in place at the
        scan's layer index (``pool[layer]``), so no layer's pool is sliced
        out of the stack, copied, or written into a second stack; only the
        per-layer params are scanned over. Returns (x, new pools)."""
        cfg = self.cfg
        shared = params.get("shared")
        new_pools = []
        for gi, g in enumerate(cfg.groups):

            def body(carry, inp, period=g.period):
                xx, pools_g = carry
                per_params, layer = inp
                pools_g = dict(pools_g)
                for j, blk in enumerate(period):
                    pj = shared if blk.kind == "shared_attn" \
                        else per_params[f"b{j}"]
                    xx, pools_g[f"b{j}"] = block_fn(
                        pj, cfg, blk, xx, pools_g[f"b{j}"], layer, ctx)
                return (xx, pools_g), None

            (x, pools_g), _ = jax.lax.scan(
                body, (x, pools[gi]),
                (params["groups"][gi]["scan"],
                 jnp.arange(g.n_periods, dtype=jnp.int32)))
            new_pools.append(pools_g)
        return x, tuple(new_pools)

    def extend_step(self, params, tokens, start, cache, *, embeds=None,
                    window_override="cfg", logits_index=None):
        """Chunked prefill / recomputation: append T tokens per sequence at
        absolute positions start[b]..start[b]+T-1, attending to the cached
        prefix. tokens: (B, T) (or (B, T, K) audio; or embeds (B, T, d)).
        Requires cache length >= start + T (no ring wrap). Returns
        (logits at position logits_index (B,), default the last new
        position, and the new cache)."""
        cfg = self.cfg
        x = self.embed(params, tokens) if tokens is not None else embeds
        ctx = {"start": start, "window_override": window_override}
        shared = params.get("shared")
        new_caches = []

        for gi, g in enumerate(cfg.groups):
            period = g.period

            def body(carry, inp, period=period):
                xx, aa = carry
                per_params, cache_p = inp
                new_c = {}
                for j, blk in enumerate(period):
                    pj = shared if blk.kind == "shared_attn" \
                        else per_params[f"b{j}"]
                    xx, cj, auxj = B.block_extend(pj, cfg, blk, xx,
                                                  cache_p[f"b{j}"], ctx)
                    new_c[f"b{j}"] = cj
                    aa = aa + auxj
                return (xx, aa), new_c

            (x, _), cache_g = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)),
                (params["groups"][gi]["scan"], cache[gi]))
            new_caches.append(cache_g)

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if logits_index is None:
            last = x[:, -1]
        else:
            last = x[jnp.arange(x.shape[0]), logits_index]
        return self.logits(params, last), tuple(new_caches)

    # ------------------------------------------------------------------
    # Loss
    # ------------------------------------------------------------------
    def loss(self, params, tokens=None, labels=None, embeds=None,
             label_mask=None, *, remat=True, window_override="cfg"):
        """Next-token CE (labels already shifted by the data pipeline).

        Uses the streaming vocab-chunked CE for large vocabularies so the
        (B, T, V) logits are never materialized.
        """
        cfg = self.cfg
        out = self.forward(params, tokens, embeds, remat=remat,
                           window_override=window_override)
        h = out.hidden
        if embeds is not None:
            P = embeds.shape[1]
            h = h[:, P:]
        w = self.head_matrix(params)
        if cfg.n_codebooks:
            lg = jnp.einsum("btd,kdv->btkv", h, w)
            lg = softcap(lg, cfg.final_logit_softcap)
            ce = cross_entropy_logits(
                lg.reshape(lg.shape[0], -1, cfg.vocab_size),
                labels.reshape(labels.shape[0], -1),
                None if label_mask is None else
                jnp.repeat(label_mask, cfg.n_codebooks, axis=-1))
        elif cfg.vocab_size >= 65536 and cfg.final_logit_softcap is None:
            ce = chunked_cross_entropy(h, w, labels, label_mask=label_mask)
        else:
            lg = softcap(jnp.einsum("btd,dv->btv", h, w),
                         cfg.final_logit_softcap)
            ce = cross_entropy_logits(lg, labels, label_mask)
        return ce + out.aux_loss, {"ce": ce, "aux": out.aux_loss}


def _leaf_dtype(tree):
    return jax.tree.leaves(tree)[0].dtype
