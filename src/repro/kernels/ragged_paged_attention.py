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

A decode-only launch (one token per sequence with per-sequence context
lengths ctx_lens) is the case tok_seq = arange(B), tok_pos = ctx_lens - 1.
Padded token rows carry ``tok_pos[i] == -1``: their output is zeros and the
caller ignores it.

Layout: q (N, Hkv, G, hd); pools (L, n_pages, page, Hkv, hd), the model's
stack over one layer group's layers, of which the call reads layer
``layer`` (an int32 scalar-prefetch operand that every page's index map
leads with, so the stack is addressed in place and no layer's pool is
sliced out); block_tables (B, max_pages) int32; tok_seq/tok_pos (N,) int32.

Iteration space: query tiles × KV blocks.

- Rows are ordered by sequence (a stable sort; padded rows last) and cut
  into q blocks of T rows. A *tile* is one sequence's rows inside one q
  block, so a tile reads one block-table row; there are at most
  ``ceil(N / T) + B - 1`` tiles, the grid's first axis.
- A KV block is P consecutive block-table columns. One grid step moves the
  block's K and V pages (each page one DMA of all Hkv heads, the pool's own
  layout) and serves all T·G query rows of every head with
  ``(T·G, hd) x (hd, P·page)`` matmuls on the pool's operands with float32
  accumulation; the online-softmax state stays float32 in VMEM.
- Each tile's page loop is bounded: the tile's metadata (sequence, q block,
  row range, first and last live block) is computed on the device from
  tok_seq/tok_pos and prefetched as scalars. The KV index map is clamped to
  the tile's last live block, so the steps past it repeat the previous
  block index and issue no DMA, and ``pl.when`` skips their compute; a
  window starts the loop at the first block inside it.
- Every row keeps its own causal (and window) mask from its own position,
  so a tile's positions need not be consecutive.

T and P come from the shapes (``tile_sizes``): P·page = 128 KV rows per
step, T·G up to 128 query rows, halved until the blocks and scratch fit a
VMEM budget. ``grid_steps`` counts one launch's grid steps and live steps
with the same arithmetic, on the host.

A head's (P·page, hd) K or V rows sit Hkv rows apart in the page; a loop
over the heads takes them out, one of two ways chosen from the pool's
dtype and shape. A bfloat16 pool with hd one lane width (128) and Hkv even
(every benchmark cell) takes two heads a step by strided 32-bit loads
straight from the pages, each word holding the same position's rows of
heads 2i and 2i+1. Other pools (quantized, float32, other head sizes)
convert the block to float32 once into VMEM scratch (a quantized pool's
per-page per-head scales applied there) and take one head a step by a
strided load of it; the copy costs 16–87% more kernel time at the
benchmark stages' widths, which is why bf16 pools skip it. The matmuls run on
the pool's own dtype where it is bf16 or f32 (the probabilities are cast
to it for P·V) and in float32 for a quantized pool. The loop is not
unrolled: Hkv copies of its body cost seconds of tracing and lowering per
compiled fused step.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
KV_ROWS = 128                 # KV positions a grid step serves: MXU width
Q_ROWS = 128                  # query rows (tokens x group) a tile holds
VMEM_BUDGET = 12 * 2 ** 20    # blocks + scratch; VMEM_LIMIT leaves Mosaic room
VMEM_LIMIT = 32 * 2 ** 20     # the kernel's scoped VMEM (v5e holds 128 MiB)
# tile metadata rows, each n_tiles long in the flat scalar-prefetch array
_SEQ, _QB, _LO, _HI, _FIRST, _LAST = range(6)


def _pairs(Hkv, hd, kv_bytes):
    """Heads come out of the pages two at a time by strided 32-bit loads:
    a 2-byte (bfloat16) pool whose rows are one lane width, Hkv even."""
    return kv_bytes == 2 and hd == 128 and Hkv % 2 == 0


def _vmem_bytes(T, P, *, Hkv, G, hd, page, q_bytes, kv_bytes):
    rows = T * G
    q_blk = Hkv * rows * hd * q_bytes
    kv_blk = P * page * Hkv * hd * kv_bytes
    lanes = rows * 128 * 4            # a (rows, 1) f32 column pads to 128
    staged = (0 if _pairs(Hkv, hd, kv_bytes)
              else P * page * Hkv * -(-hd // 128) * 128 * 4)
    return (4 * q_blk                 # q and out, double-buffered
            + 4 * kv_blk              # K and V, double-buffered
            + 2 * staged              # K and V in float32
            + Hkv * rows * hd * 4     # acc
            + 2 * Hkv * lanes         # m, l
            + 2 * lanes)              # row positions, double-buffered


def tile_sizes(N, Hkv, G, hd, page, max_pages, q_bytes, kv_bytes):
    """(T, P): query rows per tile and pages per KV block, from shapes."""
    P = max(1, min(max_pages, KV_ROWS // page))
    T = 1
    while T < N and T * G < Q_ROWS:
        T *= 2
    # a q block short of the whole batch needs T·G on the sublane tiling
    floor = 16 // math.gcd(16, G)
    while T > floor and _vmem_bytes(T, P, Hkv=Hkv, G=G, hd=hd, page=page,
                                    q_bytes=q_bytes,
                                    kv_bytes=kv_bytes) > VMEM_BUDGET:
        T //= 2
    return T, P


def grid_shape(N, B, T, P, max_pages):
    """(tiles, KV blocks): the grid of one launch."""
    return max(1, min(N, -(-N // T) + B - 1)), -(-max_pages // P)


def grid_steps(spans, N, B, *, Hkv, G, hd, page, max_pages, q_bytes,
               kv_bytes, window=None):
    """Grid steps of one launch and those that carry a live KV block, for
    rows laid out as the fused step lays them: ``spans`` lists each
    sequence's (first position, rows) in row order, padded rows after."""
    T, P = tile_sizes(N, Hkv, G, hd, page, max_pages, q_bytes, kv_bytes)
    nt, nb = grid_shape(N, B, T, P, max_pages)
    live = row = 0
    for start, n in spans:
        done = 0
        while done < n:
            take = min(n - done, T - row % T)
            lo = start + done
            first = 0 if window is None else max(lo - window + 1, 0)
            live += ((lo + take - 1) // page // P) - (first // page // P) + 1
            row += take
            done += take
    return nt * nb, live


def _tile_plan(seq, pos, *, B, T, P, page, window, n_tiles):
    """Per-tile metadata, flat (6 * n_tiles,) int32, from rows sorted by
    sequence (``seq == B`` marks the padded rows, which come last)."""
    N = seq.shape[0]
    i32 = jnp.int32
    r = jnp.arange(N, dtype=i32)
    real = seq < B
    prev = jnp.concatenate([jnp.full((1,), -1, i32), seq[:-1]])
    starts = real & ((r % T == 0) | (seq != prev))
    tid = jnp.where(real, jnp.cumsum(starts.astype(i32)) - 1, n_tiles)
    n_real = jnp.sum(starts.astype(i32))
    big = jnp.iinfo(i32).max
    row0 = jnp.full((n_tiles,), big, i32).at[tid].min(r, mode="drop")
    rows = jnp.zeros((n_tiles,), i32).at[tid].add(1, mode="drop")
    pmax = jnp.zeros((n_tiles,), i32).at[tid].max(pos, mode="drop")
    pmin = jnp.full((n_tiles,), big, i32).at[tid].min(pos, mode="drop")
    t = jnp.arange(n_tiles, dtype=i32)
    # tiles past the real ones copy the last real tile with no rows: their
    # steps repeat its last block, so they move no data
    src = jnp.where(t < n_real, t, jnp.maximum(n_real - 1, 0))
    row0 = jnp.minimum(row0[src], N - 1)
    qb = row0 // T
    lo = row0 - qb * T
    hi = jnp.where(t < n_real, lo + rows[src], lo)
    last = jnp.maximum(pmax[src], 0) // page // P
    if window is None:
        first = jnp.zeros_like(last)
    else:
        first = jnp.maximum(pmin[src] - window + 1, 0) // page // P
    first = jnp.where(t < n_real, jnp.minimum(first, last), last)
    tseq = jnp.minimum(seq[row0], B - 1)
    return jnp.stack([tseq, qb, lo, hi, first, last]).reshape(-1)


def _head_pair_rows(refs, hp, Hkv, page):
    """Heads 2hp and 2hp+1 of a bfloat16 KV block given as one ref per page,
    each (1, page * Hkv, hd): one strided 32-bit load per page, each word
    holding one position's two bfloat16 rows. Returns two (P * page, hd)."""
    w = jnp.concatenate(
        [r.at[0].bitcast(jnp.uint32)[pl.ds(hp, page, stride=Hkv // 2), :]
         for r in refs], axis=0)
    lo = pltpu.bitcast(w << 16, jnp.float32).astype(jnp.bfloat16)
    hi = pltpu.bitcast(w & jnp.uint32(0xFFFF0000),
                       jnp.float32).astype(jnp.bfloat16)
    return lo, hi


def _stage(pages, scales, dst, *, page, Hkv):
    """A KV block given as one ref per page, each (1, page * Hkv, hd), in
    float32 into ``dst``: row s·Hkv + h holds position s of head h, and a
    quantized pool's per-page per-head scales are applied. Lanes go in
    chunks of dst's width, chunk c from row c·S·Hkv, since a strided load
    wants rows of at most 128 lanes."""
    rows = page * Hkv
    R = len(pages) * rows
    w = dst.shape[1]
    for j, r in enumerate(pages):
        x = r[0].astype(jnp.float32)
        if scales is not None:
            x = (x.reshape(page, Hkv, -1)
                 * scales[j][0][None]).reshape(rows, -1)
        for c in range(dst.shape[0] // R):
            dst[pl.ds(c * R + j * rows, rows), :] = x[:, c * w:(c + 1) * w]


def _staged_rows(ref, h, S, Hkv):
    """Head h's (S, hd) rows of a block that ``_stage`` wrote."""
    parts = [ref[pl.ds(c * S * Hkv + h, S, stride=Hkv), :]
             for c in range(ref.shape[0] // (S * Hkv))]
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def _ragged_kernel(lyr_ref, bt_ref, meta_ref,   # scalar-prefetch operands
                   q_ref, pos_ref, *refs, P, page, Hkv, G, n_tiles,
                   softcap, scale, window, quant, dot_dtype):
    del lyr_ref, bt_ref
    kr, vr = refs[:P], refs[P:2 * P]
    if quant:
        ksr, vsr = refs[2 * P:3 * P], refs[3 * P:4 * P]
        refs = refs[4 * P:]
    else:
        ksr = vsr = None
        refs = refs[2 * P:]
    o_ref, acc_ref, m_ref, l_ref, *staged = refs
    t = pl.program_id(0)
    i = pl.program_id(1)
    nb = pl.num_programs(1)
    lo = meta_ref[_LO * n_tiles + t]
    hi = meta_ref[_HI * n_tiles + t]
    blk = meta_ref[_FIRST * n_tiles + t] + i
    live = (hi > lo) & (blk <= meta_ref[_LAST * n_tiles + t])

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(live)
    def _compute():
        S = P * page
        col = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1) + blk * S
        row_pos = pos_ref[...]                           # (T·G, 1)
        visible = col <= row_pos
        if window is not None:
            visible &= col > row_pos - window

        def attend(h, k, v):
            q = q_ref[h].astype(dot_dtype)               # (T·G, hd)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(visible, s, NEG_INF)
            m_prev = m_ref[h]                            # (T·G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[h] = m_new
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p.astype(dot_dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        if staged:
            kf_ref, vf_ref = staged
            _stage(kr, ksr, kf_ref, page=page, Hkv=Hkv)
            _stage(vr, vsr, vf_ref, page=page, Hkv=Hkv)
            n = 1

            def heads(g):
                return [(_staged_rows(kf_ref, g, S, Hkv).astype(dot_dtype),
                         _staged_rows(vf_ref, g, S, Hkv).astype(dot_dtype))]
        else:
            n = 2

            def heads(g):
                return zip(_head_pair_rows(kr, g, Hkv, page),
                           _head_pair_rows(vr, g, Hkv, page))

        # a loop, not an unrolled body: tracing and lowering Hkv copies of
        # it would cost seconds per compiled fused step
        @pl.loop(0, Hkv // n)
        def _(g):
            for j, (k, v) in enumerate(heads(g)):
                attend(g * n + j, k, v)

    @pl.when((i == nb - 1) & (hi > lo))
    def _flush():
        row = jax.lax.broadcasted_iota(jnp.int32, m_ref.shape, 1) // G
        mine = (row >= lo) & (row < hi)                  # (Hkv, T·G, 1)
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-37)
        o_ref[...] = jnp.where(mine, out.astype(o_ref.dtype), o_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("softcap", "scale", "window",
                                    "interpret"))
def ragged_paged_attention(q, k_pool, v_pool, layer, block_tables, tok_seq,
                           tok_pos, *, k_scale=None, v_scale=None,
                           softcap=None, scale=None, window=None,
                           interpret=None):
    """q: (N, Hkv, G, hd) flat mixed-batch query tokens; pools:
    (L, n_pages, page, Hkv, hd), of which layer ``layer`` (int32 scalar) is
    read; block_tables: (B, max_pages); tok_seq (N,) int32 names each
    token's sequence (block-table row); tok_pos (N,) int32 is its absolute
    position (-1 marks a padded token row — output zeros). ``window``
    (static) keeps only the last ``window`` positions visible.
    ``k_scale``/``v_scale`` (L, n_pages, Hkv) f32 dequantize low-bit pools
    in-register (both set or both None). Returns (N, Hkv, G, hd)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    N, Hkv, G, hd = q.shape
    L, n_pages, page, _, _ = k_pool.shape
    B, max_pages = block_tables.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    quant = k_scale is not None
    kv_dtype = np.dtype(k_pool.dtype)
    pairs = _pairs(Hkv, hd, kv_dtype.itemsize)
    # the MXU takes a bf16 or f32 pool's own values; dequantized K/V stay f32
    dot_dtype = (jnp.float32 if quant or kv_dtype.itemsize < 2
                 else kv_dtype)
    T, P = tile_sizes(N, Hkv, G, hd, page, max_pages,
                      np.dtype(q.dtype).itemsize, kv_dtype.itemsize)
    n_tiles, nb = grid_shape(N, B, T, P, max_pages)
    Nq = -(-N // T) * T

    # rows grouped by sequence, padded rows last; the sort is stable, so
    # a sequence keeps its row order
    i32 = jnp.int32
    key = jnp.where(tok_pos >= 0, tok_seq.astype(i32), B)
    order = jnp.argsort(key, stable=True)
    seq_s, pos_s = key[order], tok_pos.astype(i32)[order]
    meta = _tile_plan(seq_s, pos_s, B=B, T=T, P=P, page=page, window=window,
                      n_tiles=n_tiles)
    # head-major query rows: (Hkv, Nq·G, hd), row t·G + g
    qs = jnp.pad(q[order], ((0, Nq - N), (0, 0), (0, 0), (0, 0)))
    qs = qs.transpose(1, 0, 2, 3).reshape(Hkv, Nq * G, hd)
    row_pos = jnp.repeat(jnp.pad(pos_s, (0, Nq - N), constant_values=-1),
                         G)[:, None]

    def q_map(t, i, lyr, bt, mt):
        return (0, mt[_QB * n_tiles + t], 0)

    def page_map(j):
        def index(t, i, lyr, bt, mt):
            b = jnp.minimum(mt[_FIRST * n_tiles + t] + i,
                            mt[_LAST * n_tiles + t])
            col = jnp.minimum(b * P + j, max_pages - 1)
            return (lyr[0], bt[mt[_SEQ * n_tiles + t], col], 0, 0)
        return index

    q_spec = pl.BlockSpec((Hkv, T * G, hd), q_map)
    in_specs = [q_spec,
                pl.BlockSpec((T * G, 1),
                             lambda t, i, lyr, bt, mt:
                             (mt[_QB * n_tiles + t], 0))]
    # the layer axis is squeezed: the kernel sees one page of one layer
    page_specs = [pl.BlockSpec((None, 1, page * Hkv, hd), page_map(j))
                  for j in range(P)]
    in_specs += page_specs + page_specs
    operands = [qs, row_pos]
    operands += [k_pool.reshape(L, n_pages, page * Hkv, hd)] * P
    operands += [v_pool.reshape(L, n_pages, page * Hkv, hd)] * P
    if quant:
        # (L, n_pages, Hkv, 1): a page's scales as one legal (Hkv, 1) block
        scale_specs = [pl.BlockSpec((None, 1, Hkv, 1), page_map(j))
                       for j in range(P)]
        in_specs += scale_specs + scale_specs
        operands += [k_scale[..., None]] * P + [v_scale[..., None]] * P
    kernel = functools.partial(
        _ragged_kernel, P=P, page=page, Hkv=Hkv, G=G, n_tiles=n_tiles,
        softcap=softcap, scale=scale, window=window, quant=quant,
        dot_dtype=dot_dtype)
    scratch = [pltpu.VMEM((Hkv, T * G, hd), jnp.float32),
               pltpu.VMEM((Hkv, T * G, 1), jnp.float32),
               pltpu.VMEM((Hkv, T * G, 1), jnp.float32)]
    if not pairs:
        w = 128 if hd % 128 == 0 else hd             # float32 copy's lanes
        scratch += [pltpu.VMEM((hd // w * P * page * Hkv, w),
                               jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_tiles, nb),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, Nq * G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="ragged_paged_attention",
    )(jnp.reshape(layer, (1,)).astype(i32), block_tables.astype(i32), meta,
      *operands)
    out = out.reshape(Hkv, Nq, G, hd).transpose(1, 0, 2, 3)[:N]
    inv = jnp.zeros((N,), i32).at[order].set(jnp.arange(N, dtype=i32))
    out = out[inv]
    return jnp.where((tok_pos >= 0)[:, None, None, None], out,
                     jnp.zeros_like(out))
