"""Quantized KV page append — per-page, per-head scales (DESIGN.md §17).

KV pools can be stored low-bit (``Engine(kv_dtype="int8" | "float8_e4m3" |
"float8_e5m2")``) with one fp32 scale per (page, kv head) living in the
same pool pytree as the payload (``{"k", "v", "k_scale", "v_scale"}``), so
every page-lifecycle mechanism — COW copies, swap slabs, prefix-cache
adoption — moves payload and scales together for free.

The append is requantize-on-append, split into three phases so the
existing slot-granular ``kv_append`` kernel is reused unchanged:

  A. scale update (XLA): per-row amax over the head dim, scatter-max'd
     into the per-page scales (``new_scale = max(old, amax/qmax)``, a
     monotone update: pages only coarsen while alive; frees zero them).
  B. page requant: every touched page's existing payload is rescaled by
     ``old_scale / new_scale`` so one page never mixes scales. On the
     Pallas path this is a whole-page grid with the page id as
     scalar-prefetch; rows that are NOT the first occurrence of their
     page in this call (and invalid rows) are routed to the caller's
     write-discard page — same revolving-buffer rationale as kv_append's
     contract — so each live page is rewritten exactly once per call.
  C. row write: the new rows, quantized with the updated scales, go
     through the ordinary ``kv_append`` scatter (it is dtype-generic).

Pools and scales are the model's stacked ones (a leading layer axis) and a
call touches one layer of them, named by a scalar-prefetch layer index as
in ``kv_append``; only that layer's (n_pages, Hkv) scales are sliced out.

fp8 casts in XLA saturate to NaN on overflow, so every quantize/requant
clips to ±qmax BEFORE the cast; int8 rounds with ``jnp.rint`` (ties to
even) then clips. A zero scale means "page holds nothing" — safe-divide
maps it to ratio 0, which only ever zeroes slots that are dead or about
to be overwritten.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# public name -> (storage dtype, largest representable magnitude)
KV_QUANT_DTYPES = {
    "int8": (jnp.int8, 127.0),
    "float8_e4m3": (jnp.float8_e4m3fn, 448.0),
    "float8_e5m2": (jnp.float8_e5m2, 57344.0),
}


def kv_quant_jnp_dtype(name: str):
    """Resolve a public kv_dtype name to its jnp storage dtype."""
    try:
        return KV_QUANT_DTYPES[name][0]
    except KeyError:
        raise ValueError(
            f"unsupported kv_dtype {name!r}; "
            f"choose from {sorted(KV_QUANT_DTYPES)}") from None


def kv_quant_qmax(dtype) -> float:
    """qmax for a quantized pool's storage dtype."""
    d = jnp.dtype(dtype)
    for jd, qmax in KV_QUANT_DTYPES.values():
        if jnp.dtype(jd) == d:
            return qmax
    raise ValueError(f"not a quantized KV pool dtype: {d}")


def quantize_rows(x, scale, qdtype):
    """x: (..., Hkv, hd) -> qdtype, dividing by scale (..., Hkv).

    Zero scales (empty page) quantize to 0; values are clipped to ±qmax
    before the cast (fp8 casts NaN on overflow)."""
    qmax = kv_quant_qmax(qdtype)
    y = jnp.where(scale[..., None] > 0,
                  x.astype(jnp.float32) / jnp.where(scale[..., None] > 0,
                                                    scale[..., None], 1.0),
                  0.0)
    if jnp.issubdtype(jnp.dtype(qdtype), jnp.integer):
        y = jnp.rint(y)
    y = jnp.clip(y, -qmax, qmax)
    return y.astype(qdtype)


def requant_payload(q, ratio, qdtype):
    """Rescale already-quantized payload by ratio = old_scale/new_scale.

    q: (..., Hkv, hd) qdtype; ratio: (..., Hkv). ratio == 1 is exact
    identity for every supported dtype (int8 re-rounds an integer; fp8
    round-trips through f32 losslessly)."""
    qmax = kv_quant_qmax(qdtype)
    y = q.astype(jnp.float32) * ratio[..., None]
    if jnp.issubdtype(jnp.dtype(qdtype), jnp.integer):
        y = jnp.rint(y)
    y = jnp.clip(y, -qmax, qmax)
    return y.astype(qdtype)


def updated_page_scales(k_scale, v_scale, k_new, v_new, pids_drop, qmax):
    """Phase A: monotone per-(page, head) scale update.

    k_scale/v_scale: (n_pages, Hkv) f32; k_new/v_new: (N, Hkv, hd);
    pids_drop: (N,) int32 with out-of-range ids for rows whose write must
    be dropped. Returns the updated (k_scale, v_scale)."""
    k_amax = jnp.max(jnp.abs(k_new.astype(jnp.float32)), axis=-1)  # (N, Hkv)
    v_amax = jnp.max(jnp.abs(v_new.astype(jnp.float32)), axis=-1)
    k_scale = k_scale.at[pids_drop].max(k_amax / qmax, mode="drop")
    v_scale = v_scale.at[pids_drop].max(v_amax / qmax, mode="drop")
    return k_scale, v_scale


def first_occurrence(pids_drop):
    """first[i] is True iff no earlier row of this call names the same
    page — the one row per page that performs the phase-B requant."""
    eq = pids_drop[:, None] == pids_drop[None, :]
    earlier = jnp.tril(eq, k=-1)
    return ~jnp.any(earlier, axis=1)


# --------------------------------------------------------------------------
# Phase B Pallas kernel: whole-page requant, page id as scalar prefetch
# --------------------------------------------------------------------------
def _requant_kernel(layer, rpids, k_pool_ref, v_pool_ref, k_ratio_ref,
                    v_ratio_ref, k_out, v_out, *, qmax: float,
                    integer: bool):
    del layer, rpids

    def scale_page(pool_ref, ratio_ref, out_ref):
        y = pool_ref[0].astype(jnp.float32) * ratio_ref[0][None]
        if integer:
            y = jnp.rint(y)
        out_ref[0] = jnp.clip(y, -qmax, qmax).astype(out_ref.dtype)

    scale_page(k_pool_ref, k_ratio_ref, k_out)
    scale_page(v_pool_ref, v_ratio_ref, v_out)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0, 1))
def page_requant(k_pool, v_pool, layer, k_ratio, v_ratio, rpids, *,
                 interpret=None):
    """Rescale whole pages of one layer in place: page rpids[i] gets
    payload *= ratio[i] (re-rounded / re-cast). Rows routed to a
    write-discard page (duplicate occurrences, invalid rows) clobber only
    that page. k_pool/v_pool: (L, n_pages, page, Hkv, hd) quantized,
    donated; layer: int32 scalar; k_ratio/v_ratio: (N, Hkv) f32; rpids:
    (N,) int32."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    N = rpids.shape[0]
    *_, page, Hkv, hd = k_pool.shape
    qmax = kv_quant_qmax(k_pool.dtype)
    integer = jnp.issubdtype(k_pool.dtype, jnp.integer)

    def slot(n, lyr, ids):
        return (lyr[0], ids[n], 0, 0, 0)

    def row(n, lyr, ids):
        del lyr, ids
        return (n, 0, 0)

    page_block = pl.BlockSpec((None, 1, page, Hkv, hd), slot)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N,),
        in_specs=[
            page_block,                               # k_pool (read-back)
            page_block,                               # v_pool (read-back)
            # ratios as (N, Hkv, 1): one legal (Hkv, 1) block per row
            pl.BlockSpec((1, Hkv, 1), row),           # k_ratio
            pl.BlockSpec((1, Hkv, 1), row),           # v_ratio
        ],
        out_specs=[page_block, page_block],
    )
    kernel = functools.partial(_requant_kernel, qmax=qmax, integer=integer)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)),
        input_output_aliases={2: 0, 3: 1},   # pools flow through in place
        interpret=interpret,
        name="page_requant",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rpids, k_pool, v_pool,
      k_ratio[..., None], v_ratio[..., None])


def kv_append_quant(k_pool, v_pool, k_scale, v_scale, layer, k_new, v_new,
                    page_ids, offsets, valid, discard_pid, *,
                    interpret=None):
    """Quantized scatter of new K/V rows into one layer (Pallas path).

    Pools: (L, n_pages, page, Hkv, hd) quantized; scales: (L, n_pages,
    Hkv) f32; layer: int32 scalar; rows as in kv_append. ``discard_pid``
    MUST name a write-discard page (kv_append contract). Returns (k_pool,
    v_pool, k_scale, v_scale)."""
    from repro.kernels.kv_append import kv_append
    n_pages = k_pool.shape[1]
    qmax = kv_quant_qmax(k_pool.dtype)
    live = valid != 0
    pids_drop = jnp.where(live, page_ids, n_pages)       # OOB -> dropped
    k_scale_l, v_scale_l = k_scale[layer], v_scale[layer]
    new_k_scale, new_v_scale = updated_page_scales(
        k_scale_l, v_scale_l, k_new, v_new, pids_drop, qmax)

    # phase B: one requant per touched page; duplicates/invalids -> discard
    first = first_occurrence(pids_drop)
    rpids = jnp.where(live & first, page_ids, discard_pid).astype(jnp.int32)
    gidx = jnp.clip(pids_drop, 0, n_pages - 1)
    k_ratio = jnp.where(new_k_scale[gidx] > 0,
                        k_scale_l[gidx] / jnp.where(new_k_scale[gidx] > 0,
                                                    new_k_scale[gidx], 1.0),
                        0.0)
    v_ratio = jnp.where(new_v_scale[gidx] > 0,
                        v_scale_l[gidx] / jnp.where(new_v_scale[gidx] > 0,
                                                    new_v_scale[gidx], 1.0),
                        0.0)
    k_pool, v_pool = page_requant(k_pool, v_pool, layer, k_ratio, v_ratio,
                                  rpids, interpret=interpret)

    # phase C: quantize the rows with the post-update scales and reuse the
    # slot-granular append kernel (dtype-generic; invalid rows discard)
    qk = quantize_rows(k_new, new_k_scale[gidx], k_pool.dtype)
    qv = quantize_rows(v_new, new_v_scale[gidx], v_pool.dtype)
    wpids = jnp.where(live, page_ids, discard_pid).astype(jnp.int32)
    k_pool, v_pool = kv_append(k_pool, v_pool, layer, qk, qv, wpids,
                               offsets.astype(jnp.int32),
                               valid.astype(jnp.int32), interpret=interpret)
    return (k_pool, v_pool, k_scale.at[layer].set(new_k_scale),
            v_scale.at[layer].set(new_v_scale))
