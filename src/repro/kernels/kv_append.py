"""In-place KV append Pallas TPU kernel — the paged write path.

Each new token's K/V is written straight into its pool page slot: one
page-slot write per token, O(1) HBM traffic per generated token, instead of
the O(context) gather/scatter round trip of the contiguous staging path
(DESIGN.md §9). The pools are aliased to the outputs so only the targeted
slots are touched.

Rows flagged invalid (batch padding from bucketing, or chunk padding past a
request's real token range) must never corrupt live pages. Their page id is
still used as the DMA target — the caller MUST point it at a write-discard
page (the engine's reserved scratch page) that no valid row in the same
call writes. The kernel then copies that slot's content back instead of
writing the padding K/V. Clamping invalid rows onto a fixed slot like
(0, 0) would be wrong: page 0 is ordinarily allocatable, and when a valid
write to a slot is followed by an invalid row resolving to the same block
index, the pipeline may reuse the stale prefetched input block and the
"no-op" copy-back would overwrite the fresh value. Routing invalids to a
dedicated discard page makes the stale rewrite harmless by construction
(the discard page holds garbage; several invalid rows aliasing it are fine
— the TPU grid is sequential).

The pools are the model's stacked ones, (L, n_pages, page, Hkv, hd), one
slab per layer of a layer group, and a call writes layer ``layer`` of them:
the layer index is a scalar-prefetch operand of every slot's index map, so
the layer scan carries the whole stack through each layer's call in place
and no layer's pool is ever sliced out or copied (DESIGN.md §9).

Grid: (n_rows,); the layer, page id / offset / valid flag are
scalar-prefetch operands so the DMA destination of row i is known while row
i-1 is in flight.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _append_kernel(layer, page_ids, offsets, valid,     # scalar prefetch
                   k_pool_ref, v_pool_ref, k_ref, v_ref, k_out, v_out):
    del layer, page_ids, offsets
    n = pl.program_id(0)

    @pl.when(valid[n] != 0)
    def _write():
        k_out[0, 0] = k_ref[0].astype(k_out.dtype)
        v_out[0, 0] = v_ref[0].astype(v_out.dtype)

    @pl.when(valid[n] == 0)
    def _discard():                 # padded row: rewrite the slot unchanged
        k_out[...] = k_pool_ref[...]
        v_out[...] = v_pool_ref[...]


# donation pairs with the pallas_call's input_output_aliases below: the
# pools are donated so the in-place alias never forces a defensive copy
# (the caller must not touch the arrays it passed in again)
@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0, 1))
def kv_append(k_pool, v_pool, layer, k_new, v_new, page_ids, offsets, valid,
              *, interpret=None):
    """Scatter new K/V rows into their pool page slots of one layer.

    k_pool/v_pool: (L, n_pages, page, Hkv, hd) stacked over layers; layer:
    int32 scalar; k_new/v_new: (N, Hkv, hd); page_ids/offsets/valid: (N,)
    int32. Row i writes k_new[i]/v_new[i] into pool slot (layer,
    page_ids[i], offsets[i]) iff valid[i] != 0; invalid rows
    have their K/V discarded, but their (page_ids[i], offsets[i]) is still
    the DMA target and MUST name a write-discard page no valid row of the
    same call writes (see module docstring). Returns the updated
    (k_pool, v_pool); the inputs are donated and aliased to the outputs.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    N = k_new.shape[0]
    *_, Hkv, hd = k_pool.shape

    def slot(n, lyr, ids, offs, val):
        del val
        return (lyr[0], ids[n], offs[n], 0, 0)

    def row(n, lyr, ids, offs, val):
        del lyr, ids, offs, val
        return (n, 0, 0)

    pool_block = pl.BlockSpec((None, 1, 1, Hkv, hd), slot)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(N,),
        in_specs=[
            pool_block,                              # k_pool (read-back)
            pool_block,                              # v_pool (read-back)
            pl.BlockSpec((1, Hkv, hd), row),         # k_new
            pl.BlockSpec((1, Hkv, hd), row),         # v_new
        ],
        out_specs=[pool_block, pool_block],
    )
    return pl.pallas_call(
        _append_kernel, grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)),
        input_output_aliases={4: 0, 5: 1},   # pools flow through in place
        interpret=interpret,
        name="kv_append",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), page_ids, offsets, valid,
      k_pool, v_pool, k_new, v_new)
