"""Swap pack/unpack Pallas TPU kernels.

The paper's Swap analysis (§3.2) found that with PagedAttention the context
of one request scatters across many non-contiguous pages, so swapping costs
one kernel launch per region on GPU. The TPU analogue is many small
host DMAs. Adaptation (DESIGN.md §2): coalesce on-device first — a gather
kernel packs the request's pages into one contiguous staging buffer (swap
out), and a scatter kernel writes a staged buffer back into pool pages
(swap in). The host transfer then moves one big contiguous slab, which is
what the PCIe path wants, and the gather itself is HBM-bandwidth-bound
(cheap, hidden behind the model step per the §4.1 budget).

Grid: (n_pages_to_move,), page id as scalar-prefetch for the dynamic index.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from collections import deque
from typing import Any, List

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pack_kernel(page_ids, src_ref, dst_ref):
    del page_ids
    dst_ref[...] = src_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def swap_pack(pool, page_ids, *, interpret=None):
    """Gather pool pages into a contiguous staging buffer.

    pool: (n_pages, ...) page-major, any trailing rank — the KV payload's
    (n_pages, page, Hkv, hd) and a quantized pool's per-page scale leaf
    (n_pages, Hkv) go through the same gather, so one slab carries both;
    page_ids: (n,) int32 -> (n, ...).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n = page_ids.shape[0]
    rest = pool.shape[1:]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec((1,) + rest,
                               lambda i, ids: (ids[i],) + (0,) * len(rest))],
        out_specs=pl.BlockSpec((1,) + rest,
                               lambda i, ids: (i,) + (0,) * len(rest)),
    )
    return pl.pallas_call(
        _pack_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n,) + rest, pool.dtype),
        interpret=interpret,
    )(page_ids, pool)


def _unpack_kernel(page_ids, pool_in_ref, staging_ref, pool_ref):
    del page_ids, pool_in_ref   # pool content flows through the alias
    pool_ref[...] = staging_ref[...]


# donation pairs with swap_unpack's input_output_aliases: the pool is
# rewritten in place (the caller must not touch the array it passed in)
@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def swap_unpack(pool, staging, page_ids, *, interpret=None):
    """Scatter a staged buffer back into pool pages (returns updated pool).

    pool: (n_pages, ...) page-major, any trailing rank (payload or scale
    leaf — see swap_pack); staging: (n, ...); page_ids: (n,) int32. The
    pool is aliased to the output, so only the targeted pages are
    rewritten.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n = page_ids.shape[0]
    rest = pool.shape[1:]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec((1,) + rest,
                               lambda i, ids: (ids[i],) + (0,) * len(rest)),
                  pl.BlockSpec((1,) + rest,
                               lambda i, ids: (i,) + (0,) * len(rest))],
        out_specs=pl.BlockSpec((1,) + rest,
                               lambda i, ids: (ids[i],) + (0,) * len(rest)),
    )
    return pl.pallas_call(
        _unpack_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={1: 0},   # alias the pool to the output
        interpret=interpret,
    )(page_ids, pool, staging)


# ---------------------------------------------------------------------------
# Double-buffered staging for the pipelined engine step (DESIGN.md §12)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class StagedSlab:
    """One in-flight swap-out slab: the on-device gather has been issued
    (``arrays`` is a device pytree whose transfer may still be draining)
    but the host copy has not been collected yet. When the stager spills
    a slab to bound device staging memory, ``arrays`` is dropped and
    ``host`` holds the completed host copy."""
    ticket: int
    arrays: Any
    n_pages: int
    host: Any = None


class SwapStager:
    """Issue/collect split of the coalesced swap transfer, double-buffered.

    ``pack()`` enqueues the on-device gather of a request's pool pages into
    a contiguous slab (the swap_pack coalescing above; ``jnp.take`` on the
    XLA path, compiled to the Pallas gather on TPU) and returns a ticket
    WITHOUT synchronizing — the DMA drains while the caller dispatches the
    model step. ``collect(ticket)`` resolves a ticket to the host slab,
    blocking only on that transfer. At most ``depth`` slabs (default 2:
    classic double buffering) hold device staging memory at once; packing
    a third SPILLS the oldest — its transfer is completed host-side (the
    slab's final destination anyway) and its device buffers dropped — so
    device staging stays bounded no matter how many requests one
    iteration swaps out. ``unpack()`` is the inbound direction: scatter a
    host slab back into freshly allocated pool pages in one device
    transfer (swap_unpack on TPU), returning the new pools.

    The pytree/axis generality (engine pools are stacked
    ``(periods, n_pages, page, ...)`` per layer) lives here so the engine
    only reasons in tickets and page ids.
    """

    def __init__(self, depth: int = 2, page_axis: int = 1):
        assert depth >= 1
        self.depth = depth
        self.page_axis = page_axis
        self._inflight = deque()            # StagedSlab, FIFO
        self._tickets = itertools.count()
        self.packed_pages = 0
        self.collected_pages = 0
        self.unpacked_pages = 0

    @property
    def inflight(self) -> int:
        return len(self._inflight)

    def pack(self, pools, page_ids: List[int]) -> int:
        """Issue the gather of ``page_ids`` from ``pools`` into a staged
        slab; returns a ticket for collect(). Never synchronizes on the
        new slab — when ``depth`` slabs already hold device staging, the
        oldest is spilled host-side first so device memory stays
        bounded."""
        while sum(1 for s in self._inflight
                  if s.arrays is not None) >= self.depth:
            self._spill_oldest()  # lint: allow(dispatch-host-sync): bounded staging — depth exceeded, oldest slab's DMA must complete
        ids = jnp.asarray(page_ids, jnp.int32)
        arrays = jax.tree.map(
            lambda leaf: jnp.take(leaf, ids, axis=self.page_axis), pools)
        slab = StagedSlab(next(self._tickets), arrays, len(page_ids))
        self._inflight.append(slab)
        self.packed_pages += slab.n_pages
        return slab.ticket

    def _spill_oldest(self):
        """Complete the oldest still-device-resident slab's transfer to
        host and release its device buffers."""
        for slab in self._inflight:
            if slab.arrays is not None:
                slab.host = jax.device_get(slab.arrays)
                slab.arrays = None
                return

    def collect(self, ticket: int):
        """Resolve a ticket to its host-side slab (numpy pytree, page axis
        = ``page_axis``), blocking on that transfer only (already-spilled
        slabs return their completed host copy immediately)."""
        for i, slab in enumerate(self._inflight):
            if slab.ticket == ticket:
                del self._inflight[i]
                self.collected_pages += slab.n_pages
                return slab.host if slab.arrays is None \
                    else jax.device_get(slab.arrays)
        raise KeyError(f"unknown or already-collected ticket {ticket}")

    def unpack(self, pools, page_ids: List[int], host_slab):
        """Scatter a host slab back into ``pools`` at ``page_ids`` as one
        device transfer; returns the new pools (issue-only: the caller's
        next dispatch consumes the updated pools without a host sync)."""
        ids = jnp.asarray(page_ids, jnp.int32)
        ax = self.page_axis
        new = jax.tree.map(
            lambda leaf, val: leaf.at[(slice(None),) * ax + (ids,)].set(
                jnp.asarray(val, leaf.dtype)),
            pools, host_slab)
        self.unpacked_pages += len(page_ids)
        return new
