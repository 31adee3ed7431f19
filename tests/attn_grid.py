"""Brute-force count of the ragged attention kernel's grid and live steps
(kernels/ragged_paged_attention.py), for tests of ``grid_steps`` and of
the engine's attn_grid_steps / attn_live_steps counters."""
from repro.kernels.ragged_paged_attention import grid_shape, tile_sizes


def brute_force_steps(spans, N, B, *, Hkv, G, hd, page, max_pages, q_bytes,
                      kv_bytes, window=None):
    """(grid steps, live steps) of one launch. ``spans`` lists (sequence,
    first position, rows) in row order, padded rows after. A KV block is
    live for a tile (one sequence's rows in one q block of T rows) when
    some row of the tile sees some position in it."""
    T, P = tile_sizes(N, Hkv, G, hd, page, max_pages, q_bytes, kv_bytes)
    nt, nb = grid_shape(N, B, T, P, max_pages)
    rows = [(b, p0 + i) for b, p0, n in spans for i in range(n)]
    tiles = {}
    for r, (b, p) in enumerate(rows):
        tiles.setdefault((r // T, b), []).append(p)
    assert len(tiles) <= nt
    S = P * page
    live = 0
    for ps in tiles.values():
        for i in range(nb):
            xs = range(i * S, min((i + 1) * S, max_pages * page))
            live += any(x <= p and (window is None or x > p - window)
                        for p in ps for x in xs)
    return nt * nb, live
