"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU): shape and
dtype sweeps per kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.kv_append import kv_append
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ragged_paged_attention import ragged_paged_attention
from repro.kernels.swap_pack import swap_pack, swap_unpack

try:
    import hypothesis.strategies as hyp_st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:                                  # optional dependency
    HAVE_HYPOTHESIS = False

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("B,Hkv,G,Tq,Tk,hd", [
    (1, 1, 1, 128, 128, 64),
    (2, 2, 4, 128, 128, 64),
    (1, 2, 2, 64, 128, 32),     # cross-length (prefix context)
    (2, 1, 8, 128, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(B, Hkv, G, Tq, Tk, hd, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hkv, G, Tq, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Tk, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Tk, hd)).astype(dtype)
    out = flash_attention(q, k, v, bq=64, bk=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("window,softcap,causal", [
    (None, None, True), (64, None, True), (None, 30.0, True),
    (32, 50.0, True), (None, None, False),
])
def test_flash_attention_masking(window, softcap, causal):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 2, 128, 64))
    k = jax.random.normal(ks[1], (1, 2, 128, 64))
    v = jax.random.normal(ks[2], (1, 2, 128, 64))
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, bq=64, bk=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("B,Hkv,G,hd,page,max_pages,n_pages", [
    (2, 2, 4, 64, 16, 8, 32),
    (4, 1, 8, 128, 8, 16, 64),
    (1, 4, 1, 32, 32, 4, 16),
    (3, 2, 2, 64, 16, 5, 20),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention(B, Hkv, G, hd, page, max_pages, n_pages, dtype):
    rng = np.random.default_rng(B * 7 + page)
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hkv, G, hd)).astype(dtype)
    kp = jax.random.normal(ks[1], (n_pages, page, Hkv, hd)).astype(dtype)
    vp = jax.random.normal(ks[2], (n_pages, page, Hkv, hd)).astype(dtype)
    bt = jnp.asarray(rng.integers(0, n_pages, (B, max_pages)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, page * max_pages + 1, (B,)),
                       jnp.int32)
    out = paged_attention(q, kp[None], vp[None], 0, bt, lens,
                          interpret=True)
    want = ref.paged_attention_ref(q, kp[None], vp[None], 0, bt, lens)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_paged_attention_matches_dense_decode():
    """Paged decode == flash over the gathered dense cache (cross-oracle)."""
    rng = np.random.default_rng(3)
    B, Hkv, G, hd, page, max_pages, n_pages = 2, 2, 2, 32, 8, 6, 24
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hkv, G, hd))
    kp = jax.random.normal(ks[1], (n_pages, page, Hkv, hd))
    vp = jax.random.normal(ks[2], (n_pages, page, Hkv, hd))
    bt = jnp.asarray(rng.integers(0, n_pages, (B, max_pages)), jnp.int32)
    lens = jnp.asarray([page * max_pages, 17], jnp.int32)
    out = paged_attention(q, kp[None], vp[None], 0, bt, lens,
                          interpret=True)
    k = kp[bt].reshape(B, max_pages * page, Hkv, hd)
    v = vp[bt].reshape(B, max_pages * page, Hkv, hd)
    s = jnp.einsum("bhgd,bshd->bhgs", q, k) / np.sqrt(hd)
    valid = jnp.arange(max_pages * page)[None] < lens[:, None]
    s = jnp.where(valid[:, None, None], s, -1e30)
    want = jnp.einsum("bhgs,bshd->bhgd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("G", [1, 4])
@pytest.mark.parametrize("ctx", [1, 8, 16, 23, 32])
def test_paged_attention_ragged_edges(G, ctx):
    """Explicit ragged ctx_lens edge cases per GQA group size: ctx=1,
    ctx exactly on a page boundary (8, 16), mid-page (23), and the full
    page-table width (32 = page * max_pages)."""
    rng = np.random.default_rng(G * 100 + ctx)
    Hkv, hd, page, max_pages, n_pages = 2, 32, 8, 4, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (3, Hkv, G, hd))
    kp = jax.random.normal(ks[1], (n_pages, page, Hkv, hd))
    vp = jax.random.normal(ks[2], (n_pages, page, Hkv, hd))
    bt = jnp.asarray(rng.integers(0, n_pages, (3, max_pages)), jnp.int32)
    # one row at the edge case, the others ragged around it
    lens = jnp.asarray([ctx, max(1, ctx - 1), min(page * max_pages, ctx + 1)],
                       jnp.int32)
    out = paged_attention(q, kp[None], vp[None], 0, bt, lens,
                          interpret=True)
    want = ref.paged_attention_ref(q, kp[None], vp[None], 0, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("window", [1, 7, 16])
def test_paged_attention_sliding_window(window):
    rng = np.random.default_rng(window)
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 2, 2, 32))
    kp = jax.random.normal(ks[1], (16, 8, 2, 32))
    vp = jax.random.normal(ks[2], (16, 8, 2, 32))
    bt = jnp.asarray(rng.integers(0, 16, (2, 4)), jnp.int32)
    lens = jnp.asarray([29, 5], jnp.int32)
    out = paged_attention(q, kp[None], vp[None], 0, bt, lens,
                          window=window, interpret=True)
    want = ref.paged_attention_ref(q, kp[None], vp[None], 0, bt, lens,
                                   window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# ragged-query paged attention (the fused mixed-batch core, DESIGN.md §10)
# ---------------------------------------------------------------------------
def _span_rows(spans, N):
    """tok_seq / tok_pos for rows laid out as the fused step lays them:
    each (sequence, first position, rows) span in order, padded rows
    (position -1) after."""
    seq = [b for b, _, n in spans for _ in range(n)]
    pos = [p0 + i for _, p0, n in spans for i in range(n)]
    pad = N - len(seq)
    return (jnp.asarray(seq + [0] * pad, jnp.int32),
            jnp.asarray(pos + [-1] * pad, jnp.int32))


# query tiles (kernels/ragged_paged_attention.py): T·G query rows per tile
# reach 128, so a chunk of more than 128 / G rows spans several tiles
TILED = {
    # MHA: a 150-row chunk from mid-page position 13 over two tiles of 128,
    # decodes (one at full context, 32 pages x 8), padded rows
    "mha-chunk-midpage": ((2, 13, 150), (0, 200, 1), (3, 37, 1),
                          (1, 255, 1)),
    # GQA 8: tiles of 16 rows; a 40-row chunk from position 0, a decode at
    # full context (8 pages x 16), a short decode, padded rows
    "gqa-chunk-at-0": ((1, 0, 40), (0, 127, 1), (2, 5, 1)),
    # head size 64: a chunk that ends at full context (6 pages x 8) beside
    # a decode of another sequence at full context
    "chunk-to-full-context": ((0, 20, 28), (1, 47, 1)),
    # an odd group (G 3): q blocks of 64 rows, a 90-row chunk over two of
    # them, decodes, padded rows
    "odd-group": ((0, 5, 90), (2, 127, 1), (1, 60, 1)),
}


def _layer_of_stack(pool, L, key):
    """(L, ...) stack whose layer 1 is ``pool`` and whose other layers hold
    other values, or ``pool`` as a one-layer stack (L = 1, layer 0);
    returns (stack, layer)."""
    if L == 1:
        return pool[None], 0
    other = jax.random.normal(key, (L,) + pool.shape).astype(pool.dtype)
    return other.at[1].set(pool), 1


@pytest.mark.parametrize("N,Hkv,G,hd,page,max_pages,n_pages,B,spans,L", [
    pytest.param(1, 2, 4, 64, 16, 8, 32, 1, None, 1,   # a single decode
                 id="1-2-4-64-16-8-32-1"),
    pytest.param(9, 2, 4, 64, 16, 8, 32, 3, None, 1,   # mixed ragged batch
                 id="9-2-4-64-16-8-32-3"),
    pytest.param(6, 1, 8, 128, 8, 16, 64, 2, None, 1,
                 id="6-1-8-128-8-16-64-2"),
    pytest.param(5, 4, 1, 32, 32, 4, 16, 5, None, 1,
                 id="5-4-1-32-32-4-16-5"),
    pytest.param(192, 4, 1, 128, 8, 32, 96, 4, TILED["mha-chunk-midpage"], 1,
                 id="mha-chunk-midpage"),
    pytest.param(64, 2, 8, 128, 16, 8, 32, 3, TILED["gqa-chunk-at-0"], 1,
                 id="gqa-chunk-at-0"),
    pytest.param(32, 2, 4, 64, 8, 6, 24, 2, TILED["chunk-to-full-context"],
                 1, id="chunk-to-full-context"),
    pytest.param(128, 2, 3, 128, 8, 16, 48, 3, TILED["odd-group"], 1,
                 id="odd-group"),
    # layer 1 of a three-layer stack, G 1 and G 8
    pytest.param(5, 4, 1, 32, 32, 4, 16, 5, None, 3, id="G1-layer1-of-3"),
    pytest.param(64, 2, 8, 128, 16, 8, 32, 3, TILED["gqa-chunk-at-0"], 3,
                 id="G8-layer1-of-3"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_paged_attention(N, Hkv, G, hd, page, max_pages, n_pages, B,
                                spans, L, dtype):
    """Against the oracle; on a stack of L = 3 at layer 1, also bit-equal
    to the kernel on that layer's pool alone (a one-layer stack)."""
    rng = np.random.default_rng(N * 13 + page)
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (N, Hkv, G, hd)).astype(dtype)
    kp = jax.random.normal(ks[1], (n_pages, page, Hkv, hd)).astype(dtype)
    vp = jax.random.normal(ks[2], (n_pages, page, Hkv, hd)).astype(dtype)
    bt = jnp.asarray(rng.integers(0, n_pages, (B, max_pages)), jnp.int32)
    if spans is None:
        tok_seq = jnp.asarray(rng.integers(0, B, (N,)), jnp.int32)
        tok_pos = jnp.asarray(rng.integers(0, page * max_pages, (N,)),
                              jnp.int32)
    else:
        tok_seq, tok_pos = _span_rows(spans, N)
    k_stack, layer = _layer_of_stack(kp, L, ks[3])
    v_stack, _ = _layer_of_stack(vp, L, ks[4])
    out = ragged_paged_attention(q, k_stack, v_stack, layer, bt, tok_seq,
                                 tok_pos, interpret=True)
    want = ref.ragged_paged_attention_ref(q, k_stack, v_stack, layer, bt,
                                          tok_seq, tok_pos)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    real = np.asarray(tok_pos) >= 0
    np.testing.assert_allclose(np.asarray(out, np.float32)[real],
                               np.asarray(want, np.float32)[real], atol=tol)
    assert not np.asarray(out, np.float32)[~real].any(), \
        "padded rows must come out zero"
    if L > 1:
        alone = ragged_paged_attention(q, kp[None], vp[None], 0, bt,
                                       tok_seq, tok_pos, interpret=True)
        assert jnp.array_equal(out, alone)


@pytest.mark.parametrize("window,softcap,tiled", [
    pytest.param(7, None, False, id="7-None"),
    pytest.param(16, None, False, id="16-None"),
    pytest.param(None, 30.0, False, id="None-30.0"),
    pytest.param(9, 25.0, False, id="9-25.0"),
    # a chunk over several query tiles (GQA 8: 16 rows a tile) whose window
    # starts its page loop past block 0, with decodes and padded rows
    pytest.param(20, 30.0, True, id="tiled-20-30.0"),
    pytest.param(200, None, True, id="tiled-200-None"),
])
def test_ragged_paged_attention_window_softcap(window, softcap, tiled):
    rng = np.random.default_rng(0 if window is None else window)
    ks = jax.random.split(KEY, 3)
    if tiled:
        Hkv, G, hd, page, max_pages, n_pages = 2, 8, 128, 8, 40, 96
        q = jax.random.normal(ks[0], (64, Hkv, G, hd))
        kp = jax.random.normal(ks[1], (n_pages, page, Hkv, hd))
        vp = jax.random.normal(ks[2], (n_pages, page, Hkv, hd))
        bt = jnp.asarray(rng.integers(0, n_pages, (3, max_pages)), jnp.int32)
        tok_seq, tok_pos = _span_rows(
            ((0, 250, 45), (1, 319, 1), (2, 3, 1)), 64)
    else:
        q = jax.random.normal(ks[0], (6, 2, 2, 32))
        kp = jax.random.normal(ks[1], (16, 8, 2, 32))
        vp = jax.random.normal(ks[2], (16, 8, 2, 32))
        bt = jnp.asarray(rng.integers(0, 16, (2, 4)), jnp.int32)
        tok_seq = jnp.asarray([0, 0, 0, 1, 1, 0], jnp.int32)
        tok_pos = jnp.asarray([0, 12, 31, 7, 8, 29], jnp.int32)
    out = ragged_paged_attention(q, kp[None], vp[None], 0, bt, tok_seq,
                                 tok_pos, window=window, softcap=softcap,
                                 interpret=True)
    want = ref.ragged_paged_attention_ref(q, kp[None], vp[None], 0, bt,
                                          tok_seq, tok_pos, window=window,
                                          softcap=softcap)
    real = np.asarray(tok_pos) >= 0
    np.testing.assert_allclose(np.asarray(out)[real], np.asarray(want)[real],
                               atol=2e-5)


def test_ragged_degenerates_to_paged_attention():
    """One token per sequence at position ctx_lens-1 IS the decode kernel:
    both kernels must agree (cross-oracle, padded rows excluded)."""
    rng = np.random.default_rng(3)
    B, Hkv, G, hd, page, max_pages, n_pages = 4, 2, 2, 32, 8, 6, 24
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hkv, G, hd))
    kp = jax.random.normal(ks[1], (n_pages, page, Hkv, hd))
    vp = jax.random.normal(ks[2], (n_pages, page, Hkv, hd))
    bt = jnp.asarray(rng.integers(0, n_pages, (B, max_pages)), jnp.int32)
    lens = jnp.asarray([page * max_pages, 17, 1, 0], jnp.int32)  # 0 = pad
    got = ragged_paged_attention(q, kp[None], vp[None], 0, bt,
                                 jnp.arange(B, dtype=jnp.int32),
                                 lens - 1, interpret=True)
    want = paged_attention(q, kp[None], vp[None], 0, bt, lens,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(got)[:3], np.asarray(want)[:3],
                               atol=2e-5)


def test_ragged_chunk_internal_causality():
    """Tokens of one chunk attend to earlier chunk tokens but never later
    ones: perturbing the K/V slot of position p must change only queries
    at positions >= p."""
    rng = np.random.default_rng(1)
    Hkv, G, hd, page, max_pages, n_pages = 2, 2, 32, 8, 4, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (4, Hkv, G, hd))
    kp = jax.random.normal(ks[1], (n_pages, page, Hkv, hd))
    vp = jax.random.normal(ks[2], (n_pages, page, Hkv, hd))
    bt = jnp.asarray(rng.integers(0, n_pages, (1, max_pages)), jnp.int32)
    tok_seq = jnp.zeros(4, jnp.int32)
    tok_pos = jnp.asarray([4, 5, 6, 7], jnp.int32)       # one chunk
    base = ragged_paged_attention(q, kp[None], vp[None], 0, bt, tok_seq,
                                  tok_pos, interpret=True)
    # clobber position 6's slot (page bt[0, 0], offset 6)
    kp2 = kp.at[bt[0, 0], 6].add(3.0)
    vp2 = vp.at[bt[0, 0], 6].add(-2.0)
    pert = ragged_paged_attention(q, kp2[None], vp2[None], 0, bt, tok_seq,
                                  tok_pos, interpret=True)
    d = np.max(np.abs(np.asarray(pert) - np.asarray(base)),
               axis=(1, 2, 3))
    assert np.all(d[:2] == 0.0), "earlier chunk tokens saw a later slot"
    assert np.all(d[2:] > 0.0), "later chunk tokens missed an earlier slot"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,L", [
    pytest.param(1, 1, id="1"), pytest.param(4, 1, id="4"),
    pytest.param(9, 1, id="9"),
    # layer 1 of a three-layer stack, padded rows to the discard page
    pytest.param(9, 3, id="9-layer1-of-3"),
])
def test_kv_append_matches_ref(dtype, N, L):
    rng = np.random.default_rng(N)
    n_pages, page, Hkv, hd = 12, 8, 2, 16
    ks = jax.random.split(KEY, 6)
    kp = jax.random.normal(ks[0], (n_pages, page, Hkv, hd)).astype(dtype)
    vp = jax.random.normal(ks[1], (n_pages, page, Hkv, hd)).astype(dtype)
    kn = jax.random.normal(ks[2], (N, Hkv, hd)).astype(dtype)
    vn = jax.random.normal(ks[3], (N, Hkv, hd)).astype(dtype)
    k_stack, layer = _layer_of_stack(kp, L, ks[4])
    v_stack, _ = _layer_of_stack(vp, L, ks[5])
    valid = jnp.asarray(rng.integers(0, 2, N), jnp.int32)
    if L == 1:
        # distinct live slots; rows randomly flagged invalid keep their
        # slot index (in interpret mode the kernel's copy-back is
        # content-preserving, matching the ref's drop semantics
        # bit-for-bit)
        slots = rng.choice(n_pages * page, N, replace=False)
        ids = jnp.asarray(slots // page, jnp.int32)
    else:
        # live slots off the last page, the write-discard page that every
        # invalid row is routed to (the engine's contract)
        slots = rng.choice((n_pages - 1) * page, N, replace=False)
        ids = jnp.where(valid != 0, jnp.asarray(slots // page, jnp.int32),
                        n_pages - 1)
    offs = jnp.asarray(slots % page, jnp.int32)
    # the kernel donates its pools: hand it copies, keep the originals
    got_k, got_v = kv_append(k_stack.copy(), v_stack.copy(), layer, kn, vn,
                             ids, offs, valid, interpret=True)
    want_k, want_v = ref.kv_append_ref(kp[None], vp[None], 0, kn, vn, ids,
                                       offs, valid)
    assert jnp.array_equal(got_k[layer], want_k[0])
    assert jnp.array_equal(got_v[layer], want_v[0])
    others = np.asarray([i for i in range(L) if i != layer], np.int32)
    assert jnp.array_equal(got_k[others], k_stack[others])
    assert jnp.array_equal(got_v[others], v_stack[others])


def test_kv_append_invalid_rows_leave_pool_untouched():
    """All-invalid append (a fully padded bucket): the pools must come back
    bit-identical even when several invalid rows alias the same slot."""
    ks = jax.random.split(KEY, 4)
    kp = jax.random.normal(ks[0], (6, 4, 2, 8))
    vp = jax.random.normal(ks[1], (6, 4, 2, 8))
    kn = jax.random.normal(ks[2], (5, 2, 8))
    vn = jax.random.normal(ks[3], (5, 2, 8))
    ids = jnp.asarray([2, 2, 2, 5, 0], jnp.int32)
    offs = jnp.asarray([1, 1, 3, 0, 0], jnp.int32)
    valid = jnp.zeros(5, jnp.int32)
    got_k, got_v = kv_append(kp[None], vp[None], 0, kn, vn, ids, offs,
                             valid, interpret=True)
    assert jnp.array_equal(got_k[0], kp) and jnp.array_equal(got_v[0], vp)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8,
                                   jnp.float8_e4m3fn, jnp.float8_e5m2])
@pytest.mark.parametrize("n_move", [1, 5, 16])
def test_swap_pack_unpack_roundtrip(dtype, n_move):
    rng = np.random.default_rng(n_move)
    pool = jnp.asarray(rng.normal(size=(32, 8, 2, 16)) * 10).astype(dtype)
    ids = jnp.asarray(rng.choice(32, n_move, replace=False), jnp.int32)
    staged = swap_pack(pool, ids, interpret=True)
    assert jnp.array_equal(staged, ref.swap_pack_ref(pool, ids))
    # overwrite, then restore: exact roundtrip (swap_unpack donates the
    # pool it is given, so it gets a copy)
    zeroed = swap_unpack(pool.copy(), jnp.zeros_like(staged), ids,
                         interpret=True)
    assert jnp.array_equal(zeroed, ref.swap_unpack_ref(
        pool, jnp.zeros_like(staged), ids))
    restored = swap_unpack(zeroed, staged, ids, interpret=True)
    assert jnp.array_equal(restored, pool)


@pytest.mark.parametrize("kv_dtype", ["int8", "float8_e4m3",
                                      "float8_e5m2"])
def test_swap_roundtrip_quantized_slab(kv_dtype):
    """A quantized pool's slab is TWO leaves — low-bit payload
    (n_pages, page, Hkv, hd) and fp32 scales (n_pages, Hkv) — packed by
    the same rank-generic kernel in one contiguous DMA. Both roundtrip
    bit-exactly (DESIGN.md §17)."""
    from repro.kernels.kv_quant import kv_quant_jnp_dtype
    rng = np.random.default_rng(3)
    qd = kv_quant_jnp_dtype(kv_dtype)
    payload = jnp.asarray(rng.normal(size=(24, 8, 2, 16)) * 5).astype(qd)
    scales = jnp.asarray(rng.uniform(0, 0.1, (24, 2)), jnp.float32)
    ids = jnp.asarray(rng.choice(24, 7, replace=False), jnp.int32)
    for pool in (payload, scales):
        staged = swap_pack(pool, ids, interpret=True)
        assert jnp.array_equal(staged, pool[ids])
        clobbered = swap_unpack(pool.copy(), jnp.zeros_like(staged), ids,
                                interpret=True)
        restored = swap_unpack(clobbered, staged, ids, interpret=True)
        assert jnp.array_equal(restored, pool)


@pytest.mark.parametrize("B,H,T,dk,dv,c", [
    (2, 2, 64, 16, 16, 16),
    (1, 4, 128, 32, 64, 32),
    (2, 1, 256, 64, 64, 128),
    (1, 2, 96, 16, 16, 32),      # non-power-of-two chunk count
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gla_scan_kernel(B, H, T, dk, dv, c, dtype):
    from repro.kernels.gla_scan import gla_scan
    from repro.models.ssm import chunked_gla
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, T, dk)).astype(dtype)
    k = jax.random.normal(ks[1], (B, H, T, dk)).astype(dtype)
    v = jax.random.normal(ks[2], (B, H, T, dv)).astype(dtype)
    la = (-jnp.abs(jax.random.normal(ks[3], (B, H, T))) * 0.2
          ).astype(jnp.float32)
    y, S = gla_scan(q, k, v, la, chunk=c, interpret=True)
    y_ref, S_ref = chunked_gla(q, k, v, la, c)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref), atol=tol)


# ---------------------------------------------------------------------------
# swap pack/unpack roundtrip property (hypothesis; skipped when absent)
# ---------------------------------------------------------------------------
if HAVE_HYPOTHESIS:
    # shapes drawn from a small fixed set so pallas interpret-mode programs
    # hit the jit cache across examples
    @settings(max_examples=15, deadline=None)
    @given(
        shape=hyp_st.sampled_from([(12, 4, 1, 8), (24, 8, 2, 16)]),
        seed=hyp_st.integers(0, 2**16 - 1),
        frac=hyp_st.floats(0.05, 1.0),
        kv_dtype=hyp_st.sampled_from([None, "int8", "float8_e4m3",
                                      "float8_e5m2"]),
    )
    def test_swap_roundtrip_property(shape, seed, frac, kv_dtype):
        """For ANY page subset: pack -> clobber -> unpack restores the pool
        bit-exactly, and pages outside the subset are never touched.
        Quantized slabs (DESIGN.md §17) carry a low-bit payload leaf plus
        an fp32 (n_pages, Hkv) scale leaf through the SAME pack/unpack —
        both must roundtrip exactly for every supported kv_dtype."""
        from repro.kernels.kv_quant import kv_quant_jnp_dtype
        rng = np.random.default_rng(seed)
        n_pages, _, Hkv, _ = shape
        n_move = max(1, int(frac * n_pages))
        payload = jnp.asarray(rng.normal(size=shape), jnp.float32)
        leaves = [payload]
        if kv_dtype is not None:
            qd = kv_quant_jnp_dtype(kv_dtype)
            leaves = [jnp.asarray(rng.normal(size=shape) * 5).astype(qd),
                      jnp.asarray(rng.uniform(0, 0.1, (n_pages, Hkv)),
                                  jnp.float32)]
        ids_np = rng.choice(n_pages, n_move, replace=False)
        ids = jnp.asarray(ids_np, jnp.int32)
        untouched = np.setdiff1d(np.arange(n_pages), ids_np)
        for pool in leaves:
            staged = swap_pack(pool, ids, interpret=True)
            assert _bits_equal(staged, pool[ids])
            clobbered = swap_unpack(pool.copy(), jnp.zeros_like(staged),
                                    ids, interpret=True)
            assert _bits_equal(clobbered[untouched], pool[untouched])
            assert _bits_equal(clobbered[ids], jnp.zeros_like(staged))
            restored = swap_unpack(clobbered, staged, ids, interpret=True)
            assert _bits_equal(restored, pool)

    def _bits_equal(a, b):
        # fp8 NaN payloads (rng bytes cast through fp8) defeat ==; compare
        # the raw storage bytes instead
        return np.array_equal(np.asarray(a).view(np.uint8),
                              np.asarray(b).view(np.uint8))
else:                                                # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_swap_roundtrip_property():
        pass


# ---------------------------------------------------------------------------
# the ragged kernel's grid and live steps (the engine's attn_* counters)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("G", [1, 2, 3, 5, 8])
def test_tile_sizes_keep_q_rows_on_sublane_tiling(G):
    """Wide heads make the VMEM budget halve T down to its floor; a q block
    short of the batch keeps T·G a multiple of 16 (bf16's sublane tiling),
    odd groups included."""
    from repro.kernels.ragged_paged_attention import tile_sizes
    T, P = tile_sizes(512, 32, G, 256, 16, 256, 2, 2)
    assert T < 512 and (T * G) % 16 == 0 and P == 8


@pytest.mark.parametrize("window", [None, 24])
def test_grid_steps_match_brute_force(window):
    """``grid_steps`` (host, from each sequence's span) equals a brute-force
    count over a toy plan: a KV block is live for a tile when some row of
    the tile sees some position in it. The device-side tile plan the kernel
    launches from gives the same count."""
    from attn_grid import brute_force_steps
    from repro.kernels.ragged_paged_attention import (_tile_plan,
                                                      grid_shape,
                                                      grid_steps,
                                                      tile_sizes)
    dims = dict(Hkv=2, G=8, hd=128, page=8, max_pages=40, q_bytes=4,
                kv_bytes=4)
    N, B = 128, 5
    spans = [(0, 29, 70), (1, 300, 1), (2, 0, 1), (3, 150, 9), (4, 319, 1)]
    grid, live = grid_steps([(p0, n) for _, p0, n in spans], N, B,
                            window=window, **dims)
    assert (grid, live) == brute_force_steps(spans, N, B, window=window,
                                             **dims)

    T, P = tile_sizes(N, dims["Hkv"], dims["G"], dims["hd"], dims["page"],
                      dims["max_pages"], 4, 4)
    nt, _ = grid_shape(N, B, T, P, dims["max_pages"])
    tok_seq, tok_pos = _span_rows(spans, N)
    key = jnp.where(tok_pos >= 0, tok_seq, B)
    meta = np.asarray(_tile_plan(key, tok_pos, B=B, T=T, P=P, page=8,
                                 window=window, n_tiles=nt)).reshape(6, nt)
    _, _, lo, hi, first, last = meta
    assert int(np.sum((hi > lo) * (last - first + 1))) == live
