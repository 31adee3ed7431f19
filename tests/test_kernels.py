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
    out = paged_attention(q, kp, vp, bt, lens, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, lens)
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
    out = paged_attention(q, kp, vp, bt, lens, interpret=True)
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
    out = paged_attention(q, kp, vp, bt, lens, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, lens)
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
    out = paged_attention(q, kp, vp, bt, lens, window=window,
                          interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, lens, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# ragged-query paged attention (the fused mixed-batch core, DESIGN.md §10)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("N,Hkv,G,hd,page,max_pages,n_pages,B", [
    (1, 2, 4, 64, 16, 8, 32, 1),     # a single decode token
    (9, 2, 4, 64, 16, 8, 32, 3),     # mixed ragged batch
    (6, 1, 8, 128, 8, 16, 64, 2),
    (5, 4, 1, 32, 32, 4, 16, 5),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_paged_attention(N, Hkv, G, hd, page, max_pages, n_pages, B,
                                dtype):
    rng = np.random.default_rng(N * 13 + page)
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (N, Hkv, G, hd)).astype(dtype)
    kp = jax.random.normal(ks[1], (n_pages, page, Hkv, hd)).astype(dtype)
    vp = jax.random.normal(ks[2], (n_pages, page, Hkv, hd)).astype(dtype)
    bt = jnp.asarray(rng.integers(0, n_pages, (B, max_pages)), jnp.int32)
    tok_seq = jnp.asarray(rng.integers(0, B, (N,)), jnp.int32)
    tok_pos = jnp.asarray(rng.integers(0, page * max_pages, (N,)), jnp.int32)
    out = ragged_paged_attention(q, kp, vp, bt, tok_seq, tok_pos,
                                 interpret=True)
    want = ref.ragged_paged_attention_ref(q, kp, vp, bt, tok_seq, tok_pos)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("window,softcap", [(7, None), (16, None),
                                            (None, 30.0), (9, 25.0)])
def test_ragged_paged_attention_window_softcap(window, softcap):
    rng = np.random.default_rng(0 if window is None else window)
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (6, 2, 2, 32))
    kp = jax.random.normal(ks[1], (16, 8, 2, 32))
    vp = jax.random.normal(ks[2], (16, 8, 2, 32))
    bt = jnp.asarray(rng.integers(0, 16, (2, 4)), jnp.int32)
    tok_seq = jnp.asarray([0, 0, 0, 1, 1, 0], jnp.int32)
    tok_pos = jnp.asarray([0, 12, 31, 7, 8, 29], jnp.int32)
    out = ragged_paged_attention(q, kp, vp, bt, tok_seq, tok_pos,
                                 window=window, softcap=softcap,
                                 interpret=True)
    want = ref.ragged_paged_attention_ref(q, kp, vp, bt, tok_seq, tok_pos,
                                          window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


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
    got = ragged_paged_attention(q, kp, vp, bt,
                                 jnp.arange(B, dtype=jnp.int32),
                                 lens - 1, interpret=True)
    want = paged_attention(q, kp, vp, bt, lens, interpret=True)
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
    base = ragged_paged_attention(q, kp, vp, bt, tok_seq, tok_pos,
                                  interpret=True)
    # clobber position 6's slot (page bt[0, 0], offset 6)
    kp2 = kp.at[bt[0, 0], 6].add(3.0)
    vp2 = vp.at[bt[0, 0], 6].add(-2.0)
    pert = ragged_paged_attention(q, kp2, vp2, bt, tok_seq, tok_pos,
                                  interpret=True)
    d = np.max(np.abs(np.asarray(pert) - np.asarray(base)),
               axis=(1, 2, 3))
    assert np.all(d[:2] == 0.0), "earlier chunk tokens saw a later slot"
    assert np.all(d[2:] > 0.0), "later chunk tokens missed an earlier slot"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N", [1, 4, 9])
def test_kv_append_matches_ref(dtype, N):
    rng = np.random.default_rng(N)
    n_pages, page, Hkv, hd = 12, 8, 2, 16
    ks = jax.random.split(KEY, 4)
    kp = jax.random.normal(ks[0], (n_pages, page, Hkv, hd)).astype(dtype)
    vp = jax.random.normal(ks[1], (n_pages, page, Hkv, hd)).astype(dtype)
    kn = jax.random.normal(ks[2], (N, Hkv, hd)).astype(dtype)
    vn = jax.random.normal(ks[3], (N, Hkv, hd)).astype(dtype)
    # distinct live slots; rows randomly flagged invalid keep their slot
    # index (in interpret mode the kernel's copy-back is content-preserving,
    # matching the ref's drop semantics bit-for-bit)
    slots = rng.choice(n_pages * page, N, replace=False)
    ids = jnp.asarray(slots // page, jnp.int32)
    offs = jnp.asarray(slots % page, jnp.int32)
    valid = jnp.asarray(rng.integers(0, 2, N), jnp.int32)
    # the kernel donates its pools: hand it copies, keep the originals
    got_k, got_v = kv_append(kp.copy(), vp.copy(), kn, vn, ids, offs, valid,
                             interpret=True)
    want_k, want_v = ref.kv_append_ref(kp, vp, kn, vn, ids, offs, valid)
    assert jnp.array_equal(got_k, want_k) and jnp.array_equal(got_v, want_v)


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
    got_k, got_v = kv_append(kp.copy(), vp.copy(), kn, vn, ids, offs, valid,
                             interpret=True)
    assert jnp.array_equal(got_k, kp) and jnp.array_equal(got_v, vp)


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
