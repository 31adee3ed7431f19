"""Quantized KV pages (DESIGN.md §17): INT8/FP8 pools with per-page scales.

Three layers of pinning:

  * kernel parity — the Pallas requantize-on-append path is bit-exact
    against the jnp reference for every supported kv_dtype, including
    duplicate pages within one call and invalid (dropped) rows; the
    dequantized pool recovers the fp32 rows within each dtype's
    precision envelope.
  * model parity — teacher-forced decode through the fused step over a
    quantized pool tracks the fp32 pool's logits within a calibrated bound
    at every matched position.
  * engine bounded-divergence harness — a quantized engine finishes the
    same workload across policies × speculation × cache × overlap with greedy
    token streams agreeing with the fp32 baseline above a per-dtype
    threshold (exact equality is impossible: the requant history is
    scheduling-order-dependent), while ``kv_dtype=None`` stays
    structurally identical to the historical pools (no scale leaves,
    same kv_token_bytes) so the existing identity tests keep pinning
    bit-identity.

Thresholds are calibrated empirically on the tiny random-init config —
its near-uniform logits AMPLIFY quantization divergence, so real
checkpoints sit far above these floors (measured values in §17).
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import POLICIES
from repro.core.costmodel import CostModel
from repro.kernels import ref
from repro.kernels.kv_quant import (KV_QUANT_DTYPES, kv_append_quant,
                                    kv_quant_jnp_dtype, kv_quant_qmax,
                                    quantize_rows)
from repro.models import LM
from repro.serving.api_executor import OracleToolResultPredictor
from repro.serving.engine import Engine
from repro.serving.workloads import make_agent_workload
from repro.utils.hw import TPU_V5E, dtype_bytes

ALL_POLICIES = ["preserve", "vllm", "swap", "infercept"]
ALL_KV_DTYPES = sorted(KV_QUANT_DTYPES)

# dequant recovery: max |dequant(q) - x| / max|x| per storage dtype
# (measured on gaussian rows: int8 0.004, e4m3 0.024, e5m2 0.066)
DEQUANT_REL_BOUND = {"int8": 0.006, "float8_e4m3": 0.05,
                     "float8_e5m2": 0.15}
# greedy-stream agreement vs the fp32 baseline on the tiny random-init
# model (measured: int8 ~0.83, e4m3 ~0.80, e5m2 ~0.79 — see DESIGN.md
# §17 for the calibration runs behind the floors)
STREAM_AGREEMENT_FLOOR = {"int8": 0.55, "float8_e4m3": 0.5,
                          "float8_e5m2": 0.45}

KEY = jax.random.PRNGKey(0)


def _rand_rows(key, n, Hkv, hd, scale=3.0):
    return jax.random.normal(key, (n, Hkv, hd), jnp.float32) * scale


def _append_round(key, qdtype, pools, *, n=6, n_pages=8, page=4, Hkv=2,
                  hd=16, discard_pid=7, layer=0, interpret=True):
    """One quantized append into layer ``layer`` of stacked pools on both
    paths; returns (pallas, ref) pools."""
    ks = jax.random.split(key, 4)
    k_new = _rand_rows(ks[0], n, Hkv, hd)
    v_new = _rand_rows(ks[1], n, Hkv, hd, scale=1.5)
    # duplicate pages within the call + one invalid row + varying offsets
    pids = jnp.asarray([0, 0, 2, 3, 2, 5][:n], jnp.int32)
    offs = jnp.asarray([0, 1, 2, 0, 3, 1][:n], jnp.int32)
    valid = jnp.asarray([1, 1, 1, 1, 1, 0][:n], jnp.int32)
    (pk, pv, pks, pvs), (rk, rv, rks, rvs) = pools
    pal = kv_append_quant(pk, pv, pks, pvs, layer, k_new, v_new, pids,
                          offs, valid, discard_pid, interpret=interpret)
    r = ref.kv_append_quant_ref(rk, rv, rks, rvs, layer, k_new, v_new, pids,
                                offs, valid)
    return pal, r


def _zero_pools(qdtype, n_pages=8, page=4, Hkv=2, hd=16, L=1, layer=0):
    """(pallas, ref) stacked pools of L layers: layer ``layer`` zeroed (an
    empty pool), every other layer holding payload and scales of its own."""
    key = jax.random.fold_in(KEY, 5)

    def leaf(shape, dtype, i):
        full = (jax.random.normal(jax.random.fold_in(key, i), (L,) + shape)
                * (0.01 if dtype == jnp.float32 else 50.0))
        full = jnp.abs(full) if dtype == jnp.float32 else full
        full = full.astype(dtype)
        return full.at[layer].set(jnp.zeros(shape, dtype))

    shapes = [((n_pages, page, Hkv, hd), qdtype)] * 2 \
        + [((n_pages, Hkv), jnp.float32)] * 2
    # separate buffers per leaf: the Pallas path donates its pools
    return tuple(tuple(leaf(sh, dt, i) for i, (sh, dt) in enumerate(shapes))
                 for _ in range(2))


def _bytes(x):
    return np.asarray(x).view(np.uint8)


@pytest.mark.parametrize("name,L", [
    pytest.param(n, 1, id=n) for n in ALL_KV_DTYPES] + [
    # layer 1 of a three-layer stack: layers 0 and 2 stay as they were
    pytest.param("int8", 3, id="int8-layer1-of-3")])
def test_kv_append_quant_pallas_matches_ref(name, L):
    """Two append rounds (the second re-quantizes already-written pages):
    the Pallas path is bit-exact against the jnp reference everywhere but
    the write-discard page; on a stack, the other layers are bit-identical
    to what they held, discard page included."""
    qdtype = kv_quant_jnp_dtype(name)
    layer = 1 if L > 1 else 0
    pal, r = _zero_pools(qdtype, L=L, layer=layer)
    before = [np.asarray(x).copy() for x in r]
    pal, r = _append_round(jax.random.fold_in(KEY, 1), qdtype, (pal, r),
                           layer=layer)
    pal, r = _append_round(jax.random.fold_in(KEY, 2), qdtype, (pal, r),
                           layer=layer)
    live = np.setdiff1d(np.arange(8), [7])      # exclude the discard page
    for got, want, label in [(pal[0], r[0], "k"), (pal[1], r[1], "v")]:
        assert np.array_equal(_bytes(got[layer])[live],
                              _bytes(want[layer])[live]), label
    assert jnp.array_equal(pal[2], r[2]) and jnp.array_equal(pal[3], r[3])
    others = np.asarray([i for i in range(L) if i != layer], np.int32)
    for got, old in zip(pal, before):
        assert np.array_equal(_bytes(got[others]), _bytes(old[others]))


@pytest.mark.parametrize("name", ALL_KV_DTYPES)
def test_kv_append_quant_dequant_recovers_rows(name):
    """Dequantizing the pool recovers the appended fp32 rows within the
    storage dtype's precision envelope (relative to the row max)."""
    qdtype = kv_quant_jnp_dtype(name)
    n_pages, page, Hkv, hd = 8, 4, 2, 16
    pk = jnp.zeros((1, n_pages, page, Hkv, hd), qdtype)
    ks = jnp.zeros((1, n_pages, Hkv), jnp.float32)
    k_new = _rand_rows(jax.random.fold_in(KEY, 3), 4, Hkv, hd)
    pids = jnp.asarray([1, 1, 2, 4], jnp.int32)
    offs = jnp.asarray([0, 1, 0, 0], jnp.int32)
    valid = jnp.ones(4, jnp.int32)
    # the K and V pools are donated, so each gets its own buffer
    pk, _, ks, _ = kv_append_quant(pk, pk.copy(), ks, ks, 0, k_new, k_new,
                                   pids, offs, valid, discard_pid=7,
                                   interpret=True)
    deq = ref.dequant_gathered(pk[0, pids], ks[0, pids])  # (4, page, ...)
    got = deq[jnp.arange(4), offs]                   # the written slots
    err = np.abs(np.asarray(got) - np.asarray(k_new)).max()
    rel = err / np.abs(np.asarray(k_new)).max()
    assert rel < DEQUANT_REL_BOUND[name], (name, rel)


def test_scale_update_is_monotone_and_requant_preserves_old_rows():
    """A later, larger row coarsens the page scale; the earlier row's
    requantized payload still dequantizes to its original value within
    the (new, coarser) quantization step."""
    qdtype = kv_quant_jnp_dtype("int8")
    n_pages, page, Hkv, hd = 4, 4, 1, 8
    pk = jnp.zeros((1, n_pages, page, Hkv, hd), qdtype)
    ks = jnp.zeros((1, n_pages, Hkv), jnp.float32)
    small = jnp.full((1, Hkv, hd), 0.5, jnp.float32)
    big = jnp.full((1, Hkv, hd), 8.0, jnp.float32)
    ids0 = jnp.zeros(1, jnp.int32)
    one = jnp.ones(1, jnp.int32)
    pk, _, ks, _ = kv_append_quant(pk, pk.copy(), ks, ks, 0, small, small,
                                   ids0, 0 * one, one, discard_pid=3,
                                   interpret=True)
    s0 = float(ks[0, 0, 0])
    pk, _, ks, _ = kv_append_quant(pk, pk.copy(), ks, ks, 0, big, big, ids0,
                                   1 * one, one, discard_pid=3,
                                   interpret=True)
    s1 = float(ks[0, 0, 0])
    assert s1 > s0 > 0.0                      # monotone while alive
    deq = float(pk[0, 0, 0, 0, 0]) * s1
    assert abs(deq - 0.5) <= s1               # within one coarse step
    assert abs(float(pk[0, 0, 1, 0, 0]) * s1 - 8.0) <= s1


@pytest.mark.parametrize("name", ALL_KV_DTYPES)
def test_quant_paged_attention_matches_ref(name):
    """Scale-aware attention at a decode-only launch (one token per
    sequence, at its context length minus one): Pallas vs the
    dequantize-then-attend reference, and both near the fp32 attention
    over the pre-quant pool."""
    from repro.kernels.ops import ragged_paged_attention_op
    qdtype = kv_quant_jnp_dtype(name)
    B, Hkv, G, hd, page, n_pages, max_pages = 2, 2, 2, 16, 4, 16, 3
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Hkv, G, hd), jnp.float32)
    kf = jax.random.normal(ks[1], (n_pages, page, Hkv, hd), jnp.float32)
    vf = jax.random.normal(ks[2], (n_pages, page, Hkv, hd), jnp.float32)
    scale = jnp.max(jnp.abs(kf), axis=(1, 3)) / kv_quant_qmax(qdtype)
    kq = quantize_rows(kf, scale[:, None], qdtype)
    vscale = jnp.max(jnp.abs(vf), axis=(1, 3)) / kv_quant_qmax(qdtype)
    vq = quantize_rows(vf, vscale[:, None], qdtype)
    bt = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
    ctx = jnp.asarray([7, 12], jnp.int32)
    tok_seq, tok_pos = jnp.arange(B, dtype=jnp.int32), ctx - 1
    pal = ragged_paged_attention_op(q, kq[None], vq[None], 0, bt, tok_seq,
                                    tok_pos, k_scale=scale[None],
                                    v_scale=vscale[None], use_pallas=True,
                                    interpret=True)
    rf = ref.ragged_paged_attention_quant_ref(
        q, kq[None], vq[None], scale[None], vscale[None], 0, bt, tok_seq,
        tok_pos, softcap=None, scale=None, window=None)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(rf), atol=1e-5)
    exact = ref.ragged_paged_attention_ref(q, kf[None], vf[None], 0, bt,
                                           tok_seq, tok_pos, softcap=None,
                                           scale=None, window=None)
    tol = {"int8": 0.05, "float8_e4m3": 0.2, "float8_e5m2": 0.5}[name]
    np.testing.assert_allclose(np.asarray(pal), np.asarray(exact),
                               atol=tol)


def test_ragged_quant_attention_matches_ref():
    from repro.kernels.ops import ragged_paged_attention_op
    qdtype = kv_quant_jnp_dtype("int8")
    N, Hkv, G, hd, page, n_pages, max_pages = 5, 2, 2, 16, 4, 16, 3
    ks = jax.random.split(jax.random.fold_in(KEY, 9), 4)
    q = jax.random.normal(ks[0], (N, Hkv, G, hd), jnp.float32)
    kf = jax.random.normal(ks[1], (n_pages, page, Hkv, hd), jnp.float32)
    vf = jax.random.normal(ks[2], (n_pages, page, Hkv, hd), jnp.float32)
    kscale = jnp.max(jnp.abs(kf), axis=(1, 3)) / kv_quant_qmax(qdtype)
    vscale = jnp.max(jnp.abs(vf), axis=(1, 3)) / kv_quant_qmax(qdtype)
    kq = quantize_rows(kf, kscale[:, None], qdtype)
    vq = quantize_rows(vf, vscale[:, None], qdtype)
    bt = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
    tok_seq = jnp.asarray([0, 0, 0, 1, 1], jnp.int32)
    tok_pos = jnp.asarray([4, 5, 6, 0, 1], jnp.int32)
    pal = ragged_paged_attention_op(q, kq[None], vq[None], 0, bt, tok_seq,
                                    tok_pos, k_scale=kscale[None],
                                    v_scale=vscale[None], use_pallas=True,
                                    interpret=True)
    rf = ref.ragged_paged_attention_quant_ref(
        q, kq[None], vq[None], kscale[None], vscale[None], 0, bt, tok_seq,
        tok_pos, softcap=None, scale=None, window=None)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(rf), atol=1e-5)


def _stack_at_1(x, key):
    """A three-layer stack whose layer 1 is ``x``; the others hold other
    values of its dtype (scales stay positive)."""
    scale = 0.01 if x.dtype == jnp.float32 else 50.0
    other = jnp.abs(jax.random.normal(key, (3,) + x.shape)) * scale
    return other.astype(x.dtype).at[1].set(x)


@pytest.mark.parametrize("name,G,L", [
    pytest.param("int8", 4, 1, id="int8"),
    pytest.param("float8_e4m3", 4, 1, id="float8_e4m3"),
    # layer 1 of a three-layer stack, G 1 and G 8
    pytest.param("int8", 1, 3, id="int8-G1-layer1-of-3"),
    pytest.param("int8", 8, 3, id="int8-G8-layer1-of-3"),
])
def test_ragged_quant_attention_tiled(name, G, L):
    """The tiled grid over quantized pools: a chunk over several query
    tiles (GQA 4: 32 rows a tile; G 1 and 8 on a stack) from mid-page,
    decodes (one at full context), padded rows; per-page per-head scales
    ride each page's block. On a stack of three layers, layer 1 reads
    bit-equal to the kernel on that layer's pool and scales alone."""
    from repro.kernels.ops import ragged_paged_attention_op
    qdtype = kv_quant_jnp_dtype(name)
    N, Hkv, hd, page, n_pages, max_pages = 96, 2, 16, 8, 64, 16
    ks = jax.random.split(jax.random.fold_in(KEY, 11), 3)
    q = jax.random.normal(ks[0], (N, Hkv, G, hd), jnp.float32)
    kf = jax.random.normal(ks[1], (n_pages, page, Hkv, hd), jnp.float32)
    vf = jax.random.normal(ks[2], (n_pages, page, Hkv, hd), jnp.float32)
    kscale = jnp.max(jnp.abs(kf), axis=(1, 3)) / kv_quant_qmax(qdtype)
    vscale = jnp.max(jnp.abs(vf), axis=(1, 3)) / kv_quant_qmax(qdtype)
    kq = quantize_rows(kf, kscale[:, None], qdtype)
    vq = quantize_rows(vf, vscale[:, None], qdtype)
    bt = jnp.asarray(np.random.default_rng(4).permutation(n_pages)[:3 * 16]
                     .reshape(3, max_pages), jnp.int32)
    seq = [1] * 70 + [0, 2]
    pos = list(range(11, 81)) + [max_pages * page - 1, 40]
    tok_seq = jnp.asarray(seq + [0] * (N - 72), jnp.int32)
    tok_pos = jnp.asarray(pos + [-1] * (N - 72), jnp.int32)
    alone = (kq[None], vq[None], kscale[None], vscale[None])
    if L == 1:
        (k_st, v_st, ks_st, vs_st), layer = alone, 0
    else:
        sk = jax.random.split(ks[2], 4)
        k_st, v_st, ks_st, vs_st = (_stack_at_1(x, k) for x, k in
                                    zip((kq, vq, kscale, vscale), sk))
        layer = 1
    pal = ragged_paged_attention_op(q, k_st, v_st, layer, bt, tok_seq,
                                    tok_pos, k_scale=ks_st, v_scale=vs_st,
                                    use_pallas=True, interpret=True)
    rf = ref.ragged_paged_attention_quant_ref(
        q, k_st, v_st, ks_st, vs_st, layer, bt, tok_seq, tok_pos,
        softcap=None, scale=None, window=None)
    np.testing.assert_allclose(np.asarray(pal)[:72], np.asarray(rf)[:72],
                               atol=1e-5)
    assert not np.asarray(pal)[72:].any()
    if L > 1:
        got = ragged_paged_attention_op(
            q, alone[0], alone[1], 0, bt, tok_seq, tok_pos,
            k_scale=alone[2], v_scale=alone[3], use_pallas=True,
            interpret=True)
        assert jnp.array_equal(pal, got)


# ---------------------------------------------------------------------------
# structural pins: init_cache, engine validation, byte accounting
# ---------------------------------------------------------------------------

def test_init_cache_quantized_shapes_and_none_is_unchanged():
    cfg = get_config("llama3.2-1b", tiny=True)
    lm = LM(cfg)
    base = lm.init_cache(8, 16)
    quant = lm.init_cache(8, 16, kv_dtype="int8")
    base_leaves = {id(x) for x in jax.tree.leaves(base)}
    del base_leaves
    for entry_b, entry_q in zip(base, quant):
        for bk in entry_b:
            pb, pq = entry_b[bk], entry_q[bk]
            if isinstance(pb, dict) and "k" in pb and pb["k"].ndim == 5:
                assert set(pq) == {"k", "v", "k_scale", "v_scale"}
                assert pq["k"].dtype == jnp.int8
                assert pq["k_scale"].dtype == jnp.float32
                # (n_periods, n_pages, Hkv): one scale per page per head
                assert pq["k_scale"].shape == (
                    pb["k"].shape[0], pb["k"].shape[1], pb["k"].shape[3])
                # kv_dtype=None never grows scale leaves (bit-identity)
                assert "k_scale" not in pb


def test_engine_kv_dtype_validation():
    cfg = get_config("llama3.2-1b", tiny=True)
    with pytest.raises(ValueError, match="unsupported kv_dtype"):
        Engine(cfg, POLICIES["infercept"], page_size=16, n_pages=32,
               max_model_len=64, kv_dtype="int4")


def test_costmodel_kv_dtype_shifts_m_bytes():
    cfg = get_config("gpt-j-6b")
    base = CostModel(cfg=cfg, chip=TPU_V5E, n_chips=1)        # bf16 KV
    q8 = CostModel(cfg=cfg, chip=TPU_V5E, n_chips=1, kv_dtype="int8")
    f8 = CostModel(cfg=cfg, chip=TPU_V5E, n_chips=1,
                   kv_dtype="float8_e4m3")
    assert base.m_bytes == 2 * q8.m_bytes == 2 * f8.m_bytes
    # Eq. 5 pivots follow M: swap budgets double, capacity doubles
    assert q8.swap_tokens_within(0.01) == 2 * base.swap_tokens_within(0.01)
    assert q8.kv_capacity_tokens() >= 2 * base.kv_capacity_tokens()
    assert q8.t_swap(1000) * 2 == pytest.approx(base.t_swap(1000))


# ---------------------------------------------------------------------------
# engine bounded-divergence harness
# ---------------------------------------------------------------------------

def _workload(cfg):
    return make_agent_workload(
        seed=5, n_sessions=2, rate_rps=2.0, vocab=cfg.vocab_size,
        n_templates=2, system_prompt_len=50, turns=(2, 2), turn_gap_s=3.0,
        hist_per_turn=12, prefix_share=0.75, gen_tokens=(8, 3),
        final_gen=(8, 3), ret_tokens=(6, 2), max_tool_calls=2, max_ctx=240)


def _run(cfg, reqs, policy, **kw):
    eng = Engine(cfg, POLICIES[policy], page_size=16, n_pages=128,
                 max_model_len=256, seed=0, **kw)
    for r in copy.deepcopy(reqs):
        eng.add_request(r)
    fin = eng.run()
    assert len(fin) == len(reqs), (policy, kw)
    return {r.rid: eng.generated_text(r) for r in fin}, eng


def _agreement(streams, baseline):
    """Positionwise greedy-token agreement at matched (rid, position)."""
    num = den = 0
    for rid, s in streams.items():
        b = baseline[rid]
        n = min(len(s), len(b))
        num += sum(1 for i in range(n) if s[i] == b[i])
        den += max(len(s), len(b))
    return num / max(1, den)


@pytest.fixture(scope="module")
def quant_diff():
    cfg = get_config("llama3.2-1b", tiny=True)
    reqs = _workload(cfg)
    baseline, _ = _run(cfg, reqs, "infercept", prefix_cache=True)
    return cfg, reqs, baseline


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_quantized_streams_bounded_divergence(quant_diff, policy):
    """INT8 pools across every policy: all sessions finish, the sanitizer
    stays silent, and greedy streams agree with the fp32 baseline above
    the calibrated floor."""
    cfg, reqs, baseline = quant_diff
    streams, eng = _run(cfg, reqs, policy, prefix_cache=True,
                        kv_dtype="int8", sanitize=True)
    eng.sanitizer.audit("final")
    assert eng.sanitizer.findings == [], \
        [str(f) for f in eng.sanitizer.findings]
    rate = _agreement(streams, baseline)
    assert rate >= STREAM_AGREEMENT_FLOOR["int8"], (policy, rate)


@pytest.mark.slow
@pytest.mark.parametrize("name", ["float8_e4m3", "float8_e5m2"])
def test_fp8_streams_bounded_divergence(quant_diff, name):
    cfg, reqs, baseline = quant_diff
    streams, eng = _run(cfg, reqs, "infercept", prefix_cache=True,
                        kv_dtype=name, sanitize=True)
    eng.sanitizer.audit("final")
    assert eng.sanitizer.findings == []
    rate = _agreement(streams, baseline)
    assert rate >= STREAM_AGREEMENT_FLOOR[name], (name, rate)


@pytest.mark.slow
@pytest.mark.parametrize("spec,cache,overlap", [
    (True, True, True), (False, False, True), (False, True, False),
    (True, False, False),
])
def test_quantized_toggle_corners(quant_diff, spec, cache, overlap):
    """The speculative, cache-off, and serial corners hold the same floor
    — quantization composes with every execution toggle, speculative
    forks' COW-copied scales included."""
    cfg, reqs, baseline = quant_diff
    kw = dict(speculate=True,
              predictor=OracleToolResultPredictor(cfg.vocab_size)) \
        if spec else {}
    streams, eng = _run(cfg, reqs, "infercept", prefix_cache=cache,
                        overlap=overlap, kv_dtype="int8", sanitize=True,
                        **kw)
    eng.sanitizer.audit("final")
    assert eng.sanitizer.findings == []
    if spec:
        assert eng.counters["spec_accepted"] > 0
    rate = _agreement(streams, baseline)
    assert rate >= STREAM_AGREEMENT_FLOOR["int8"], \
        (spec, cache, overlap, rate)


def test_quantized_engine_halves_kv_bytes(quant_diff):
    """The headline capacity claim: physical bytes/resident-token drop
    >= 2x vs the fp32 pools (scale leaves priced in), and swap slabs
    shrink by the same factor (swap_bytes follows kv_token_bytes)."""
    cfg, reqs, _ = quant_diff
    base = Engine(cfg, POLICIES["infercept"], page_size=16, n_pages=32,
                  max_model_len=256)
    q8 = Engine(cfg, POLICIES["infercept"], page_size=16, n_pages=32,
                max_model_len=256, kv_dtype="int8")
    assert 2 * q8.kv_token_bytes <= base.kv_token_bytes
    # per-page slab bytes as the SwapStager stages them
    slab = lambda eng: sum(  # noqa: E731
        int(leaf.nbytes) // leaf.shape[1]
        for leaf in jax.tree.leaves(eng.pools))
    assert 2 * slab(q8) <= slab(base)


def test_quant_counters_fire(quant_diff):
    cfg, reqs, _ = quant_diff
    _, eng = _run(cfg, reqs, "infercept", prefix_cache=True,
                  kv_dtype="int8")
    assert eng.counters["kv_quant_scale_reset_pages"] > 0
    # scales travel with COW forks (prefix-cache mid-page divergence)
    assert eng.counters["kv_quant_scale_cow_pages"] >= 0


# ---------------------------------------------------------------------------
# model-level: teacher-forced logit error at matched positions
# ---------------------------------------------------------------------------

def test_teacher_forced_paged_decode_logit_error_bounded():
    """Same token fed at every step (no sampling feedback), one token per
    sequence through the fused step: the quantized pool's logits stay
    within a calibrated bound of the fp32 pool's at every matched
    position."""
    cfg = get_config("llama3.2-1b", tiny=True)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    n_pages, page, B, T = 16, 4, 2, 10
    bt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    pools_f = lm.init_cache(n_pages, page)
    pools_q = lm.init_cache(n_pages, page, kv_dtype="int8")
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    worst = 0.0
    seq = jnp.arange(B, dtype=jnp.int32)
    # page 0 is in no block table: the write-discard page
    step = jax.jit(lambda tok, pos, pools: lm.forward_mixed_paged(
        params, tok, seq, pos, seq, pools, bt, discard_pid=0)[1:])
    for t in range(T):
        pos = jnp.full((B,), t, jnp.int32)
        lf, pools_f = step(toks[:, t], pos, pools_f)
        lq, pools_q = step(toks[:, t], pos, pools_q)
        err = float(jnp.max(jnp.abs(lf - lq)))
        spread = float(jnp.max(lf) - jnp.min(lf))
        worst = max(worst, err / max(spread, 1e-6))
    # Error analysis. Requantize-on-append keeps every stored K/V element
    # within one quantization step s = amax/127 of its true value (0.5 s
    # from the first rounding, 0.5 s from one requant when the page scale
    # grows). The logit error then follows how hard attention is: scores
    # q.k/sqrt(hd) of O(1) move by O(|q| s), and softmax stays smooth. It
    # read 0.136 here while the projections were initialised with the
    # fan-in of the head axis instead of d_model: q and k came out
    # sqrt(d_model / heads) too large (6x and 11x here), scores ~60x too
    # large made attention a near-argmax, and an int8 step flipped which
    # slot won. With the
    # fan-in fixed the worst ratio is ~0.01 (bf16 weights); the bound
    # leaves 10x headroom.
    assert worst < 0.12, worst
