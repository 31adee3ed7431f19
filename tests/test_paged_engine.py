"""The served step against the plain reference (DESIGN.md §9 / §10).

The engine runs the model one way: the fused step (LM.forward_mixed_paged
— every chunk and decode of an iteration in one dispatch over the paged
pools, sampling on device). Its reference is the plain contiguous
``LM.forward``, independent of paging, scheduling and the prefix cache:
every run of the differential fixture — each scheduling policy, prefix
cache on and off — is teacher-forced through it, and every generated
token must be the reference's best within float tolerance. The runs must
also agree with each other bit for bit, report exactly one device
dispatch per non-empty iteration, and move O(B) int32 ids across the
sampling boundary.
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import POLICIES
from repro.models import LM
from repro.serving.engine import Engine
from repro.serving.workloads import make_agent_workload

ALL_POLICIES = ["preserve", "vllm", "swap", "infercept"]


def _agent_workload(cfg, n_sessions=2):
    # mid-page prefix divergence (system prompt 50 vs page 16) so the paged
    # path also exercises COW-tail forks of cached pages
    return make_agent_workload(
        seed=5, n_sessions=n_sessions, rate_rps=2.0, vocab=cfg.vocab_size,
        n_templates=2, system_prompt_len=50, turns=(2, 2), turn_gap_s=3.0,
        hist_per_turn=12, prefix_share=0.75, gen_tokens=(8, 3),
        final_gen=(8, 3), ret_tokens=(6, 2), max_tool_calls=2, max_ctx=240)


def _run(cfg, reqs, policy, *, prefix_cache=False, overlap=True):
    eng = Engine(cfg, POLICIES[policy], page_size=16, n_pages=128,
                 max_model_len=256, seed=0, prefix_cache=prefix_cache,
                 overlap=overlap)
    for r in copy.deepcopy(reqs):
        eng.add_request(r)
    fin = eng.run()
    assert len(fin) == len(reqs), (policy, prefix_cache, overlap)
    return {r.rid: eng.generated_text(r) for r in fin}, eng


# the served token's reference logit may trail the reference's best by at
# most this much (float32 rounding between the fused step and LM.forward;
# the logits spread over ~8 units on the tiny model)
REF_TOL = 1e-3


def _generated_positions(req):
    """Positions of the tokens the model generated in a finished request's
    token list, and the list's length: after the prompt, each segment's
    ``gen_tokens`` generated tokens, then its interception's
    ``returned_tokens`` tool tokens, which the model did not generate."""
    pos, gen = req.prompt_len, []
    for seg in req.segments:
        gen.extend(range(pos, pos + seg.gen_tokens))
        pos += seg.gen_tokens
        if seg.interception is not None:
            pos += seg.interception.returned_tokens
    return gen, pos


def _plain_reference(cfg, params, max_len):
    """The plain reference's verdict on finished streams: ``gaps(req,
    tokens)`` teacher-forces the contiguous LM.forward over the request's
    whole token list and returns, for every generated position, how far
    the served token's logit trails the reference's best there."""
    model = LM(cfg)
    logits_of = jax.jit(
        lambda p, x: model.logits(p, model.forward(p, x).hidden)[0])

    def gaps(req, tokens):
        gen, end = _generated_positions(req)
        assert end == len(tokens), (req.rid, end, len(tokens))
        x = np.zeros((1, max_len), np.int32)      # causal: tail is inert
        x[0, :len(tokens)] = tokens
        lg = np.asarray(logits_of(params, jnp.asarray(x)))
        return {p: float(lg[p - 1].max() - lg[p - 1][tokens[p]])
                for p in gen}
    return gaps


@pytest.fixture(scope="module")
def diff():
    cfg = get_config("llama3.2-1b", tiny=True)
    reqs = _agent_workload(cfg)
    runs = {(name, cache_on): _run(cfg, reqs, name, prefix_cache=cache_on)
            for name in ALL_POLICIES for cache_on in (False, True)}
    eng = runs[("vllm", False)][1]
    return cfg, runs, _plain_reference(cfg, eng.params, eng.max_model_len)


@pytest.mark.slow
def test_fused_streams_match_plain_reference(diff):
    """The headline property: every fused run — any policy, cache on or
    off — generated, at each generated position, the token the plain
    contiguous forward ranks best there."""
    _, runs, gaps = diff
    for key, (streams, eng) in runs.items():
        for req in eng.finished:
            bad = {p: g for p, g in gaps(req, streams[req.rid]).items()
                   if g > REF_TOL}
            assert not bad, f"fused {key} rid {req.rid} off the reference " \
                f"at {bad}"


def test_plain_reference_rejects_a_changed_token(diff):
    """The reference check can fail: one generated token changed in a
    served stream is flagged at exactly its position."""
    cfg, runs, gaps = diff
    streams, eng = runs[("vllm", False)]
    req = eng.finished[0]
    gen, _ = _generated_positions(req)
    p = gen[len(gen) // 2]
    tokens = list(streams[req.rid])
    assert max(gaps(req, tokens).values()) <= REF_TOL
    tokens[p] = (tokens[p] + 1) % cfg.vocab_size
    got = gaps(req, tokens)
    assert got[p] > REF_TOL
    assert all(g <= REF_TOL for q, g in got.items() if q < p)


def test_fused_streams_identical_across_policies(diff):
    """Scheduling decides when tokens are computed, never which: every
    policy, prefix cache on and off, emits the same streams bit for
    bit."""
    _, runs, _ = diff
    want = runs[("vllm", False)][0]
    for key, (streams, _) in runs.items():
        assert streams == want, f"fused {key} diverged from vllm"


def test_fused_single_dispatch_per_iteration(diff):
    """Every non-empty iteration is exactly ONE jitted model call."""
    _, runs, _ = diff
    for key, (_, eng) in runs.items():
        assert eng.counters["mixed_iterations"] > 0
        assert eng.counters["device_dispatches"] == \
            eng.counters["mixed_iterations"], key


def test_fused_transfers_ids_not_logits(diff):
    """On-device sampling boundary: a run moves at most bucket(B) * 4
    bytes of sampled int32 ids per iteration device->host, never the
    B x vocab float logits."""
    _, runs, _ = diff
    for key, (_, eng) in runs.items():
        b_pad = Engine._bucket(len(eng.finished))      # max batch bound
        assert eng.counters["logit_bytes"] <= \
            4 * b_pad * eng.counters["mixed_iterations"], key


def test_paged_mechanisms_actually_exercised(diff):
    """The checks above must not be vacuous: recompute, swap, and cache
    hits all really happened."""
    _, runs, _ = diff
    assert runs[("vllm", False)][1].sched.stats.recompute_tokens > 0
    swap_eng = runs[("swap", False)][1]
    assert swap_eng.sched.stats.swapped_out_tokens > 0
    assert (swap_eng.sched.stats.swapped_in_tokens
            == swap_eng.sched.stats.swapped_out_tokens)
    assert runs[("vllm", True)][1].sched.stats.cache_hit_tokens > 0


def test_no_page_leaks_on_paged_path(diff):
    _, runs, _ = diff
    for key, (_, eng) in runs.items():
        held = eng.cache.n_pages if eng.cache is not None else 0
        assert eng.blocks.num_free == \
            eng.blocks.n_pages - 1 - held, key


def test_paged_decode_moves_o1_bytes_per_token(diff):
    """The measurable form of the §9 claim: the fused step writes exactly
    one token's K/V per generated (and per prefilled) token — O(1), never
    a copy of the block-table view."""
    _, runs, _ = diff
    for key in [("vllm", False), ("infercept", True)]:
        eng = runs[key][1]
        assert eng.counters["decode_tokens"] > 0
        assert eng.counters["decode_bytes"] == \
            eng.counters["decode_tokens"] * eng.kv_token_bytes, key
        assert eng.kv_bytes_per_decode_token() == eng.kv_token_bytes
        assert eng.counters["prefill_bytes"] == \
            eng.counters["prefill_tokens"] * eng.kv_token_bytes, key


def test_engine_refuses_unfused():
    """The fused step is the only execution path: asking for another is
    an error, not a silent switch."""
    cfg = get_config("llama3.2-1b", tiny=True)
    with pytest.raises(ValueError, match="fused"):
        Engine(cfg, POLICIES["vllm"], page_size=16, n_pages=32,
               max_model_len=64, fused=False)


# ---------------------------------------------------------------------------
# pipelined step: overlap-on vs overlap-off (DESIGN.md §12)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_overlap_off_streams_match_across_policies(diff):
    """The §12 differential pin: the serial engine (overlap=False,
    execute-then-sync) emits the exact token streams of the pipelined
    default across all 4 policies × prefix-cache on/off — commit-phase
    reconciliation keeps every host-visible state transition in the
    serial order. Serial runs must also charge zero overlap counters
    (nothing was hidden), while the fixture's pipelined runs hide real
    swap DMA on the swap-traffic policies."""
    cfg, runs, _ = diff
    for name in ALL_POLICIES:
        for cache_on in (False, True):
            streams, eng = _run(cfg, _agent_workload(cfg), name,
                                prefix_cache=cache_on, overlap=False)
            assert streams == runs[(name, cache_on)][0], \
                f"serial {(name, cache_on)} diverged from the pipelined " \
                "streams"
            assert eng.counters["swap_overlap_bytes"] == 0
            assert eng.counters["pipeline_bubbles"] == 0
    # the pipelined runs really hid swap DMA under the model window
    for key in [("swap", False), ("infercept", False)]:
        pipe = runs[key][1]
        assert pipe.sched.stats.swapped_out_tokens > 0, key
        assert pipe.counters["swap_overlap_bytes"] > 0, key
        assert pipe.counters["swap_overlap_bytes"] <= \
            (pipe.sched.stats.swapped_out_tokens
             + pipe.sched.stats.swapped_in_tokens) * pipe.cost.m_bytes, key


def test_swap_stager_spills_to_bound_device_staging():
    """SwapStager unit contract: no more than ``depth`` slabs hold device
    staging at once — packing beyond it spills the oldest host-side — and
    every ticket collects the exact gathered payload regardless of spill
    order."""
    from repro.kernels.swap_pack import SwapStager
    pools = jnp.arange(2 * 8 * 4, dtype=jnp.float32).reshape(2, 8, 4)
    stager = SwapStager(depth=2)
    ids = [[0, 3], [1], [5, 6], [7]]
    tickets = [stager.pack(pools, pg) for pg in ids]
    resident = sum(1 for s in stager._inflight if s.arrays is not None)
    assert resident <= 2                      # oldest slabs were spilled
    assert stager.inflight == 4               # but none were lost
    # collect out of order: spilled and device-resident alike are exact
    for t, pg in sorted(zip(tickets, ids), key=lambda x: -x[0]):
        got = stager.collect(t)
        np.testing.assert_array_equal(got, np.asarray(pools)[:, pg])
    assert stager.inflight == 0
    assert stager.packed_pages == stager.collected_pages == 6


def test_overlap_uses_double_buffered_stager(diff):
    """Pipelined swap-out really routes through the SwapStager: every
    packed page is eventually collected (no slab leaks), and staging never
    holds more than its double-buffer depth."""
    _, runs, _ = diff
    eng = runs[("swap", False)][1]
    assert eng.stager.packed_pages > 0
    assert eng.stager.packed_pages == eng.stager.collected_pages
    assert eng.stager.inflight == 0
    assert eng.stager.unpacked_pages > 0      # swap-in scatters staged too


# ---------------------------------------------------------------------------
# swap-in under physical-page exhaustion: requeue, never crash
# ---------------------------------------------------------------------------
def test_swap_in_page_exhaustion_requeues_instead_of_crashing():
    """Regression for the hard RuntimeError('out of KV pages during
    swap-in'): when the physical pool cannot back a planned swap-in, the
    request is re-preempted (host payload dropped into recompute debt,
    requeued FCFS) and the engine keeps serving; once memory frees up the
    request recomputes and finishes with the exact stream an undisturbed
    engine produces."""
    from repro.core.request import Interception, Request, Segment

    cfg = get_config("llama3.2-1b", tiny=True)

    def make_reqs():
        return [Request(
            rid=0, arrival=0.0, prompt_len=48,
            segments=[Segment(gen_tokens=4, interception=Interception(
                kind="math", duration=5.0, returned_tokens=4)),
                Segment(gen_tokens=4, interception=None)])]

    def build():
        eng = Engine(cfg, POLICIES["swap"], page_size=16, n_pages=48,
                     max_model_len=128, seed=0)
        for r in make_reqs():
            eng.add_request(r)
        return eng

    # undisturbed oracle
    ref = build()
    fin = ref.run()
    assert len(fin) == 1
    oracle = ref.generated_text(fin[0])

    eng = build()
    # drive until the interception swapped the context out (the request is
    # paused with host-resident pages; the swap-in fires inside the step
    # that processes its resume)
    for _ in range(10_000):
        if any(r.host_tokens > 0 for r in eng.sched.paused):
            break
        assert eng.step()
    victims = [r for r in eng.sched.paused if r.host_tokens > 0]
    assert victims, "interception never swapped the context out"
    victim = victims[0]

    # exhaust the physical pool while the tool call is in flight, so the
    # resume step's planned swap-in cannot be backed
    hoard = eng.blocks.allocate(eng.blocks.num_free)
    assert hoard is not None
    for _ in range(10_000):                 # must NOT raise
        if eng.sched.stats.swap_in_failures:
            break
        assert eng.step()
    assert eng.sched.stats.swap_in_failures == 1
    from repro.core.request import Phase
    assert victim.phase == Phase.WAITING
    assert victim.host_tokens == 0 and victim.device_tokens == 0
    assert victim.to_compute == victim.target_ctx   # full recompute debt
    assert eng.kv[victim.rid].pages == []
    assert victim not in eng.sched.swap_queue

    # free the hoarded pages: the request recomputes and finishes with the
    # undisturbed engine's exact stream
    eng.blocks.free(hoard)
    fin = eng.run()
    assert fin.drained and len(fin) == 1
    assert eng.generated_text(fin[0]) == oracle
    # no page leaks after the failure/recompute cycle either
    assert eng.blocks.num_free == eng.blocks.n_pages - 1


# ---------------------------------------------------------------------------
# fused dispatch density under genuinely mixed iterations
# ---------------------------------------------------------------------------
def test_fused_one_dispatch_on_concurrent_prefill_and_decode():
    """A near-simultaneous burst forces iterations that carry prefill
    chunks AND a decode batch at once: each is still exactly one
    dispatch with an O(B)-ids logit transfer, and every stream is the
    plain reference's."""
    cfg = get_config("llama3.2-1b", tiny=True)
    reqs = make_agent_workload(
        seed=7, n_sessions=4, rate_rps=500.0, vocab=cfg.vocab_size,
        n_templates=2, system_prompt_len=50, turns=(2, 2), turn_gap_s=0.01,
        hist_per_turn=12, prefix_share=0.75, gen_tokens=(10, 3),
        final_gen=(10, 3), ret_tokens=(6, 2), max_tool_calls=2, max_ctx=240)
    eng = Engine(cfg, POLICIES["vllm"], page_size=16, n_pages=256,
                 max_model_len=256, seed=0)
    mixed = []
    real = eng._dispatch_mixed

    def watched(plan):
        out = real(plan)
        if out is not None:
            kinds = {is_chunk for *_, is_chunk in out[0]}
            mixed.append(kinds == {True, False})
        return out

    eng._dispatch_mixed = watched
    for r in copy.deepcopy(reqs):
        eng.add_request(r)
    fin = eng.run()
    assert len(fin) == len(reqs)
    # the scenario is real: some iteration ran chunk(s) + decode together
    assert any(mixed), "no mixed iteration occurred"
    assert eng.counters["device_dispatches"] == \
        eng.counters["mixed_iterations"] == len(mixed)
    assert eng.counters["logit_bytes"] <= \
        4 * Engine._bucket(len(reqs)) * eng.counters["mixed_iterations"]
    gaps = _plain_reference(cfg, eng.params, eng.max_model_len)
    for req in fin:
        assert max(gaps(req, eng.generated_text(req)).values()) <= REF_TOL


# ---------------------------------------------------------------------------
# engine intake / allocation satellites
# ---------------------------------------------------------------------------
def test_add_request_keeps_arrival_order_stable():
    """Out-of-order submission must admit by arrival time, FIFO among
    ties (the bisect.insort intake: descending list, tail pops first)."""
    from repro.core.request import Request, Segment
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = Engine(cfg, POLICIES["vllm"], page_size=16, n_pages=32,
                 max_model_len=64)
    arrivals = [3.0, 1.0, 2.0, 1.0, 0.5, 2.0]
    for i, t in enumerate(arrivals):
        eng.add_request(Request(
            rid=i, arrival=t, prompt_len=4,
            segments=[Segment(gen_tokens=1, interception=None)]))
    admit_order = [(r.arrival, r.rid)
                   for r in reversed(eng._pending_arrivals)]
    assert admit_order == [(0.5, 4), (1.0, 1), (1.0, 3), (2.0, 2),
                           (2.0, 5), (3.0, 0)]
    eng.now = 10.0
    eng._admit()
    assert not eng._pending_arrivals and len(eng.kv) == len(arrivals)


def test_ensure_pages_allocates_shortfall_in_one_call(monkeypatch):
    """A multi-page shortfall triggers exactly ONE allocator round trip
    (one potential eviction pass), not one per page."""
    from repro.serving.engine import ReqKV
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = Engine(cfg, POLICIES["vllm"], page_size=16, n_pages=32,
                 max_model_len=256)
    calls = []
    orig = eng._allocate_pages
    monkeypatch.setattr(eng, "_allocate_pages",
                        lambda n: calls.append(n) or orig(n))
    st = ReqKV(tokens=[], pages=[])
    eng._ensure_pages(st, 5 * eng.page)
    assert calls == [5] and len(st.pages) == 5
    eng._ensure_pages(st, 5 * eng.page)            # no shortfall: no call
    assert calls == [5]
    eng._ensure_pages(st, 7 * eng.page - 1)
    assert calls == [5, 2] and len(st.pages) == 7


# ---------------------------------------------------------------------------
# pad rows must never corrupt live pages
# ---------------------------------------------------------------------------
def test_paged_decode_pad_rows_never_touch_pages():
    """A decode-only fused batch whose two padded rows deliberately alias
    the same block-table page: with masked appends neither may write
    anywhere — every pool slot except the two live targets keeps its
    sentinel."""
    cfg = get_config("llama3.2-1b", tiny=True)
    m = LM(cfg)
    params = m.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    page, n_pages, max_pages = 8, 12, 4
    pools = m.init_cache(n_pages, page, dtype=jnp.float32)
    pools = jax.tree.map(lambda l: jnp.full_like(l, 7.5), pools)
    bt = np.zeros((4, max_pages), np.int64)
    bt[0, :2] = [3, 4]          # live: position 8 -> writes (page 4, slot 0)
    bt[1, :] = 5                # pad rows 1 and 2 alias page 5 on purpose
    bt[2, :] = 5
    bt[3, :1] = [7]             # live: position 0 -> writes (page 7, slot 0)
    # one token per sequence: row b is sequence b at its context length
    # minus one, -1 on the padded rows
    tseq = jnp.arange(4, dtype=jnp.int32)
    tpos = jnp.asarray([8, -1, -1, 0], jnp.int32)
    toks = jnp.asarray([5, 6, 7, 8], jnp.int32)
    _, _, new_pools = m.forward_mixed_paged(
        params, toks, tseq, tpos, tseq, pools, jnp.asarray(bt, jnp.int32),
        discard_pid=n_pages - 1)
    live = np.zeros((n_pages, page), bool)
    live[4, 0] = live[7, 0] = True
    for leaf in jax.tree.leaves(new_pools):
        arr = np.asarray(leaf)              # (periods, n_pages, page, ...)
        assert np.all(arr[:, ~live] == 7.5), "pad row wrote a pool page"
        assert not np.any(arr[:, live] == 7.5), "live row write missing"


def test_mixed_pad_rows_never_touch_pages():
    """Fused mixed batch: padded token rows (tok_pos == -1, tok_seq
    deliberately aliasing a live sequence) must write nothing — every pool
    slot except the live chunk/decode targets keeps its sentinel, and the
    sampled ids come from the right rows."""
    cfg = get_config("llama3.2-1b", tiny=True)
    m = LM(cfg)
    params = m.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    page, n_pages, max_pages = 8, 12, 4
    pools = m.init_cache(n_pages, page, dtype=jnp.float32)
    pools = jax.tree.map(lambda l: jnp.full_like(l, 7.5), pools)
    bt = np.zeros((2, max_pages), np.int64)
    bt[0, :2] = [3, 4]          # seq 0: chunk positions 5..7 -> pages 3, 4
    bt[1, :1] = [7]             # seq 1: decode at position 1 -> page 7
    # flat batch: 3 chunk tokens + 1 decode token + 4 pad rows that alias
    # live sequences on purpose
    tseq = jnp.asarray([0, 0, 0, 1, 0, 1, 0, 1], jnp.int32)
    tpos = jnp.asarray([5, 6, 7, 1, -1, -1, -1, -1], jnp.int32)
    toks = jnp.asarray([5, 6, 7, 8, 1, 1, 1, 1], jnp.int32)
    qlast = jnp.asarray([2, 3], jnp.int32)
    _, _, new_pools = m.forward_mixed_paged(
        params, toks, tseq, tpos, qlast, pools,
        jnp.asarray(bt, jnp.int32), discard_pid=n_pages - 1)
    live = np.zeros((n_pages, page), bool)
    live[3, 5:] = True          # positions 5..7 of seq 0 (all in page 3)
    live[7, 1] = True           # position 1 of seq 1
    for leaf in jax.tree.leaves(new_pools):
        arr = np.asarray(leaf)              # (periods, n_pages, page, ...)
        assert np.all(arr[:, ~live] == 7.5), "pad row wrote a pool page"
        assert not np.any(arr[:, live] == 7.5), "live row write missing"


def _check_attention_grid_counts(monkeypatch, arch):
    """Runs the agent workload on ``arch`` and checks that every fused
    step adds its launch's brute-force grid and live steps; returns the
    attention config of the layer counted."""
    from attn_grid import brute_force_steps
    cfg = get_config(arch, tiny=True)
    eng = Engine(cfg, POLICIES["infercept"], page_size=16, n_pages=128,
                 max_model_len=256, seed=0)
    layers = [b.attn for g in cfg.groups for b in g.period if b.attn]
    full = [a for a in layers if a.sliding_window is None]
    a = (full or layers)[0]
    seen = []
    real = eng._dispatch_mixed

    def counted(plan):
        g0 = eng.counters["attn_grid_steps"]
        l0 = eng.counters["attn_live_steps"]
        out = real(plan)
        if out is not None:
            spans = [(b, start, n)
                     for b, (_, _, start, n, _) in enumerate(out[0])]
            want = brute_force_steps(
                spans, Engine._bucket(sum(n for *_, n in spans)),
                Engine._bucket(len(spans)), Hkv=a.n_kv_heads,
                G=a.n_heads // a.n_kv_heads, hd=a.head_dim, page=16,
                max_pages=eng.max_pages, q_bytes=4, kv_bytes=4,
                window=a.sliding_window)
            assert (eng.counters["attn_grid_steps"] - g0,
                    eng.counters["attn_live_steps"] - l0) == want
            seen.append(want)
        return out

    monkeypatch.setattr(eng, "_dispatch_mixed", counted)
    for r in _agent_workload(cfg):
        eng.add_request(r)
    eng.run()
    assert seen and all(0 < live <= grid for grid, live in seen)
    assert eng.counters["attn_grid_steps"] == sum(g for g, _ in seen)
    assert eng.counters["attn_live_steps"] == sum(lv for _, lv in seen)
    return a


def test_fused_step_counts_attention_grid(monkeypatch):
    """Every fused step adds the ragged kernel's grid steps and live steps
    for its launch to attn_grid_steps / attn_live_steps: a brute-force
    count over the step's rows (tests/attn_grid.py)."""
    _check_attention_grid_counts(monkeypatch, "llama3.2-1b")


def test_fused_step_counts_full_attention_layer(monkeypatch):
    """Gemma2 alternates windowed and full layers, windowed first: the
    counters count a full-attention layer's launch."""
    a = _check_attention_grid_counts(monkeypatch, "gemma2-9b")
    assert a.sliding_window is None


@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_fused_step_matches_per_layer_reference(monkeypatch, pallas,
                                                kv_dtype):
    """The fused step carries the stacked pools through the layer scan and
    addresses pool[layer] in place: on a two-layer model its pools (every
    layer, the appended rows of layer 1 included) and sampled ids equal a
    reference that runs each layer by itself on that layer's own one-layer
    pool. Both the jnp path and the Pallas kernels (interpreted)."""
    from repro.models import attention
    from repro.models import blocks as B
    from repro.models.common import rms_norm
    monkeypatch.setattr(attention, "_paged_use_pallas", lambda: pallas)
    cfg = get_config("llama3.2-1b", tiny=True)
    (g,) = cfg.groups
    assert g.n_periods >= 2 and len(g.period) == 1
    m = LM(cfg)
    params = m.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    page, n_pages, max_pages = 8, 12, 4
    pools = m.init_cache(n_pages, page, dtype=jnp.float32, kv_dtype=kv_dtype)
    # every layer starts from contents of its own, so a write to the wrong
    # layer (or none) shows
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    pools = jax.tree.map(
        lambda l: (jnp.abs(jax.random.normal(next(keys), l.shape)) * 0.01
                   if l.dtype == jnp.float32 and l.ndim == 3 else
                   jax.random.normal(next(keys), l.shape) * 3).astype(
                       l.dtype), pools)
    bt = np.zeros((2, max_pages), np.int64)
    bt[0, :2] = [3, 4]          # seq 0: a chunk at positions 5..10
    bt[1, :1] = [7]             # seq 1: a decode at position 1
    tseq = jnp.asarray([0] * 6 + [1] + [0] * 3 + [1], jnp.int32)
    tpos = jnp.asarray(list(range(5, 11)) + [1] + [-1] * 4, jnp.int32)
    toks = jnp.asarray([5, 6, 7, 8, 9, 10, 11, 1, 1, 1, 1], jnp.int32)
    qlast = jnp.asarray([5, 6], jnp.int32)
    bt = jnp.asarray(bt, jnp.int32)
    discard = n_pages - 1

    fused = jax.jit(lambda p, pl: m.forward_mixed_paged(
        p, toks, tseq, tpos, qlast, pl, bt, discard_pid=discard))

    @jax.jit
    def per_layer(p, pl):
        ctx = {"block_tables": bt, "tok_seq": tseq, "tok_pos": tpos,
               "discard_pid": discard}
        x = m.embed(p, toks[:, None])[:, 0]
        own = []
        for layer in range(g.n_periods):
            pj = jax.tree.map(lambda w: w[layer], p["groups"][0]["scan"])
            pool = jax.tree.map(lambda a: a[layer][None], pl[0]["b0"])
            x, pool = B.block_mixed_paged(pj["b0"], cfg, g.period[0], x,
                                          pool, 0, ctx)
            own.append(pool)
        x = rms_norm(x, p["final_norm"], cfg.norm_eps)
        ids = jnp.argmax(m.logits(p, x[qlast]), axis=-1).astype(jnp.int32)
        return ids, ({"b0": jax.tree.map(
            lambda *a: jnp.concatenate(a), *own)},)

    want_ids, want = per_layer(params, jax.tree.map(jnp.copy, pools))
    got_ids, _, got = fused(params, jax.tree.map(jnp.copy, pools))
    assert jnp.array_equal(got_ids, want_ids)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g_leaf, w_leaf in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(g_leaf).view(np.uint8),
                              np.asarray(w_leaf).view(np.uint8))
    # the rows landed in layer 1 as in layer 0: the chunk's positions 5..7
    # on page 3, 8..10 on page 4, the decode's position 1 on page 7
    new_k, old_k = got[0]["b0"]["k"], pools[0]["b0"]["k"]
    for pid, slots in ((3, slice(5, 8)), (4, slice(0, 3)), (7, slice(1, 2))):
        for layer in range(g.n_periods):
            assert not np.array_equal(new_k[layer, pid, slots],
                                      old_k[layer, pid, slots])
