"""Differential harness for the paged and fused execution paths
(DESIGN.md §9 / §10).

Two oracle layers, mirroring how the engine grew:

  * gather oracle (``paged=False``) — materializes a contiguous cache view
    per decode step / prefill chunk; the in-place paged path must emit its
    exact greedy token streams (§9).
  * unfused oracle (``paged=True, fused=False``) — one jitted call per
    chunk plus one per decode batch; the fused mixed-batch path (one
    dispatch per iteration, on-device sampling) must emit ITS exact
    streams too (§10), across every scheduling policy with the prefix
    cache on and off, while reporting exactly one device dispatch per
    non-empty iteration and an O(B)-ids logit transfer.
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


def _run(cfg, reqs, policy, *, paged, fused=True, prefix_cache=False,
         overlap=True):
    eng = Engine(cfg, POLICIES[policy], page_size=16, n_pages=128,
                 max_model_len=256, seed=0, paged=paged, fused=fused,
                 prefix_cache=prefix_cache, overlap=overlap)
    for r in copy.deepcopy(reqs):
        eng.add_request(r)
    fin = eng.run()
    assert len(fin) == len(reqs), (policy, paged, fused, prefix_cache)
    return {r.rid: eng.generated_text(r) for r in fin}, eng


@pytest.fixture(scope="module")
def diff():
    cfg = get_config("llama3.2-1b", tiny=True)
    reqs = _agent_workload(cfg)
    oracle = _run(cfg, reqs, "vllm", paged=False, fused=False)
    fused, unfused = {}, {}
    for name in ALL_POLICIES:
        for cache_on in (False, True):
            fused[(name, cache_on)] = _run(cfg, reqs, name, paged=True,
                                           fused=True,
                                           prefix_cache=cache_on)
            unfused[(name, cache_on)] = _run(cfg, reqs, name, paged=True,
                                             fused=False,
                                             prefix_cache=cache_on)
    return cfg, oracle, fused, unfused


@pytest.mark.slow
def test_paged_streams_match_gather_oracle(diff):
    """The headline differential property: every paged run — any policy,
    cache on or off, fused or not — emits the gather oracle's exact token
    streams."""
    _, (oracle_streams, _), fused, unfused = diff
    for key, (streams, _) in fused.items():
        assert streams == oracle_streams, \
            f"fused {key} diverged from the gather oracle"
    for key, (streams, _) in unfused.items():
        assert streams == oracle_streams, \
            f"unfused {key} diverged from the gather oracle"


def test_fused_streams_match_unfused(diff):
    """§10's differential pin: the fused mixed-batch path reproduces the
    unfused per-call path's greedy streams bit-for-bit on every policy,
    prefix cache on and off."""
    _, _, fused, unfused = diff
    for key in fused:
        assert fused[key][0] == unfused[key][0], \
            f"fused {key} diverged from the unfused oracle"


def test_fused_single_dispatch_per_iteration(diff):
    """Every non-empty iteration of a fused run is exactly ONE jitted
    model call; unfused runs pay one per chunk plus one per decode batch
    (>= fused, strictly more whenever an iteration mixes work)."""
    _, _, fused, unfused = diff
    for key, (_, eng) in fused.items():
        assert eng.counters["mixed_iterations"] > 0
        assert eng.counters["device_dispatches"] == \
            eng.counters["mixed_iterations"], key
    for key, (_, eng) in unfused.items():
        assert eng.counters["device_dispatches"] >= \
            eng.counters["mixed_iterations"], key


def test_fused_transfers_ids_not_logits(diff):
    """On-device sampling boundary: a fused run moves at most
    bucket(B) * 4 bytes of sampled int32 ids per iteration device->host;
    the unfused oracle fetches the full B x vocab float32 logits every
    decode step."""
    cfg, _, fused, unfused = diff
    for key, (_, eng) in fused.items():
        b_pad = Engine._bucket(len(eng.finished))      # max batch bound
        assert eng.counters["logit_bytes"] <= \
            4 * b_pad * eng.counters["mixed_iterations"], key
    for key, (_, eng) in unfused.items():
        assert eng.counters["logit_bytes"] >= \
            eng.counters["decode_tokens"] * cfg.vocab_size * 4, key
        ratio = (eng.counters["logit_bytes"]
                 / max(1, fused[key][1].counters["logit_bytes"]))
        assert ratio >= cfg.vocab_size / 2, \
            f"fused logit transfer only {ratio:.0f}x smaller for {key}"


def test_paged_mechanisms_actually_exercised(diff):
    """The equality above must not be vacuous: recompute, swap, and cache
    hits all really happened on the paged path."""
    _, _, fused, _ = diff
    assert fused[("vllm", False)][1].sched.stats.recompute_tokens > 0
    swap_eng = fused[("swap", False)][1]
    assert swap_eng.sched.stats.swapped_out_tokens > 0
    assert (swap_eng.sched.stats.swapped_in_tokens
            == swap_eng.sched.stats.swapped_out_tokens)
    assert fused[("vllm", True)][1].sched.stats.cache_hit_tokens > 0


def test_no_page_leaks_on_paged_path(diff):
    _, _, fused, unfused = diff
    for runs in (fused, unfused):
        for key, (_, eng) in runs.items():
            held = eng.cache.n_pages if eng.cache is not None else 0
            assert eng.blocks.num_free == \
                eng.blocks.n_pages - 1 - held, key


def test_paged_decode_moves_o1_bytes_per_token(diff):
    """The measurable form of the §9 claim: the paged path (fused or not)
    writes exactly one token's K/V per generated token; the gather oracle
    round-trips the whole block-table view (O(context))."""
    _, (_, gather_eng), fused, unfused = diff
    for runs in (fused, unfused):
        for key in [("vllm", False), ("infercept", True)]:
            eng = runs[key][1]
            assert eng.counters["decode_tokens"] > 0
            assert eng.counters["decode_bytes"] == \
                eng.counters["decode_tokens"] * eng.kv_token_bytes, key
            assert eng.counters["prefill_bytes"] == \
                eng.counters["prefill_tokens"] * eng.kv_token_bytes, key
    # gather decode: >= one full table gather per token => O(context)
    table_tokens = gather_eng.max_pages * gather_eng.page
    assert gather_eng.kv_bytes_per_decode_token() >= \
        table_tokens * gather_eng.kv_token_bytes
    ratio = (gather_eng.kv_bytes_per_decode_token()
             / fused[("vllm", False)][1].kv_bytes_per_decode_token())
    assert ratio >= 10.0, f"paged decode only {ratio:.1f}x cheaper"


# ---------------------------------------------------------------------------
# pipelined step: overlap-on vs overlap-off (DESIGN.md §12)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_overlap_off_streams_match_across_policies(diff):
    """The §12 differential pin: the serial engine (overlap=False, the
    execute-then-sync oracle) emits the exact token streams of the
    pipelined default across all 4 policies × fused on/off × prefix-cache
    on/off — commit-phase reconciliation keeps every host-visible state
    transition in the serial order. Serial runs must also charge zero
    overlap counters (nothing was hidden), while the fixture's pipelined
    runs hide real swap DMA on the swap-traffic policies."""
    cfg, (oracle_streams, _), fused, _ = diff
    for name in ALL_POLICIES:
        for fus in (True, False):
            for cache_on in (False, True):
                streams, eng = _run(cfg, _agent_workload(cfg), name,
                                    paged=True, fused=fus,
                                    prefix_cache=cache_on, overlap=False)
                assert streams == oracle_streams, \
                    f"serial {(name, fus, cache_on)} diverged from the " \
                    "pipelined oracle streams"
                assert eng.counters["swap_overlap_bytes"] == 0
                assert eng.counters["pipeline_bubbles"] == 0
    # the pipelined runs really hid swap DMA under the model window
    for key in [("swap", False), ("infercept", False)]:
        pipe = fused[key][1]
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
    _, _, fused, _ = diff
    eng = fused[("swap", False)][1]
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
    chunks AND a decode batch at once. The unfused engine pays
    1 + len(chunks) dispatches there; the fused engine must still report
    exactly one per non-empty iteration, with identical streams and an
    O(B)-ids logit transfer."""
    cfg = get_config("llama3.2-1b", tiny=True)
    reqs = make_agent_workload(
        seed=7, n_sessions=4, rate_rps=500.0, vocab=cfg.vocab_size,
        n_templates=2, system_prompt_len=50, turns=(2, 2), turn_gap_s=0.01,
        hist_per_turn=12, prefix_share=0.75, gen_tokens=(10, 3),
        final_gen=(10, 3), ret_tokens=(6, 2), max_tool_calls=2, max_ctx=240)

    def burst(fused):
        eng = Engine(cfg, POLICIES["vllm"], page_size=16, n_pages=256,
                     max_model_len=256, seed=0, paged=True, fused=fused)
        for r in copy.deepcopy(reqs):
            eng.add_request(r)
        fin = eng.run()
        assert len(fin) == len(reqs)
        return {r.rid: eng.generated_text(r) for r in fin}, eng

    sf, ef = burst(True)
    su, eu = burst(False)
    assert sf == su
    # the scenario is real: some unfused iteration ran chunk(s) + decode
    assert eu.counters["device_dispatches"] > \
        eu.counters["mixed_iterations"], "no mixed iteration occurred"
    assert ef.counters["device_dispatches"] == \
        ef.counters["mixed_iterations"]
    assert ef.counters["logit_bytes"] <= \
        4 * Engine._bucket(len(reqs)) * ef.counters["mixed_iterations"]
    assert eu.counters["logit_bytes"] >= \
        eu.counters["decode_tokens"] * cfg.vocab_size * 4


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
    """Two padded rows deliberately alias the same block-table page: with
    masked appends neither may write anywhere — every pool slot except the
    two live targets keeps its sentinel."""
    cfg = get_config("llama3.2-1b", tiny=True)
    m = LM(cfg)
    params = m.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    page, n_pages, max_pages = 8, 12, 4
    pools = m.init_cache(n_pages, page, dtype=jnp.float32)
    pools = jax.tree.map(lambda l: jnp.full_like(l, 7.5), pools)
    bt = np.zeros((4, max_pages), np.int64)
    bt[0, :2] = [3, 4]          # live: ctx 9 -> writes (page 4, slot 0)
    bt[1, :] = 5                # pad rows 1 and 2 alias page 5 on purpose
    bt[2, :] = 5
    bt[3, :1] = [7]             # live: ctx 1 -> writes (page 7, slot 0)
    cl = jnp.asarray([9, 0, 0, 1], jnp.int32)
    toks = jnp.asarray([5, 6, 7, 8], jnp.int32)
    _, new_pools = m.decode_step_paged(params, toks, cl, pools,
                                       jnp.asarray(bt, jnp.int32),
                                       discard_pid=n_pages - 1)
    live = np.zeros((n_pages, page), bool)
    live[4, 0] = live[7, 0] = True
    for leaf in jax.tree.leaves(new_pools):
        arr = np.asarray(leaf)              # (periods, n_pages, page, ...)
        assert np.all(arr[:, ~live] == 7.5), "pad row wrote a pool page"
        assert not np.any(arr[:, live] == 7.5), "live row write missing"


def test_gather_scatter_pad_rows_never_touch_pages():
    """White-box regression for the gather oracle: _scatter_tokens used to
    route padded rows into the shared scratch page — two pad rows aliasing
    one physical page in a single unordered scatter. Padded entries must
    now be dropped outright."""
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = Engine(cfg, POLICIES["vllm"], page_size=8, n_pages=16,
                 max_model_len=64, paged=False)
    eng.pools = jax.tree.map(lambda l: jnp.full_like(l, 3.25), eng.pools)
    bt = np.asarray([[1, 2] + [eng.scratch_page] * (eng.max_pages - 2)])
    cache = jax.tree.map(lambda l: jnp.full_like(l, 9.0),
                         eng._gather_cache(bt))
    eng._scatter_tokens(cache, bt, np.zeros(1, np.int64),
                        np.asarray([5]), pad_to=4)      # 3 pad entries
    target = np.zeros((16, 8), bool)
    target[1, 5] = True                                  # pos 5 -> page 1
    for leaf in jax.tree.leaves(eng.pools):
        arr = np.asarray(leaf)
        assert np.all(arr[:, ~target] == 3.25), \
            "pad scatter entry wrote a pool page (scratch included)"
        assert np.all(arr[:, target] == 9.0)


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
                 max_model_len=256, seed=0, paged=True, fused=True)
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
