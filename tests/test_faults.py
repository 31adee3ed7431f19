"""Fault-tolerant interception: the blast-radius suite (DESIGN.md §15).

The contract under test: a tool failure, timeout, caller cancellation,
or pool-saturation event ends AT MOST the session that suffered it —
never the engine, and never a co-resident session's token stream. The
pins exploit the repo's determinism discipline:

  * greedy streams are keyed by (seed, position) only, so an unaffected
    session's stream under 10-30% injected faults must be BIT-IDENTICAL
    to the fault-free run — the chaos harness makes "unaffected" itself
    deterministic (draws keyed by (seed, rid, seg_idx, attempt));
  * VirtualTimeToolExecutor's returned ids are f(rid, seg_idx),
    attempt-independent, so a session that recovers via retry also
    reproduces the fault-free stream exactly;
  * teardown must reclaim every page: after a drained run the block pool
    is back to n_pages - 1 (the reserved scratch page), whatever mix of
    cancels/failures/preemptions happened in between;
  * the WasteLedger's independent ``total_check`` accumulator must equal
    the per-cause sum after any teardown storm.
"""
import copy
import threading

import pytest

from repro.configs import get_config
from repro.core import POLICIES
from repro.core.request import InterceptDirective, SamplingParams
from repro.serving.api_executor import (ChaosToolExecutor,
                                        OracleToolResultPredictor,
                                        VirtualTimeToolExecutor,
                                        WallClockToolExecutor)
from repro.serving.engine import Engine
from repro.serving.session import InferCeptClient
from repro.serving.workloads import make_agent_workload

ALL_POLICIES = ["preserve", "vllm", "swap", "infercept"]


def _engine(policy, **kw):
    cfg = kw.pop("cfg", None) or get_config("llama3.2-1b", tiny=True)
    kw.setdefault("page_size", 16)
    kw.setdefault("n_pages", 128)
    kw.setdefault("max_model_len", 256)
    kw.setdefault("seed", 0)
    return Engine(cfg, POLICIES[policy], **kw)


def _leak_free(eng):
    # every page is free but the scratch page and those the prefix cache
    # holds
    held = eng.cache.n_pages if eng.cache is not None else 0
    return eng.blocks.num_free == eng.blocks.n_pages - 1 - held


def _ledger_balanced(eng):
    tot = sum(eng.ledger.causes.values())
    return abs(tot - eng.ledger.total_check) <= 1e-6 * max(1.0, tot)


def once_detector(n_at, kind="math", duration=0.05):
    """Fire one interception per session the first time it reaches
    ``n_at`` output tokens (stop tokens may never be sampled, so the
    detector — not the token stream — decides when to pause)."""
    fired = set()

    def det(req, tid, now):
        if req.output_tokens == n_at and req.rid not in fired:
            fired.add(req.rid)
            return InterceptDirective(kind=kind, duration_hint=duration)
        return None
    return det


def multi_detector(at=(5, 10), kind="math", duration=0.05):
    fired = {}

    def det(req, tid, now):
        seen = fired.setdefault(req.rid, set())
        if req.output_tokens in at and req.output_tokens not in seen:
            seen.add(req.output_tokens)
            return InterceptDirective(kind=kind, duration_hint=duration)
        return None
    return det


# ---------------------------------------------------------------------------
# fault policy: retries, backoff, timeouts
# ---------------------------------------------------------------------------

def test_terminal_failure_fails_only_that_session():
    """Retries exhausted -> FailedEvent, accrued occupancy charged to
    ``tool_failed``, pages reclaimed — the engine keeps stepping."""
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = _engine("infercept", cfg=cfg)
    cl = InferCeptClient(eng)
    bad = ChaosToolExecutor(
        VirtualTimeToolExecutor(cfg.vocab_size, n_tokens=4, duration=0.05),
        seed=1, failure_rate=1.0)
    h = cl.submit([1, 2, 3, 4], detector=once_detector(5),
                  max_new_tokens=64, tools=bad,
                  sampling=SamplingParams(tool_retries=2,
                                          tool_backoff_s=0.01))
    hb = cl.submit([9, 8, 7, 6], max_new_tokens=12)
    cl.poll()
    assert h.state == "failed" and h.done and not h.finished
    assert h.error is not None and h.error.kind == "unavailable"
    assert eng.counters["tool_retries"] == 2      # attempts 1 and 2
    assert eng.counters["tool_faults"] == 3       # every attempt failed
    assert eng.counters["sessions_failed"] == 1
    assert eng.ledger.causes["tool_failed"] > 0.0
    assert eng.sched.stats.tool_failures == 1
    assert hb.finished and hb.request.output_tokens == 12
    assert _ledger_balanced(eng) and _leak_free(eng)


def test_retry_recovery_stream_bit_identical():
    """A failure recovered by retry only costs time: the session's stream
    equals the fault-free run bit-for-bit (returned ids are
    attempt-independent), the estimator saw the failed attempt, and the
    pause got longer by the failure latency + backoff."""
    cfg = get_config("llama3.2-1b", tiny=True)

    def run(failure_rate):
        eng = _engine("infercept", cfg=cfg)
        cl = InferCeptClient(eng)
        # seed 0 probed against the chaos keying (rid=0, seg_idx=1 at
        # dispatch — segment_done already advanced it): the attempt-0
        # draw fails, the attempt-1 draw succeeds
        tools = ChaosToolExecutor(
            VirtualTimeToolExecutor(cfg.vocab_size, n_tokens=4,
                                    duration=0.05),
            seed=0, failure_rate=failure_rate)
        h = cl.submit([1, 2, 3, 4], detector=once_detector(5),
                      max_new_tokens=24, tools=tools,
                      sampling=SamplingParams(tool_retries=5,
                                              tool_backoff_s=0.01))
        cl.poll()
        return h, eng, cl.token_ids(h)

    h1, e1, s1 = run(0.5)
    h0, e0, s0 = run(0.0)
    assert h1.finished and h0.finished
    assert e1.counters["tool_retries"] == 1
    assert e0.counters["tool_retries"] == 0
    assert s1 == s0, "recovered session's stream diverged from fault-free"
    assert e1.sched.estimator.failed_observations("math") == 1
    assert h1.request.paused_time > h0.request.paused_time
    assert _leak_free(e1) and _ledger_balanced(e1)


def test_timeout_fires_at_virtual_deadline():
    """A hung tool (completion far in the future) is cut off at the
    virtual deadline, retried, and — still hanging — exhausts into a
    terminal ``timeout`` failure."""
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = _engine("infercept", cfg=cfg)
    cl = InferCeptClient(eng)
    hang = ChaosToolExecutor(
        VirtualTimeToolExecutor(cfg.vocab_size, n_tokens=4, duration=0.05),
        seed=2, timeout_rate=1.0)
    h = cl.submit([1, 2, 3, 4], detector=once_detector(5),
                  max_new_tokens=64, tools=hang,
                  sampling=SamplingParams(tool_timeout_s=0.5,
                                          tool_retries=1,
                                          tool_backoff_s=0.01))
    cl.poll()
    assert h.state == "failed"
    assert h.error is not None and h.error.kind == "timeout"
    assert eng.counters["tool_timeouts"] == 2     # attempt 0 and the retry
    # the deadline is virtual: the engine never waited out the hang
    assert eng.now < 100.0
    assert _leak_free(eng) and _ledger_balanced(eng)


def test_admission_backpressure_rejects_not_raises():
    """Beyond max_queued the engine rejects with a RejectedEvent instead
    of growing the arrival queue; admitted sessions are unaffected."""
    eng = _engine("infercept", max_queued=2)
    cl = InferCeptClient(eng)
    hs = [cl.submit([1, 2, 3], max_new_tokens=4) for _ in range(4)]
    states = [h.state for h in hs]
    assert states.count("rejected") == 2
    assert eng.counters["sessions_rejected"] == 2
    cl.poll()
    assert sum(1 for h in hs if h.finished) == 2
    assert _leak_free(eng)


def test_chaos_draws_are_deterministic():
    """The chaos harness is a pure function of (seed, rid, seg_idx,
    attempt): two identical runs produce identical outcomes, counters,
    and ledger charges."""
    cfg = get_config("llama3.2-1b", tiny=True)

    def run():
        eng = _engine("infercept", cfg=cfg)
        cl = InferCeptClient(eng)
        tools = ChaosToolExecutor(
            VirtualTimeToolExecutor(cfg.vocab_size, n_tokens=4,
                                    duration=0.05),
            seed=5, failure_rate=0.3, timeout_rate=0.1)
        hs = [cl.submit([10 + i, 11 + i, 12 + i],
                        detector=multi_detector(), max_new_tokens=16,
                        tools=tools,
                        sampling=SamplingParams(tool_timeout_s=1.0,
                                                tool_retries=1,
                                                tool_backoff_s=0.01))
              for i in range(5)]
        cl.poll()
        return ([h.state for h in hs],
                {k: eng.counters[k] for k in ("tool_faults", "tool_retries",
                                              "tool_timeouts",
                                              "sessions_failed")},
                dict(eng.ledger.causes))

    assert run() == run()


# ---------------------------------------------------------------------------
# chaos soak: blast radius under injected faults
# ---------------------------------------------------------------------------

def _soak(policy, *, overlap=True, failure_rate=0.0,
          timeout_rate=0.0, n=6, seed_chaos=7, prefix_cache=False):
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = _engine(policy, cfg=cfg, overlap=overlap,
                  prefix_cache=prefix_cache)
    cl = InferCeptClient(eng)
    tools = ChaosToolExecutor(
        VirtualTimeToolExecutor(cfg.vocab_size, n_tokens=4, duration=0.05),
        seed=seed_chaos, failure_rate=failure_rate,
        timeout_rate=timeout_rate)
    hs = [cl.submit([10 + i, 11 + i, 12 + i, 13 + i],
                    detector=multi_detector(), max_new_tokens=20,
                    tools=tools,
                    sampling=SamplingParams(tool_timeout_s=1.0,
                                            tool_retries=1,
                                            tool_backoff_s=0.01))
          for i in range(n)]
    cl.poll()
    streams = {h.rid: cl.token_ids(h) for h in hs if h.finished}
    return eng, hs, streams


def _assert_soak_invariants(eng, hs, streams, clean):
    # 1. every session reached a terminal state — the engine never died
    assert all(h.done for h in hs)
    # 2. zero page leaks after the teardown storm
    assert _leak_free(eng)
    # 3. the ledger's cause split still sums to the independent check
    assert _ledger_balanced(eng)
    # 4. blast radius: every SURVIVING session (untouched or recovered
    #    via retry) emits the fault-free run's exact stream
    for rid, stream in streams.items():
        assert stream == clean[rid], \
            f"surviving session {rid} diverged under injected faults"


@pytest.mark.parametrize("rate", [0.1, 0.3])
def test_chaos_soak_quick(rate):
    _, _, clean = _soak("infercept", failure_rate=0.0)
    eng, hs, streams = _soak("infercept", failure_rate=rate,
                             timeout_rate=0.05)
    _assert_soak_invariants(eng, hs, streams, clean)
    # the sweep must not be vacuous at these rates
    assert eng.counters["tool_faults"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("policy", ALL_POLICIES)
@pytest.mark.parametrize("prefix_cache", [False, True])
def test_chaos_soak_matrix(policy, prefix_cache):
    _, _, clean = _soak(policy, prefix_cache=prefix_cache,
                        failure_rate=0.0)
    eng, hs, streams = _soak(policy, prefix_cache=prefix_cache,
                             failure_rate=0.2, timeout_rate=0.1)
    _assert_soak_invariants(eng, hs, streams, clean)


@pytest.mark.slow
def test_chaos_soak_serial_engine():
    """overlap=False (the serial execute-then-sync oracle) under faults:
    the teardown paths cannot assume the pipelined swap stager exists."""
    _, _, clean = _soak("swap", overlap=False, failure_rate=0.0)
    eng, hs, streams = _soak("swap", overlap=False, failure_rate=0.2,
                             timeout_rate=0.1)
    _assert_soak_invariants(eng, hs, streams, clean)


# ---------------------------------------------------------------------------
# cancellation from every lifecycle state
# ---------------------------------------------------------------------------

def test_cancel_from_queued():
    eng = _engine("infercept")
    cl = InferCeptClient(eng)
    h = cl.submit([1, 2, 3, 4], max_new_tokens=64)
    h.cancel()
    cl.poll()
    assert h.state == "cancelled" and h.done and not h.finished
    assert eng.counters["sessions_cancelled"] == 1
    # never admitted: nothing accrued, nothing charged
    assert eng.ledger.causes["cancelled"] == 0.0
    assert _leak_free(eng)


def test_cancel_from_running_leaves_coresident_untouched():
    cfg = get_config("llama3.2-1b", tiny=True)

    def run(with_cancel):
        eng = _engine("infercept", cfg=cfg)
        cl = InferCeptClient(eng)
        h = cl.submit([1, 2, 3, 4], max_new_tokens=64)
        hb = cl.submit([9, 8, 7, 6], max_new_tokens=16)
        if with_cancel:
            while h.request.output_tokens < 4:
                cl.poll(max_steps=1)
            h.cancel()
        cl.poll()
        return eng, h, hb, cl.token_ids(hb)

    eng, h, hb, stream = run(True)
    assert h.state == "cancelled" and h.request.output_tokens >= 4
    assert hb.finished
    assert eng.ledger.causes["cancelled"] > 0.0
    assert eng.sched.stats.cancellations == 1
    assert _leak_free(eng) and _ledger_balanced(eng)
    _, _, _, clean = run(False)
    assert stream == clean


def test_cancel_from_paused_preserve():
    """Cancel mid-interception under preserve: the pinned pause context
    is released and its byte-seconds land in the ``cancelled`` cause."""
    eng = _engine("preserve")
    cl = InferCeptClient(eng)
    h = cl.submit(list(range(16)), max_new_tokens=32)
    hb = cl.submit(list(range(30, 46)), max_new_tokens=12)
    cl.intercept(h, duration_hint=5.0)
    while h.state != "intercepted":
        cl.poll(max_steps=1)
    assert h.request.device_tokens > 0      # preserve pins the context
    cl.poll(max_steps=2)    # let the pinned pause accrue byte-seconds
    h.cancel()
    cl.poll()
    assert h.state == "cancelled"
    assert hb.finished
    assert eng.ledger.causes["cancelled"] > 0.0
    assert _leak_free(eng) and _ledger_balanced(eng)


def test_cancel_from_swapped():
    """Cancel a session whose paused context was swapped to host: host
    bytes are dropped without a swap-in and the pool stays clean."""
    eng = _engine("swap")
    cl = InferCeptClient(eng)
    h = cl.submit(list(range(32)), max_new_tokens=32)
    hb = cl.submit(list(range(40, 56)), max_new_tokens=20)
    cl.intercept(h, duration_hint=50.0)
    for _ in range(200):
        cl.poll(max_steps=1)
        if h.request.host_tokens > 0:
            break
    assert h.request.host_tokens > 0, "never reached the swapped state"
    h.cancel()
    cl.poll()
    assert h.state == "cancelled"
    assert h.request.host_tokens == 0       # host bytes reconciled
    assert hb.finished
    assert _leak_free(eng) and _ledger_balanced(eng)


def test_cancel_with_inflight_async_tool():
    """Cancel while an off-thread tool is still running: the late result
    is discarded on drain (never resumes a dead rid) and the co-resident
    session drains normally."""
    eng = _engine("vllm")
    cl = InferCeptClient(eng, tool_workers=1)
    gate = threading.Event()

    def slow_tool(call):
        assert gate.wait(30.0), "test gate never opened"
        return [5, 6, 7]

    def det(req, tid, now):
        if req.output_tokens == 3 and req.seg_idx == 0:
            return InterceptDirective("tool", 0.2, reason="detector")
        return None

    h = cl.submit(list(range(16)), detector=det, max_new_tokens=10,
                  tools=WallClockToolExecutor(slow_tool))
    hb = cl.submit(list(range(30, 46)), max_new_tokens=24)
    for _ in range(200):
        cl.poll(max_steps=1)
        if h.state == "resuming" or eng.async_tools.inflight > 0:
            break
    h.cancel()
    gate.set()                              # worker completes AFTER cancel
    cl.poll()
    assert h.state == "cancelled"
    assert hb.finished and hb.request.output_tokens == 24
    assert _leak_free(eng) and _ledger_balanced(eng)
    cl.close()


def test_cancel_while_speculating_frees_fork():
    """Cancel a session with a live speculative fork: the fork's pages
    are freed, its accrued occupancy joins the cancel charge, and every
    other session's stream matches the cancel-free speculative run."""
    cfg = get_config("llama3.2-1b", tiny=True)
    reqs = make_agent_workload(
        seed=5, n_sessions=2, rate_rps=2.0, vocab=cfg.vocab_size,
        n_templates=2, system_prompt_len=50, turns=(2, 2), turn_gap_s=3.0,
        hist_per_turn=12, prefix_share=0.75, gen_tokens=(8, 3),
        final_gen=(8, 3), ret_tokens=(6, 2), max_tool_calls=2, max_ctx=240)

    def run(cancel):
        eng = _engine("infercept", cfg=cfg, speculate=True,
                      predictor=OracleToolResultPredictor(cfg.vocab_size))
        assert eng.speculate
        for r in copy.deepcopy(reqs):
            eng.add_request(r)
        target = {}
        if cancel:
            def hook(e):
                if e._spec_forks and not target:
                    target["rid"] = min(e._spec_forks)
                    e.cancel_request(target["rid"])
            eng.on_plan = hook
        fin = eng.run()
        return eng, fin, target

    eng, fin, target = run(True)
    assert "rid" in target, "no fork was ever live"
    assert len(fin) == len(reqs) - 1
    assert eng.counters["sessions_cancelled"] == 1
    assert eng.ledger.causes["cancelled"] > 0.0
    assert not eng._spec_forks
    assert _leak_free(eng) and _ledger_balanced(eng)
    base_eng, base_fin, _ = run(False)
    base = {r.rid: base_eng.generated_text(r) for r in base_fin}
    for r in fin:
        assert base[r.rid] == eng.generated_text(r), \
            f"co-resident {r.rid} disturbed by the cancel"


# ---------------------------------------------------------------------------
# graceful admission: pool saturation re-preempts instead of crashing
# ---------------------------------------------------------------------------

def test_saturated_pool_repreempts_instead_of_crashing():
    """Physical exhaustion the scheduler's TOKEN accounting cannot see:
    page-granularity rounding. Ten 17-token prompts are 170 tokens —
    comfortably under the planner's (n_pages-8)*page capacity of 192 —
    but each prompt is one token into its second page, so backing all
    ten takes 20 physical pages and the pool only has 19 (one is the
    reserved scratch page). The dispatch-phase pre-flight (`_back_plan`)
    must drop the unbackable chunk and re-preempt it to waiting
    (`notify_pool_exhausted` → recompute debt, FCFS requeue) instead of
    the old hard RuntimeError; the preempted session finishes once a
    co-resident frees its pages, and every stream equals the ample-pool
    run bit-for-bit."""
    cfg = get_config("llama3.2-1b", tiny=True)
    n, plen = 10, 17  # 1 token past a page boundary, per session

    def run(n_pages, max_steps=200):
        eng = _engine("vllm", cfg=cfg, n_pages=n_pages, max_model_len=64)
        cl = InferCeptClient(eng)
        hs = [cl.submit(
            [(100 + 7 * i + j) % cfg.vocab_size for j in range(plen)],
            max_new_tokens=8) for i in range(n)]
        steps = 0
        while not all(h.done for h in hs) and steps < max_steps:
            cl.poll(max_steps=1)
            steps += 1
        assert all(h.state == "finished" for h in hs), \
            f"n_pages={n_pages} stalled: {[h.state for h in hs]}"
        return eng, [tuple(cl.token_ids(h)) for h in hs]

    ample_eng, ample = run(128)
    assert ample_eng.sched.stats.pool_preempts == 0
    tight_eng, tight = run(20)
    assert tight_eng.sched.stats.pool_preempts > 0, \
        "pool never saturated — shrink n_pages"
    assert tight == ample, "pool preemption changed a token stream"
    assert _ledger_balanced(tight_eng)
    assert _leak_free(tight_eng)


# ---------------------------------------------------------------------------
# simulator mirror
# ---------------------------------------------------------------------------

def test_sim_mirror_cancel_and_fail():
    from repro.core import CostModel
    from repro.sim import simulate
    from repro.serving.workloads import make_workload
    from repro.utils.hw import A100
    cost = CostModel(cfg=get_config("gpt-j-6b"), chip=A100, n_chips=1)
    reqs = make_workload(seed=3, n_requests=8, rate_rps=2.0, max_ctx=400)
    base = simulate(copy.deepcopy(reqs), POLICIES["infercept"], cost)
    assert len(base.finished) == 8
    assert base.ledger.causes["cancelled"] == 0.0
    assert base.ledger.causes["tool_failed"] == 0.0
    # cancel rid 0 after 3 output tokens; rid 1's first interception
    # (seg_idx=1 at dispatch) resolves as a terminal failure
    r = simulate(copy.deepcopy(reqs), POLICIES["infercept"], cost,
                 cancel_at={0: 3}, fail_at={1: 1})
    assert r.cancelled == 1 and r.failed == 1
    assert len(r.finished) == 6
    assert {q.rid for q in r.finished} == set(range(8)) - {0, 1}
    assert r.ledger.causes["cancelled"] > 0.0
    assert r.ledger.causes["tool_failed"] > 0.0
    assert r.stats.cancellations == 1 and r.stats.tool_failures == 1
    tot = sum(r.ledger.causes.values())
    assert abs(tot - r.ledger.total_check) <= 1e-6 * max(1.0, tot)
