"""Differential + lifecycle harness for the first-class session API
(DESIGN.md §11).

The headline pins:

  * scripted-over-session equivalence — replaying the scripted Table-1
    workloads through InferCeptClient/ScriptedClient produces token
    streams bit-identical to the legacy closed-loop Engine.run(), across
    all four scheduling policies;
  * caller-driven resume — an out-of-band resume with caller-chosen
    returned tokens lands verbatim in the context and generation
    continues;
  * sampling determinism — temperature/top-k streams under a fixed
    per-request seed are identical across policies (noise is keyed by
    (seed, position) only), and SamplingParams(temperature=0) equals the
    legacy argmax streams.
"""
import copy

import pytest

from repro.configs import get_config
from repro.core import POLICIES
from repro.core.request import InterceptDirective, SamplingParams
from repro.serving.api_executor import (VirtualTimeToolExecutor,
                                        WallClockToolExecutor)
from repro.serving.engine import Engine
from repro.serving.session import (FinishEvent, InferCeptClient,
                                   InterceptEvent, ScriptedClient,
                                   TokenEvent)
from repro.serving.workloads import make_agent_workload, make_workload

ALL_POLICIES = ["preserve", "vllm", "swap", "infercept"]


def _mixed_workload(cfg):
    """Agent sessions (explicit prompt ids) plus Table-1 scripted requests
    (engine-synthesized prompt ids), so the replay covers both prompt
    construction paths."""
    reqs = make_agent_workload(
        seed=5, n_sessions=2, rate_rps=2.0, vocab=cfg.vocab_size,
        n_templates=2, system_prompt_len=50, turns=(2, 2), turn_gap_s=3.0,
        hist_per_turn=12, prefix_share=0.75, gen_tokens=(8, 3),
        final_gen=(8, 3), ret_tokens=(6, 2), max_tool_calls=2, max_ctx=240)
    from repro.launch.serve import scale_to_budget
    extra = scale_to_budget(
        make_workload(seed=3, n_requests=2, rate_rps=1.0, max_ctx=200),
        200, prompt_cap=32, gen_cap=8, ret_cap=6, max_segments=2)
    for i, r in enumerate(extra):
        r.rid = len(reqs) + i
    return reqs + extra


def _engine(cfg, policy, **kw):
    kw.setdefault("page_size", 16)
    kw.setdefault("n_pages", 128)
    kw.setdefault("max_model_len", 256)
    kw.setdefault("seed", 0)
    return Engine(cfg, POLICIES[policy], **kw)


@pytest.fixture(scope="module")
def sess_diff():
    cfg = get_config("llama3.2-1b", tiny=True)
    reqs = _mixed_workload(cfg)
    # legacy closed loop: one run suffices as the oracle — cross-policy
    # identity of the legacy engine is already pinned by
    # tests/test_engine.py and tests/test_paged_engine.py
    eng = _engine(cfg, "vllm")
    for r in copy.deepcopy(reqs):
        eng.add_request(r)
    fin = eng.run()
    assert fin.drained and len(fin) == len(reqs)
    oracle = {r.rid: eng.generated_text(r) for r in fin}

    session, engines = {}, {}
    for name in ALL_POLICIES:
        e = _engine(cfg, name)
        session[name] = ScriptedClient(e).replay(copy.deepcopy(reqs))
        engines[name] = e
    return cfg, reqs, oracle, session, engines


@pytest.mark.slow
def test_scripted_sessions_match_legacy_streams(sess_diff):
    """The §11 equivalence pin: the scripted workloads replayed through
    the session API emit the legacy closed-loop engine's exact token
    streams — every policy."""
    _, _, oracle, session, _ = sess_diff
    for key, streams in session.items():
        assert streams == oracle, \
            f"session replay {key} diverged from the legacy engine"


def test_session_interceptions_really_happened(sess_diff):
    """The equivalence must not be vacuous: the replay exercised real
    interceptions, and the runs kept the one-dispatch-per-iteration
    property with the session lifecycle in the loop."""
    _, reqs, _, _, engines = sess_diff
    n_int = sum(1 for r in reqs for s in r.segments if s.interception)
    assert n_int > 0
    for name, eng in engines.items():
        assert eng.sched.stats.decode_tokens > 0
        done = {r.rid: r for r in eng.finished}
        assert sum(sum(1 for s in done[r.rid].segments if s.interception)
                   for r in reqs) == n_int, name
        assert eng.counters["device_dispatches"] == \
            eng.counters["mixed_iterations"], name
    # per-request latency metrics flow through the session path
    for r in engines["infercept"].finished:
        m = r.latency_metrics()
        assert m["output_tokens"] > 0 and m["ttft"] is not None


def test_caller_driven_resume_out_of_band():
    """A detector pauses the session mid-generation; the caller resumes it
    with hand-picked token ids, which must land verbatim (and in order) in
    the context before generation continues to the finish."""
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = _engine(cfg, "infercept", n_pages=64)
    cl = InferCeptClient(eng)

    def det(req, tid, now):
        if req.output_tokens == 6 and req.seg_idx == 0:
            return InterceptDirective("qa", 0.4, reason="detector")
        return None

    h = cl.submit(list(range(24)), detector=det, max_new_tokens=16)
    evs = cl.poll()
    assert h.state == "intercepted"
    iev = [e for e in evs if isinstance(e, InterceptEvent)][0]
    assert iev.reason == "detector" and iev.caller_owned
    assert iev.trigger_token_id is not None
    # the trigger was consumed, not committed: exactly output_tokens (6)
    # generated ids joined the prompt before the pause
    n_before = len(cl.token_ids(h))
    assert n_before == 24 + 6
    cl.resume(h, [7, 8, 9], delay=0.4)
    evs = cl.poll()
    assert h.finished and any(isinstance(e, FinishEvent) for e in evs)
    stream = cl.token_ids(h)
    assert stream[n_before:n_before + 3] == [7, 8, 9]
    assert len(stream) > n_before + 3          # generation continued
    # the resume is processed at the first iteration boundary at/after its
    # due time, so the pause is the requested delay plus sub-iteration slack
    assert 0.4 <= h.request.paused_time < 0.45
    assert h.request.output_tokens == 16


def test_stop_token_detector_consumes_trigger():
    """Stop-token interception: the configured id pauses the session the
    moment it is sampled and is consumed by the runtime (never enters the
    context), mirroring a tool-call token."""
    cfg = get_config("llama3.2-1b", tiny=True)
    prompt = list(range(20))
    # learn which token a greedy session emits third
    eng = _engine(cfg, "vllm", n_pages=64)
    cl = InferCeptClient(eng)
    h = cl.submit(prompt, max_new_tokens=8)
    cl.poll()
    third = cl.token_ids(h)[len(prompt) + 2]

    eng2 = _engine(cfg, "vllm", n_pages=64)
    cl2 = InferCeptClient(eng2)
    h2 = cl2.submit(prompt, stop_tokens={third}, max_new_tokens=8,
                    kind="tool")
    evs = cl2.poll()
    iev = [e for e in evs if isinstance(e, InterceptEvent)][0]
    assert h2.state == "intercepted"
    assert iev.reason == "stop_token" and iev.trigger_token_id == third
    assert cl2.token_ids(h2)[len(prompt):].count(third) == 0
    cl2.resume(h2, [3, 1])
    cl2.finish(h2)
    cl2.poll()
    assert h2.finished


def test_explicit_intercept_at_first_boundary_and_tool_roundtrip():
    """client.intercept() before any generation fires at the prefill's
    first emitted token (the earliest boundary, popped from the stream);
    an attached WallClockToolExecutor round-trips the call
    automatically."""
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = _engine(cfg, "vllm", n_pages=64)
    cl = InferCeptClient(eng)
    seen = []

    def tool(call):
        seen.append(call)
        return [11, 12]

    h = cl.submit(list(range(16)), max_new_tokens=6,
                  tools=WallClockToolExecutor(tool))
    cl.intercept(h, duration_hint=0.2)
    cl.poll()
    assert h.finished
    assert len(seen) == 1 and seen[0].trigger_token_id is not None
    stream = cl.token_ids(h)
    # intercept fired before any token was committed: returned ids follow
    # the prompt immediately
    assert stream[16:18] == [11, 12]
    assert h.request.segments[0].gen_tokens == 0
    assert h.request.output_tokens == 6


def test_async_tool_runtime_does_not_stall_unrelated_sessions():
    """DESIGN.md §12: with an AsyncToolRuntime attached, a slow tool runs
    off-thread and unrelated sessions keep making progress while it is in
    flight. The tool itself blocks until the OTHER session has finished —
    with the legacy inline dispatch this would deadlock (the engine's
    step loop would be stuck inside the tool call, so the other session
    could never advance); off-thread it completes, the completion is
    injected through the resume queue, and both sessions drain."""
    import time as _time
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = _engine(cfg, "vllm", n_pages=96)
    cl = InferCeptClient(eng, tool_workers=2)
    assert eng.async_tools is not None
    other = {}

    def slow_tool(call):
        # worker thread: wait until the unrelated session finished (its
        # state is written by the engine thread during poll)
        deadline = _time.time() + 30.0
        while not other["handle"].finished:
            assert _time.time() < deadline, \
                "unrelated session stalled behind the in-flight tool"
            _time.sleep(0.005)
        return [5, 6, 7]

    def det(req, tid, now):
        if req.output_tokens == 3 and req.seg_idx == 0:
            return InterceptDirective("tool", 0.2, reason="detector")
        return None

    ha = cl.submit(list(range(20)), detector=det, max_new_tokens=10,
                   tools=WallClockToolExecutor(slow_tool))
    hb = cl.submit(list(range(30, 50)), max_new_tokens=12)
    other["handle"] = hb
    cl.poll()
    assert ha.finished and hb.finished
    # the unrelated session finished (in virtual time) while ha's tool was
    # still in flight, and the tool's pause overlapped engine-busy time
    assert hb.request.finish_time < ha.request.finish_time
    assert eng.counters["tool_seconds"] > 0
    assert eng.counters["overlapped_tool_seconds"] > 0
    stream = cl.token_ids(ha)
    assert [5, 6, 7] == stream[20 + 3:20 + 6]   # returned ids landed
    assert ha.request.output_tokens == 10
    cl.close()                                  # reclaim the pool threads


def test_async_tool_failure_fails_only_that_session():
    """DESIGN.md §15: a raising off-thread executor no longer takes the
    engine thread down. The worker's exception becomes a non-retryable
    ToolError, the owning session ends with a FailedEvent, and a
    co-resident session with a healthy executor drains to the exact
    stream it produces when the poisoned session never existed."""
    cfg = get_config("llama3.2-1b", tiny=True)

    def run(with_poisoned: bool):
        eng = _engine(cfg, "vllm", n_pages=64)
        cl = InferCeptClient(eng, tool_workers=1)

        def bad_tool(call):
            raise ValueError("tool exploded")

        def det(req, tid, now):
            if req.output_tokens == 2 and req.seg_idx == 0:
                return InterceptDirective("tool", 0.1, reason="detector")
            return None

        h = None
        if with_poisoned:
            h = cl.submit(list(range(16)), detector=det, max_new_tokens=8,
                          tools=WallClockToolExecutor(bad_tool))
        hb = cl.submit(list(range(30, 46)), max_new_tokens=10)
        cl.poll()
        stream = cl.token_ids(hb)
        cl.close()
        return eng, h, hb, stream

    eng, h, hb, stream = run(with_poisoned=True)
    assert h.state == "failed" and h.done and not h.finished
    assert h.error is not None and h.error.kind == "exception"
    assert not h.error.retryable
    assert "tool exploded" in h.error.message
    assert eng.counters["sessions_failed"] == 1
    # the blast radius stops at the poisoned session
    assert hb.finished and hb.request.output_tokens == 10
    _, _, _, clean = run(with_poisoned=False)
    assert stream == clean
    # teardown reclaimed every page the failed session held
    assert eng.ledger.causes["tool_failed"] > 0.0


def test_resume_and_rid_guardrails():
    """Lifecycle guardrails: a second resume for the same interception is
    rejected while the first is still queued; auto-allocated session rids
    avoid legacy requests still sitting in the pending-arrivals queue; and
    poll surfaces step exhaustion via EventBatch.drained — a truncated
    event stream is never silent."""
    from repro.core.request import Request, Segment
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = _engine(cfg, "vllm", n_pages=64)
    # legacy scripted request added directly; not admitted until t=5.0
    eng.add_request(Request(
        rid=0, arrival=5.0, prompt_len=8,
        segments=[Segment(gen_tokens=2, interception=None)]))
    cl = InferCeptClient(eng)
    h = cl.submit(list(range(16)), max_new_tokens=8)
    assert h.rid != 0                        # pending-arrival rid avoided
    cl.intercept(h, duration_hint=0.1)
    batch = cl.poll(max_steps=1)
    assert batch.drained is False            # exhaustion is surfaced
    assert cl.poll().drained is True
    assert h.state == "intercepted"
    with pytest.raises(ValueError):
        cl.resume(h, [])                     # empty resume rejected: the
    cl.resume(h, [1, 2])                     # trigger was consumed, so a
    with pytest.raises(ValueError):          # feed token is required
        cl.resume(h, [3, 4])                 # double resume rejected
    cl.poll()
    assert h.finished


def _sampled_run(cfg, policy, *, seed=11, temp=0.8, top_k=6, top_p=1.0):
    eng = _engine(cfg, policy, n_pages=96)
    cl = InferCeptClient(eng)
    tool = VirtualTimeToolExecutor(cfg.vocab_size, n_tokens=5, duration=0.3)

    def det(req, tid, now):
        if req.output_tokens == 5 and req.seg_idx == 0:
            return InterceptDirective("qa", 0.3, reason="detector")
        return None

    hs = [cl.submit(list(range(r, r + 20)),
                    SamplingParams(temperature=temp, top_k=top_k,
                                   top_p=top_p, seed=seed + r),
                    detector=det, max_new_tokens=14, tools=tool)
          for r in range(2)]
    cl.poll()
    assert all(h.finished for h in hs)
    return {h.rid: cl.token_ids(h) for h in hs}, eng


@pytest.mark.slow
def test_sampling_deterministic_across_policies_and_paths():
    """Temperature/top-k sampling under a fixed per-request seed: noise is
    keyed by (seed, position) only, so streams are bit-identical across
    every scheduling policy — the §6 equivalence property survives
    stochastic sampling. A different seed moves the stream."""
    cfg = get_config("llama3.2-1b", tiny=True)
    base, eng = _sampled_run(cfg, "vllm")
    # sampling stayed on device on the fused path: one dispatch per
    # iteration, ids-not-logits across the boundary
    assert eng.counters["device_dispatches"] == \
        eng.counters["mixed_iterations"]
    assert eng.counters["logit_bytes"] < 4 * 64 * \
        eng.counters["mixed_iterations"]
    for policy in ["infercept", "swap", "preserve"]:
        streams, _ = _sampled_run(cfg, policy)
        assert streams == base, f"sampled stream diverged under {policy}"
    other, _ = _sampled_run(cfg, "vllm", seed=999)
    assert other != base, "per-request seed had no effect"


@pytest.mark.slow
def test_top_p_deterministic_across_policies_and_paths():
    """Nucleus sampling rides the same (seed, position)-keyed seam: top-p
    streams are bit-identical across scheduling policies, and a binding
    threshold really changes the stream (vs top-k-only sampling with the
    same seed)."""
    cfg = get_config("llama3.2-1b", tiny=True)
    base, _ = _sampled_run(cfg, "vllm", top_k=0, top_p=0.3)
    for policy in ["infercept", "swap", "preserve"]:
        streams, _ = _sampled_run(cfg, policy, top_k=0, top_p=0.3)
        assert streams == base, f"top-p stream diverged under {policy}"
    full, _ = _sampled_run(cfg, "vllm", top_k=0, top_p=1.0)
    assert full != base, "top_p=0.3 did not bind (same stream as full)"


def test_top_p_nucleus_membership_and_disabled_identity():
    """Unit-level contract of the sample_tokens nucleus seam: every
    sampled id lies inside the numpy-computed smallest prefix of the
    temperature-scaled distribution reaching top_p (threshold token
    included), and top_p=1.0 leaves the top-k-only graph's output
    bit-identical."""
    import numpy as np
    import jax.numpy as jnp
    from repro.models import sample_tokens

    rng = np.random.default_rng(0)
    B, V, p = 8, 64, 0.3
    logits = rng.normal(size=(B, V)).astype(np.float32) * 3.0
    temps = np.full(B, 0.7, np.float32)
    seeds = np.arange(B, dtype=np.int32)
    poss = np.arange(10, 10 + B, dtype=np.int32)

    def sample(top_p):
        return np.asarray(sample_tokens(
            jnp.asarray(logits), jnp.asarray(temps),
            jnp.zeros(B, jnp.int32), jnp.full(B, top_p, jnp.float32),
            jnp.asarray(seeds), jnp.asarray(poss)))

    out = sample(p)
    for b in range(B):
        scaled = logits[b] / temps[b]
        probs = np.exp(scaled - scaled.max())
        probs /= probs.sum()
        order = np.argsort(-probs)
        cum = np.cumsum(probs[order])
        cut = int(np.searchsorted(cum, p)) + 1   # smallest prefix >= p
        nucleus = set(order[:cut].tolist())
        assert int(out[b]) in nucleus, \
            f"row {b}: sampled {out[b]} outside the top-p nucleus"
    # disabled filter: bit-identical to the top-k-only behavior
    assert np.array_equal(sample(1.0), sample(0.0))
    assert np.array_equal(sample(1.0), sample(-1.0))


def test_greedy_sampling_params_equal_legacy_argmax():
    """SamplingParams(temperature=0) is the legacy greedy oracle: streams
    equal a sampling=None session bit-for-bit (and the engine keeps the
    argmax-only compiled graph for such batches)."""
    cfg = get_config("llama3.2-1b", tiny=True)

    def run(sampling):
        eng = _engine(cfg, "vllm", n_pages=64)
        cl = InferCeptClient(eng)
        hs = [cl.submit(list(range(r, r + 18)), sampling,
                        max_new_tokens=10) for r in range(2)]
        cl.poll()
        assert all(h.finished for h in hs)
        return {h.rid: cl.token_ids(h) for h in hs}

    assert run(SamplingParams(temperature=0.0, top_k=0, seed=42)) == \
        run(None)
