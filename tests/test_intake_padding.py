"""Engine intake refuses prompts longer than max_model_len, and every
model dispatch counts its bucket padding (step_tokens, pad_tokens,
pad_rows) from the plan's rows and tokens."""
import pytest

from repro.configs import get_config
from repro.core import POLICIES
from repro.core.request import Interception, Request, Segment
from repro.serving.engine import Engine
from repro.serving.session import InferCeptClient, RejectedEvent


def _engine(policy="infercept", **kw):
    cfg = get_config("llama3.2-1b", tiny=True)
    return Engine(cfg, POLICIES[policy], page_size=16, n_pages=64,
                  max_model_len=256, seed=0, **kw)


def test_prompt_over_max_model_len_is_refused():
    eng = _engine()
    cl = InferCeptClient(eng)
    before = dict(eng.metrics.counters)
    h = cl.submit(list(range(257)), max_new_tokens=4)
    assert h.state == "rejected"
    assert [(type(e), e.reason) for e in eng.events] \
        == [(RejectedEvent, "too_long")]
    after = dict(eng.metrics.counters)
    assert after.pop("engine_sessions_rejected") \
        == before.pop("engine_sessions_rejected") + 1
    assert after.pop("engine_rejected_too_long") \
        == before.pop("engine_rejected_too_long") + 1
    assert after == before
    assert not eng._pending_arrivals and not eng.kv and not eng.sched.live
    assert h.rid not in cl.handles
    # a prompt at the cap is admitted and served
    ok = cl.submit(list(range(240)), max_new_tokens=4)
    cl.poll()
    assert ok.state == "finished"
    assert eng.counters["rejected_too_long"] == 1


def _requests(prompts, gen=3, intercept=False):
    """Requests arriving together; with ``intercept`` each pauses for a
    tool call after ``gen`` tokens and then generates ``gen`` more."""
    def segments():
        if not intercept:
            return [Segment(gen, None)]
        return [Segment(gen, Interception(kind="math", duration=0.5,
                                          returned_tokens=5)),
                Segment(gen, None)]
    return [Request(rid=i, arrival=0.0, prompt_len=n, segments=segments())
            for i, n in enumerate(prompts)]


def _bucket(n):
    return 1 << (n - 1).bit_length()


@pytest.mark.parametrize("policy", ["infercept", "vllm"])
def test_fused_step_counts_bucket_padding(monkeypatch, policy):
    """Every fused step adds its token and row bucket padding. The vllm
    case pauses every request on a tool call and discards its context, so
    its recompute chunks are padded in the same steps as the decodes."""
    eng = _engine(policy=policy)
    seen = []
    real = eng._dispatch_mixed

    def counted(plan):
        c0 = {k: eng.counters[k] for k in ("step_tokens", "pad_tokens",
                                           "pad_rows")}
        out = real(plan)
        if out is not None:
            B = len(out[0])
            N = sum(n for _, _, _, n, _ in out[0])
            got = {k: eng.counters[k] - v for k, v in c0.items()}
            assert got == {"step_tokens": _bucket(N),
                           "pad_tokens": _bucket(N) - N,
                           "pad_rows": _bucket(B) - B}
            seen.append((B, N))
        return out

    monkeypatch.setattr(eng, "_dispatch_mixed", counted)
    for r in _requests([20, 30, 7], intercept=policy == "vllm"):
        eng.add_request(r)
    eng.run()
    # the first step prefills all three prompts: B 3 -> 4, N 57 -> 64;
    # then decode steps of B 3 -> 4, N 3 -> 4
    assert seen[0] == (3, 57)
    assert eng.counters["step_tokens"] == sum(_bucket(n) for _, n in seen)
    assert eng.counters["pad_tokens"] == sum(
        _bucket(n) - n for _, n in seen)
    assert eng.counters["pad_rows"] == sum(_bucket(b) - b for b, _ in seen)
    if policy == "vllm":
        # discard on every intercept: each context came back as a padded
        # recompute chunk
        assert eng.sched.stats.recompute_tokens > 0
        assert any(n > b for b, n in seen[1:])
