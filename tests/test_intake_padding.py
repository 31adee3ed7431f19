"""Engine intake refuses prompts longer than max_model_len, and every
model dispatch counts its bucket padding (step_tokens, pad_tokens,
pad_rows) from the plan's rows and tokens."""
import copy

import pytest

from repro.configs import get_config
from repro.core import POLICIES
from repro.core.request import Request, Segment
from repro.serving.engine import Engine
from repro.serving.session import InferCeptClient, RejectedEvent


def _engine(**kw):
    cfg = get_config("llama3.2-1b", tiny=True)
    return Engine(cfg, POLICIES["infercept"], page_size=16, n_pages=64,
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


def _requests(prompts, gen=3):
    return [Request(rid=i, arrival=0.0, prompt_len=n,
                    segments=[Segment(gen, None)])
            for i, n in enumerate(prompts)]


def _bucket(n):
    return 1 << (n - 1).bit_length()


def test_fused_step_counts_bucket_padding(monkeypatch):
    eng = _engine()
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
    for r in _requests([20, 30, 7]):
        eng.add_request(r)
    eng.run()
    # the first step prefills all three prompts: B 3 -> 4, N 57 -> 64;
    # then three decode steps of B 3 -> 4, N 3 -> 4
    assert seen[0] == (3, 57)
    assert eng.counters["step_tokens"] == sum(_bucket(n) for _, n in seen)
    assert eng.counters["pad_tokens"] == 7 + sum(
        _bucket(n) - n for _, n in seen[1:])
    assert eng.counters["pad_rows"] == len(seen)


@pytest.mark.parametrize("policy", ["infercept", "vllm"])
def test_unfused_paths_count_bucket_padding(policy):
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = Engine(cfg, POLICIES[policy], page_size=16, n_pages=64,
                 max_model_len=256, seed=0, fused=False)
    for r in copy.deepcopy(_requests([20, 30, 7], gen=3)):
        eng.add_request(r)
    eng.run()
    # chunks pad to min(bucket, room left in the block table) alone in a
    # batch of one: 20 -> 32, 30 -> 32, 7 -> 8; then decode batches of 3
    # -> 4 rows
    chunk = (32 - 20) + (32 - 30) + (8 - 7)
    decode_steps = eng.counters["device_dispatches"] - 3
    assert decode_steps > 0
    assert eng.counters["decode_tokens"] == 3 * decode_steps
    assert eng.counters["step_tokens"] == 32 + 32 + 8 + 4 * decode_steps
    assert eng.counters["pad_tokens"] == chunk + 1 * decode_steps
    assert eng.counters["pad_rows"] == 1 * decode_steps
