"""The plain reference against the served path, at a toy size on the CPU:
served greedy tokens are the reference's argmax after a prefill in
chunks, in decode, after a swap-out and swap-in, and after a discard and
its recompute."""
from __future__ import annotations

import copy

import numpy as np
import pytest

import tiny
from harness import check, setup
from harness.loop import _resume_kind

# a greedy token's reference logit lies this far at most below the
# reference's best: the served path computes in bfloat16 (weights, KV and
# activations), the reference in float32
TOL = 0.05


def serve(policy: str, seed: int = 3, control: bool = False):
    from repro.core.request import InterceptDirective, SamplingParams
    from repro.serving.session import InterceptEvent, TokenEvent
    from repro.serving.session import InferCeptClient
    conf = copy.deepcopy(tiny.CONFIG)
    conf["serving"].update(max_model_len=1024, n_pages=160)
    params = setup.make_weights(conf, seed)
    eng = setup.build_engine(setup.model_config(conf, "tiny"), params,
                             conf["serving"], policy=policy)
    client = InferCeptClient(eng)
    rng = np.random.default_rng(seed)
    # prompts of 700 tokens prefill in two chunks of the 512-token budget
    lengths = [700, 40, 120]
    kinds = {}
    served = {}
    handles = []
    for n in lengths:
        stage = [0]

        def detect(req, tok, now, stage=stage):
            if req.gen_in_seg < 6:
                return None
            stage[0] += 1
            if stage[0] > 2:
                return "finish"
            return InterceptDirective(kind="qa", duration_hint=30.0,
                                      reason="detector")
        h = client.submit(rng.integers(0, 512, n).tolist(),
                          SamplingParams(), detector=detect)
        handles.append(h)
        kinds[h.rid] = "new"
        served[h.rid] = []
    while not all(h.finished for h in handles):
        events = client.poll()
        for ev in events:
            if isinstance(ev, TokenEvent):
                served[ev.rid].append((ev.index, ev.token_id, kinds[ev.rid]))
        for ev in events:
            if isinstance(ev, InterceptEvent):
                h = client.handles[ev.rid]
                kinds[ev.rid] = _resume_kind(h.request)
                client.resume(h, rng.integers(0, 512, 5).tolist())
    samples = [check.Sample(h.rid, client.token_ids(h), served[h.rid])
               for h in handles]
    return check.compare(params, conf, samples, control=control)


@pytest.mark.parametrize("policy,kind", [("preserve", "preserve"),
                                         ("swap", "swap"),
                                         ("vllm", "discard")])
def test_served_tokens_are_the_references_argmax(policy, kind):
    res = serve(policy)
    assert res["stream_errors"] == 0
    assert res["by_kind"]["new"]["tokens"] >= 3 * 6
    assert res["by_kind"][kind]["tokens"] >= 3 * 6
    assert res["max_gap"] <= TOL, res


def test_float8_control_fails_the_limit():
    """The control: the reference in float8_e4m3 put in the program's
    place reads gaps far above the served path's, and above the toy
    cell's limit, so the verdict is false. (At the toy's width it reads
    0.17-0.56; at the benchmark cells' widths it reads over their limit,
    PERF.md section 6.)"""
    res = serve("infercept", control=True)
    limits = tiny.cell().limits
    assert res["program_max_gap"] <= TOL < limits["max_gap"] \
        < res["max_gap"], res
    assert check.verdict(res, limits)[0] is False
