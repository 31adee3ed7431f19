"""The Qwen2-72B stage's architecture at a toy size on the CPU: GQA with
G = 8 (16 query heads over 2 KV heads of 128, so bfloat16 pools take the
ragged kernel's head-pair loader), QKV bias, a 512-row vocabulary slice,
no host KV space and a pool tight enough that the InferCept policy
discards contexts and recomputes them. Served greedy tokens are the plain
reference's argmax; the float8 control still fails the toy's limit."""
from __future__ import annotations

import contextlib
import copy
import functools
from unittest import mock

import numpy as np
import pytest

import tiny
from harness import check, setup

# the served path computes in bfloat16 (weights, KV, activations), the
# reference in float32; the toy cell's sound runs read ~0.01-0.06
TOL = 0.05

CONFIG = copy.deepcopy(tiny.CONFIG)
CONFIG.update(hidden_size=256, intermediate_size=512,
              num_attention_heads=16, num_key_value_heads=2, head_dim=128,
              attention_bias=True, rope_theta=1e6)
# 27 pages hold (27 - 8) x 16 = 304 tokens: the three sessions' contexts
# (about 150, 110 and 90 tokens before their returns) cannot all stay
CONFIG["serving"].update(max_model_len=512, n_pages=27, host_kv_tokens=0)


def _pallas_interpreted():
    """The served path through the Pallas kernels in interpret mode, as on
    the chip (the CPU otherwise runs their jnp mirrors)."""
    from repro.kernels import ops
    from repro.models import attention
    return [mock.patch.object(attention, "_paged_use_pallas", lambda: True),
            mock.patch.object(ops, "ragged_paged_attention_op",
                              functools.partial(ops.ragged_paged_attention_op,
                                                interpret=True)),
            mock.patch.object(ops, "kv_append_op",
                              functools.partial(ops.kv_append_op,
                                                interpret=True))]


def serve(seed: int = 5, control: bool = False):
    from repro.core.request import InterceptDirective, SamplingParams
    from repro.serving.session import (InferCeptClient, InterceptEvent,
                                       TokenEvent)
    from harness.loop import _resume_kind
    conf = copy.deepcopy(CONFIG)
    params = setup.make_weights(conf, seed)
    eng = setup.build_engine(setup.model_config(conf, "gqa8"), params,
                             conf["serving"])
    assert eng.sched.cpu_capacity == 0
    client = InferCeptClient(eng)
    rng = np.random.default_rng(seed)
    served, kinds, handles = {}, {}, []
    for n in (140, 100, 80):
        stage = [0]

        def detect(req, tok, now, stage=stage):
            if req.gen_in_seg < 5:
                return None
            stage[0] += 1
            if stage[0] > 2:
                return "finish"
            return InterceptDirective(kind="qa", duration_hint=0.7,
                                      reason="detector")
        h = client.submit(rng.integers(0, 512, n).tolist(),
                          SamplingParams(), detector=detect)
        handles.append(h)
        kinds[h.rid], served[h.rid] = "new", []
    while not all(h.finished for h in handles):
        events = client.poll()
        for ev in events:
            if isinstance(ev, TokenEvent):
                served[ev.rid].append((ev.index, ev.token_id, kinds[ev.rid]))
        for ev in events:
            if isinstance(ev, InterceptEvent):
                h = client.handles[ev.rid]
                kinds[ev.rid] = _resume_kind(h.request)
                client.resume(h, rng.integers(0, 512, 6).tolist())
    samples = [check.Sample(h.rid, client.token_ids(h), served[h.rid])
               for h in handles]
    return eng.sched.stats, check.compare(params, conf, samples,
                                          control=control)


@pytest.fixture(scope="module")
def served():
    with contextlib.ExitStack() as stack:
        for cm in _pallas_interpreted():
            stack.enter_context(cm)
        return serve(), serve(control=True)[1]


def test_served_tokens_are_the_references_argmax(served):
    (stats, res), _ = served
    # the pool forced contexts out (evictions, and stalls behind a
    # resumed head) and their tokens were recomputed before serving on
    assert stats.discards > 0 and stats.recompute_tokens > 0, stats
    assert res["stream_errors"] == 0
    assert res["tokens"] == 3 * 3 * 5
    assert res["max_gap"] <= TOL, res


def test_float8_control_fails_the_limit(served):
    (_, sound), control = served
    limits = tiny.cell().limits
    assert sound["max_gap"] <= TOL < limits["max_gap"] < control["max_gap"], \
        control
    assert check.verdict(control, limits)[0] is False
