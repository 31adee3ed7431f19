"""Speculative resume past intercepts (DESIGN.md §14).

The differential pins:

  * ``speculate=False`` (the default) is a no-op: streams are the
    baseline's, bit-for-bit, on every policy;
  * speculation ON with a perfect predictor grafts the fork on resume —
    the returned-token re-prefill is skipped (prefill/decode token
    conservation against baseline), streams still bit-identical (the
    fork's tokens are keyed by (seed, position), so acceptance moves them
    earlier in virtual time without changing them);
  * speculation ON with a wrong predictor rejects every fork: the
    baseline resume path runs bit-identically and the fork's pinned
    bytes land in the ledger's ``speculation_wasted`` cause;
  * a fork runs through the fused dispatch as a one-entry batch: it
    writes only its own pages and leaves the served step's counters
    alone;
  * the session API surfaces per-intercept outcomes
    (``SessionHandle.speculation``), and the analytic simulator mirrors
    the same accept/reject accounting.
"""
import copy

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import POLICIES
from repro.core.request import InterceptDirective
from repro.serving.api_executor import (OracleToolResultPredictor,
                                        TemplateToolResultPredictor)
from repro.serving.engine import Engine
from repro.serving.session import InferCeptClient
from repro.serving.workloads import make_agent_workload

ALL_POLICIES = ["preserve", "vllm", "swap", "infercept"]


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


@pytest.fixture(scope="module")
def spec_diff():
    cfg = get_config("llama3.2-1b", tiny=True)
    reqs = _workload(cfg)
    base, accept, reject = {}, {}, {}
    for name in ALL_POLICIES:
        base[name] = _run(cfg, reqs, name, speculate=False)
        accept[name] = _run(cfg, reqs, name, speculate=True,
                            predictor=OracleToolResultPredictor(
                                cfg.vocab_size))
        reject[name] = _run(cfg, reqs, name, speculate=True,
                            predictor=TemplateToolResultPredictor(
                                {"search": [1, 2, 3], "math": [4, 5],
                                 "chatbot": [7], "qa": [9, 9]}))
    return cfg, reqs, base, accept, reject


def test_speculation_disabled_without_predictor():
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = Engine(cfg, POLICIES["infercept"], page_size=16, n_pages=64,
                 max_model_len=256)
    assert eng.speculate is False
    # opting in without a predictor stays off
    eng = Engine(cfg, POLICIES["infercept"], page_size=16, n_pages=64,
                 max_model_len=256, speculate=True)
    assert eng.speculate is False


def test_streams_bit_identical_across_speculation_modes(spec_diff):
    """The headline pin: default-off, all-accept, and all-reject runs emit
    identical token streams on every policy — speculation can only move
    compute earlier in virtual time, never change the stream."""
    _, _, base, accept, reject = spec_diff
    ref = base["preserve"][0]
    for name in ALL_POLICIES:
        assert base[name][0] == ref, name
        assert accept[name][0] == ref, f"accept-path {name} diverged"
        assert reject[name][0] == ref, f"reject-path {name} diverged"


def test_accepted_forks_skip_reprefill(spec_diff):
    """With a perfect predictor every validated fork grafts; the returned
    tokens the baseline re-prefills after resume were already computed on
    the fork, so baseline prefill = spec prefill + fork prefill, and the
    same conservation holds for decode."""
    _, _, base, accept, _ = spec_diff
    for name in ALL_POLICIES:
        eb, ea = base[name][1], accept[name][1]
        c = ea.counters
        assert c["spec_forks"] > 0 and c["spec_accepted"] > 0, name
        assert c["spec_rejected"] == 0 and c["spec_killed"] == 0, name
        assert c["spec_accepted"] == c["spec_forks"], name
        assert c["spec_prefill_tokens"] > 0
        # the fork prefilled the returned tokens the baseline re-prefills
        # after resume; under discard-style policies a graft additionally
        # voids the WHOLE-context recompute debt, so baseline prefill
        # exceeds spec prefill by AT LEAST the fork's own prefill — and
        # exactly by it under preserve (nothing else to skip)
        assert c["prefill_tokens"] + c["spec_prefill_tokens"] <= \
            eb.counters["prefill_tokens"], name
        if name == "preserve":
            assert c["prefill_tokens"] + c["spec_prefill_tokens"] == \
                eb.counters["prefill_tokens"]
        assert c["decode_tokens"] + c["spec_decode_tokens"] == \
            eb.counters["decode_tokens"], name
        # grafted = one seed per accepted fork + every fork-decoded token
        assert c["spec_grafted_tokens"] == \
            c["spec_accepted"] + c["spec_decode_tokens"], name
        # nothing recomputed that baseline did not, and no waste charged
        assert ea.sched.stats.recompute_tokens <= \
            eb.sched.stats.recompute_tokens, name
        assert ea.ledger.causes["speculation_wasted"] == 0.0, name


# the served step's counters, which a fork's dispatch must leave alone
STEP_COUNTERS = ("device_dispatches", "mixed_iterations", "step_tokens",
                 "pad_tokens", "pad_rows", "prefill_tokens", "decode_tokens",
                 "prefill_bytes", "decode_bytes", "logit_bytes",
                 "attn_grid_steps", "attn_live_steps")


def test_fork_dispatch_writes_own_pages_and_no_step_counters():
    """A fork step is a one-entry fused dispatch: its prefill and its
    decodes add only to spec_prefill_tokens / spec_decode_tokens, and the
    only pool pages it changes are pages the fork holds alone."""
    cfg = get_config("llama3.2-1b", tiny=True)
    reqs = _workload(cfg)
    eng = Engine(cfg, POLICIES["preserve"], page_size=16, n_pages=128,
                 max_model_len=256, seed=0, speculate=True, spec_tokens=4,
                 predictor=OracleToolResultPredictor(cfg.vocab_size))
    for r in copy.deepcopy(reqs):
        eng.add_request(r)
    seen = {"prefill": 0, "decode": 0}
    real = eng._spec_advance

    def checked(fork):
        before = {k: eng.counters[k] for k in STEP_COUNTERS}
        spec0 = (eng.counters["spec_prefill_tokens"],
                 eng.counters["spec_decode_tokens"])
        pools0 = [np.asarray(x).copy() for x in jax.tree.leaves(eng.pools)]
        emitted, n_tok = fork.emitted, len(fork.st.tokens)
        n = len(fork.predicted) if emitted == 0 else 1
        ok = real(fork)
        assert {k: eng.counters[k] for k in STEP_COUNTERS} == before
        if not ok:
            return ok
        assert fork.emitted == emitted + 1
        assert len(fork.st.tokens) == n_tok + 1
        kind = "prefill" if emitted == 0 else "decode"
        assert (eng.counters["spec_prefill_tokens"] - spec0[0],
                eng.counters["spec_decode_tokens"] - spec0[1]) == \
            ((n, 0) if kind == "prefill" else (0, 1))
        seen[kind] += 1
        others = {e[1] for rid, st in eng.kv.items() for e in st.pages
                  if e is not None and e[0] == "dev"}
        own = {pid for _, pid in fork.st.pages} - others
        changed = set()
        for old, new in zip(pools0, jax.tree.leaves(eng.pools)):
            diff = np.asarray(new) != old      # (layers, n_pages, ...)
            changed |= set(np.nonzero(diff.reshape(
                diff.shape[0], diff.shape[1], -1).any(axis=(0, 2)))[0])
        assert changed and changed <= own, (changed, own)
        return ok

    eng._spec_advance = checked
    fin = eng.run()
    assert len(fin) == len(reqs)
    assert seen["prefill"] > 0 and seen["decode"] > 0, seen
    assert eng.counters["device_dispatches"] == \
        eng.counters["mixed_iterations"]


def test_rejected_forks_charge_speculation_waste(spec_diff):
    """A wrong predictor rejects at validation: the baseline resume runs
    unchanged (prefill totals equal baseline) and the fork's pinned
    byte-seconds are charged to the ``speculation_wasted`` cause."""
    _, _, base, _, reject = spec_diff
    charged = False
    for name in ALL_POLICIES:
        eb, er = base[name][1], reject[name][1]
        c = er.counters
        assert c["spec_accepted"] == 0, name
        # every fork that reached validation was rejected; nothing skipped
        assert c["prefill_tokens"] == eb.counters["prefill_tokens"], name
        assert c["decode_tokens"] == eb.counters["decode_tokens"], name
        if c["spec_rejected"]:
            assert er.ledger.causes["speculation_wasted"] > 0.0, name
            charged = True
    assert charged, "no policy ever rejected a fork — vacuous test"


def test_ledger_totals_include_speculation(spec_diff):
    """charge_speculation feeds the same total the other causes do."""
    _, _, _, _, reject = spec_diff
    eng = reject["infercept"][1]
    led = eng.ledger
    assert led.causes["speculation_wasted"] == pytest.approx(
        sum(led.causes.values()) - sum(
            v for k, v in led.causes.items()
            if k != "speculation_wasted"))
    assert led.causes["speculation_wasted"] <= led.total_check + 1e-6


def test_session_handle_surfaces_speculation():
    """Caller-owned intercepts speculate too: a template predictor that
    matches the caller's eventual resume grafts (accepted entry on the
    handle), one that mismatches rejects — both visible via
    SessionHandle.speculation / spec_accept_rate."""
    cfg = get_config("llama3.2-1b", tiny=True)

    def run(resume_ids):
        eng = Engine(cfg, POLICIES["infercept"], page_size=16, n_pages=64,
                     max_model_len=256, seed=0, speculate=True,
                     predictor=TemplateToolResultPredictor(
                         {"qa": [7, 8, 9]}))
        cl = InferCeptClient(eng)

        def det(req, tid, now):
            if req.output_tokens == 6 and req.seg_idx == 0:
                return InterceptDirective("qa", 0.4, reason="detector")
            return None

        h = cl.submit(list(range(24)), detector=det, max_new_tokens=16)
        cl.poll()
        assert h.state == "intercepted"
        n_before = len(cl.token_ids(h))
        cl.resume(h, resume_ids, delay=0.4)
        cl.poll()
        assert h.finished
        stream = cl.token_ids(h)
        assert stream[n_before:n_before + len(resume_ids)] == resume_ids
        assert h.request.output_tokens == 16
        return h, stream

    h_acc, s_acc = run([7, 8, 9])      # matches the template: graft
    assert [e["accepted"] for e in h_acc.speculation] == [True]
    assert h_acc.speculation[0]["kind"] == "qa"
    assert h_acc.speculation[0]["grafted_tokens"] >= 1
    assert h_acc.spec_accept_rate == 1.0

    h_rej, s_rej = run([1, 2, 3])      # mismatch: reject, baseline resume
    assert [e["accepted"] for e in h_rej.speculation] == [False]
    assert h_rej.spec_accept_rate == 0.0

    # the two runs agree everywhere except the caller-chosen returned ids
    # (and the continuation they condition) — and a no-speculation run
    # with the same resume ids is bit-identical to the accepted run
    eng0 = Engine(cfg, POLICIES["infercept"], page_size=16, n_pages=64,
                  max_model_len=256, seed=0)
    cl0 = InferCeptClient(eng0)

    def det0(req, tid, now):
        if req.output_tokens == 6 and req.seg_idx == 0:
            return InterceptDirective("qa", 0.4, reason="detector")
        return None

    h0 = cl0.submit(list(range(24)), detector=det0, max_new_tokens=16)
    cl0.poll()
    cl0.resume(h0, [7, 8, 9], delay=0.4)
    cl0.poll()
    assert cl0.token_ids(h0) == s_acc
    assert h0.speculation == [] and h0.spec_accept_rate is None


def test_simulator_mirrors_speculation_accounting():
    from repro.core import CostModel
    from repro.serving.workloads import make_workload
    from repro.sim.simulator import simulate
    from repro.utils.hw import A100

    cost = CostModel(cfg=get_config("gpt-j-6b"), chip=A100, n_chips=1)
    reqs = make_workload(seed=3, n_requests=20, rate_rps=2.0)
    base = simulate(copy.deepcopy(reqs), POLICIES["infercept"], cost)
    assert base.spec_forks == 0 and base.spec_accepted == 0
    assert len(base.finished) == 20

    vocab = 50_000
    acc = simulate(copy.deepcopy(reqs), POLICIES["infercept"], cost,
                   speculate=True,
                   predictor=OracleToolResultPredictor(vocab),
                   spec_vocab=vocab)
    assert len(acc.finished) == 20
    assert acc.spec_forks > 0
    assert acc.spec_accepted == acc.spec_forks and acc.spec_rejected == 0
    assert acc.spec_grafted_tokens >= acc.spec_accepted
    assert acc.ledger.causes["speculation_wasted"] == 0.0
    # grafting can only remove re-prefill work from the clock
    assert acc.sim_time <= base.sim_time + 1e-9

    rej = simulate(copy.deepcopy(reqs), POLICIES["infercept"], cost,
                   speculate=True,
                   predictor=TemplateToolResultPredictor(
                       {"search": [1], "math": [2], "chatbot": [3],
                        "qa": [4], "code": [5]}),
                   spec_vocab=vocab)
    assert rej.spec_accepted == 0
    if rej.spec_forks:
        assert rej.ledger.causes["speculation_wasted"] > 0.0
    # rejected-fork runs reproduce the baseline clock exactly
    assert rej.sim_time == pytest.approx(base.sim_time)
    assert rej.normalized_latency() == \
        pytest.approx(base.normalized_latency())
