"""Invariant-enforcement layer (DESIGN.md §16): the detectors must fire.

Two families:

  * injection tests — surgically corrupt a sanitized engine's page
    ownership or a request's lifecycle and assert the sanitizer reports
    exactly that corruption class with rid/page/site context;
  * identity tests — sanitize=True is observation-only: a chaos soak
    across policies × overlap × speculation runs with ZERO findings and
    streams bit-identical to sanitize=False, and the default engine
    carries no sanitizer state at all.

Plus unit tests for each static lint rule on synthetic files, and the
repo-clean pin (`python -m repro.analysis.lint src tests` exits 0).
"""
import copy
import textwrap

import pytest

from repro.analysis import lint
from repro.analysis.lifecycle import TRANSITIONS, IllegalTransition, LifecycleChecker
from repro.configs import get_config
from repro.core import POLICIES, CostModel
from repro.core.request import Phase, Request, SamplingParams, Segment
from repro.serving.api_executor import (ChaosToolExecutor,
                                        OracleToolResultPredictor,
                                        VirtualTimeToolExecutor)
from repro.serving.engine import Engine
from repro.serving.session import InferCeptClient
from repro.serving.workloads import make_workload
from repro.sim import simulate
from repro.utils.hw import A100

ALL_POLICIES = ["preserve", "vllm", "swap", "infercept"]


def _engine(policy="infercept", **kw):
    cfg = kw.pop("cfg", None) or get_config("llama3.2-1b", tiny=True)
    kw.setdefault("page_size", 16)
    kw.setdefault("n_pages", 128)
    kw.setdefault("max_model_len", 256)
    kw.setdefault("seed", 0)
    return Engine(cfg, POLICIES[policy], **kw)


def _run_some(eng, n_sessions=2, max_new=8, steps=None):
    """Submit a few sessions and step the engine until drained (or for
    ``steps`` iterations), returning the client."""
    cl = InferCeptClient(eng)
    for i in range(n_sessions):
        cl.submit([10 + i, 11 + i, 12 + i, 13 + i], max_new_tokens=max_new)
    if steps is None:
        cl.poll()
    else:
        for _ in range(steps):
            if not eng.step():
                break
    return cl


# ---------------------------------------------------------------------------
# sanitize=False: no sanitizer state, no schema enforcement
# ---------------------------------------------------------------------------

def test_default_engine_carries_no_sanitizer():
    eng = _engine()
    assert eng.sanitizer is None and eng._lifecycle_checker is None
    # the counters view carries no schema -> plain dict-speed writes
    assert eng.counters._schema is None
    _run_some(eng)
    # requests never grew a _lifecycle slot
    assert all("_lifecycle" not in r.__dict__
               for r in eng.finished)


def test_sanitized_counter_view_fails_fast_on_undeclared_key():
    eng = _engine(sanitize=True)
    with pytest.raises(KeyError, match="undeclared counter key"):
        eng.counters["bogus_key"] = 1  # lint: allow(undeclared-counter): intentionally-bogus key under test


# ---------------------------------------------------------------------------
# injection: each corruption class fires its detector
# ---------------------------------------------------------------------------

def test_injected_leak_detected():
    eng = _engine(sanitize=True)
    _run_some(eng)
    assert eng.sanitizer.findings == []          # clean run, clean report
    # allocate a page no table will ever own
    [pid] = eng.blocks.allocate(1)
    eng.sanitizer.audit("test-inject")
    leaks = [f for f in eng.sanitizer.findings if f.kind == "leak"]
    assert leaks and leaks[0].page == pid
    assert leaks[0].site == "test-inject"
    assert "1" in leaks[0].detail or "owner" in leaks[0].detail


def test_injected_double_free_detected():
    eng = _engine(sanitize=True)
    [pid] = eng.blocks.allocate(1)
    eng.blocks.free([pid])
    eng.blocks.free([pid])                       # would assert un-sanitized
    dfs = [f for f in eng.sanitizer.findings if f.kind == "double_free"]
    assert dfs and dfs[0].page == pid
    assert "test_analysis.py" in dfs[0].site     # faulting call site


def test_injected_stale_block_table_entry_detected():
    eng = _engine(sanitize=True)
    _run_some(eng, steps=4)                      # mid-flight: live tables
    rid, st = next((rid, st) for rid, st in eng.kv.items()
                   if any(e is not None and e[0] == "dev" for e in st.pages))
    pid = next(e[1] for e in st.pages if e is not None and e[0] == "dev")
    eng.blocks.free([pid])                       # yank a live page
    eng.sanitizer.audit("test-inject")
    uafs = [f for f in eng.sanitizer.findings if f.kind == "use_after_free"]
    assert uafs and any(f.page == pid for f in uafs)
    assert any(f.rid is not None and str(rid) in str(f.rid) for f in uafs)


def test_injected_unforked_cow_write_detected():
    # no cache, no speculation: _try_ensure_writable early-outs, so an
    # injected share on a decode target page survives to dispatch where
    # check_plan must flag the un-forked write
    eng = _engine(sanitize=True, prefix_cache=False)
    _run_some(eng, steps=4)
    rid, st = next((rid, st) for rid, st in eng.kv.items()
                   if any(e is not None and e[0] == "dev" for e in st.pages))
    pid = next(e[1] for e in st.pages if e is not None and e[0] == "dev")
    eng.blocks.fork([pid])                       # phantom co-owner
    for _ in range(3):                           # reach the next dispatch
        if any(f.kind == "cow_violation" for f in eng.sanitizer.findings):
            break
        if not eng.step():
            break
    cows = [f for f in eng.sanitizer.findings if f.kind == "cow_violation"]
    assert cows and cows[0].page == pid
    assert str(cows[0].rid) == str(rid) or cows[0].rid is not None


def test_injected_stale_scale_detected():
    """Quantized pools (DESIGN.md §17): a freed-and-recyclable page whose
    per-page quantization scales were NOT zeroed is corruption — the next
    occupant would quantize against the previous occupant's dynamic
    range. Inject exactly that and the audit must classify it."""
    eng = _engine(sanitize=True, kv_dtype="int8")
    _run_some(eng)
    eng.sanitizer.audit("pre-inject")
    assert eng.sanitizer.findings == []          # clean run, clean report
    pid = next(p for p in range(eng.blocks.n_pages)
               if eng.blocks.ref_count(p) == 0)
    pools = []                                   # resurrect a stale scale
    for entry in eng.pools:
        new_entry = {}
        for bk, pool in entry.items():
            if isinstance(pool, dict) and "k_scale" in pool:
                pool = dict(pool)
                pool["k_scale"] = pool["k_scale"].at[:, pid].set(0.25)
            new_entry[bk] = pool
        pools.append(new_entry)
    eng.pools = tuple(pools)
    eng.sanitizer.audit("test-inject")
    stale = [f for f in eng.sanitizer.findings if f.kind == "stale_scale"]
    assert stale and stale[0].page == pid
    assert stale[0].site == "test-inject"
    assert "scale" in stale[0].detail


def test_free_zeroes_scales_eagerly():
    """The runtime invariant the audit checks: the moment a page's
    refcount hits 0 its scale rows are zeroed (and counted)."""
    eng = _engine(sanitize=True, kv_dtype="int8")
    _run_some(eng)
    assert eng.counters["kv_quant_scale_reset_pages"] > 0
    assert eng._stale_scale_pages() == []


def test_injected_illegal_phase_transition_raises():
    req = Request(rid=7, arrival=0.0, prompt_len=2,
                  segments=[Segment(4, None)], prompt_tokens=[1, 2])
    req.__dict__["_lifecycle"] = LifecycleChecker()
    req.phase = Phase.RUNNING                    # legal
    req.phase = Phase.FINISHED                   # legal (terminal)
    with pytest.raises(IllegalTransition) as ei:
        req.phase = Phase.RUNNING                # terminal states are final
    assert ei.value.rid == 7
    assert ei.value.old is Phase.FINISHED and ei.value.new is Phase.RUNNING
    assert "test_analysis.py" in ei.value.site


def test_transition_table_shape():
    # every phase appears; terminal states admit nothing
    assert set(TRANSITIONS) == set(Phase)
    for terminal in (Phase.FINISHED, Phase.CANCELLED, Phase.FAILED):
        assert TRANSITIONS[terminal] == frozenset()
    # a request must always be cancellable/failable from live states
    for live in (Phase.WAITING, Phase.RUNNING, Phase.PAUSED, Phase.SWAPQ):
        assert {Phase.CANCELLED, Phase.FAILED} <= TRANSITIONS[live]


# ---------------------------------------------------------------------------
# sanitize=True is observation-only: clean runs, identical streams
# ---------------------------------------------------------------------------

def _soak(policy, *, overlap=True, sanitize=False,
          failure_rate=0.2, timeout_rate=0.1, n=6, **engine_kw):
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = _engine(policy, cfg=cfg, overlap=overlap,
                  sanitize=sanitize, **engine_kw)
    cl = InferCeptClient(eng)
    tools = ChaosToolExecutor(
        VirtualTimeToolExecutor(cfg.vocab_size, n_tokens=4, duration=0.05),
        seed=7, failure_rate=failure_rate, timeout_rate=timeout_rate)

    def detector(req, tid, now):
        from repro.core.request import InterceptDirective
        if req.output_tokens == 5:
            return InterceptDirective(kind="math", duration_hint=0.05)
        return None

    hs = [cl.submit([10 + i, 11 + i, 12 + i, 13 + i], detector=detector,
                    max_new_tokens=16, tools=tools,
                    sampling=SamplingParams(tool_timeout_s=1.0,
                                            tool_retries=1,
                                            tool_backoff_s=0.01))
          for i in range(n)]
    cl.poll()
    streams = {h.rid: cl.token_ids(h) for h in hs if h.finished}
    return eng, hs, streams


def _assert_sanitized_identity(policy, **kw):
    eng_off, hs_off, streams_off = _soak(policy, sanitize=False, **kw)
    eng_on, hs_on, streams_on = _soak(policy, sanitize=True, **kw)
    assert eng_on.sanitizer.findings == [], \
        [str(f) for f in eng_on.sanitizer.findings]
    assert [h.state for h in hs_on] == [h.state for h in hs_off]
    assert streams_on == streams_off            # bit-identical
    assert dict(eng_on.counters) == dict(eng_off.counters)


def test_sanitized_chaos_soak_quick():
    _assert_sanitized_identity("infercept")


@pytest.mark.slow
@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_sanitized_soak_matrix(policy):
    _assert_sanitized_identity(policy)


class _SoakOracle(OracleToolResultPredictor):
    """The soak's tool returns 4 ids, the same pure function of (rid,
    segment) the oracle predicts; a caller-owned intercept gives no
    count, so it is supplied here."""

    def predict(self, rid, kind, seg_idx, n_hint):
        return super().predict(rid, kind, seg_idx, n_hint or 4)


def test_sanitized_soak_speculative():
    """Speculative forks under chaos: every fork prefill runs through the
    fused dispatch on pages the sanitizer audits at each plan phase, with
    retries, timeouts and failed sessions tearing forks down — zero
    findings, and sanitize=True stays observation-only."""
    cfg = get_config("llama3.2-1b", tiny=True)
    _assert_sanitized_identity(
        "infercept", speculate=True,
        predictor=_SoakOracle(cfg.vocab_size))
    eng, _, _ = _soak("infercept", sanitize=True, speculate=True,
                      predictor=_SoakOracle(cfg.vocab_size))
    assert eng.counters["spec_forks"] > 0
    assert eng.counters["spec_prefill_tokens"] > 0


@pytest.mark.slow
def test_sanitized_soak_serial():
    _assert_sanitized_identity("infercept", overlap=False)


def test_sanitized_soak_quantized():
    """Quantized pools under chaos: sanitize=True stays observation-only
    (identical streams/counters to sanitize=False at the same kv_dtype)
    and the run — tool faults, retries, swap churn and all — produces
    ZERO findings, including the per-page scale-ownership audit."""
    _assert_sanitized_identity("infercept", kv_dtype="int8")


def test_sanitized_simulator_runs_clean():
    cost = CostModel(cfg=get_config("gpt-j-6b"), chip=A100, n_chips=1)
    reqs = make_workload(seed=1, n_requests=24, rate_rps=3.0)
    base = simulate(copy.deepcopy(reqs), POLICIES["infercept"], cost)
    sane = simulate(copy.deepcopy(reqs), POLICIES["infercept"], cost,
                    sanitize=True)
    assert len(sane.finished) == len(base.finished) == 24
    assert sane.sim_time == base.sim_time


# ---------------------------------------------------------------------------
# static lint: each rule on synthetic files, waivers, repo-clean
# ---------------------------------------------------------------------------

def _lint_file(tmp_path, name, code, subdir=()):
    d = tmp_path
    for part in subdir:
        d = d / part
        d.mkdir(exist_ok=True)
    f = d / name
    f.write_text(textwrap.dedent(code))
    return lint.run([str(f)])


def test_lint_dispatch_host_sync_via_call_graph(tmp_path):
    code = """
    import jax

    def _helper(x):
        return jax.device_get(x)

    def _dispatch_phase(x):
        return _helper(x)
    """
    found = _lint_file(tmp_path, "mod.py", code)
    assert [f.rule for f in found] == ["dispatch-host-sync"]
    assert "_helper" in found[0].message

    waived = code.replace(
        "return _helper(x)",
        "return _helper(x)  # lint: allow(dispatch-host-sync): test waiver")
    assert _lint_file(tmp_path, "waived.py", waived) == []


def test_lint_direct_sync_in_dispatch(tmp_path):
    found = _lint_file(tmp_path, "mod.py", """
    import jax

    def _dispatch_phase(x):
        return jax.device_get(x)
    """)
    assert [f.rule for f in found] == ["dispatch-host-sync"]
    assert "only commit may sync" in found[0].message


def test_lint_wall_clock_and_unseeded_rng(tmp_path):
    code = """
    import random
    import time
    import numpy as np

    def f():
        a = time.time()
        b = random.random()
        c = np.random.rand(3)
        ok = np.random.default_rng(0)      # sanctioned
        return a, b, c, ok
    """
    found = _lint_file(tmp_path, "mod.py", code,
                       subdir=("repro", "core"))
    assert {f.rule for f in found} == {"wall-clock-rng"}
    assert len(found) == 3
    # same file outside core/serving/sim: out of scope
    assert _lint_file(tmp_path, "mod.py", code,
                      subdir=("repro", "kernels")) == []


def test_lint_undeclared_counter_key(tmp_path):
    found = _lint_file(tmp_path, "mod.py", """
    def f(counters, ledger):
        counters["decode_tokens"] += 1      # declared
        counters["not_a_counter"] += 1      # undeclared
        ledger.causes["recompute"] += 1.0   # declared
        ledger.causes["mystery"] += 1.0     # undeclared
    """)
    assert [f.rule for f in found] == ["undeclared-counter"] * 2
    assert "not_a_counter" in found[0].message
    assert "mystery" in found[1].message


def test_lint_alias_needs_donation(tmp_path):
    code = """
    import jax
    from jax.experimental import pallas as pl

    def kernel(pool, out):
        out[...] = pool[...]

    def aliased(pool):
        return pl.pallas_call(
            kernel, out_shape=pool,
            input_output_aliases={0: 0})(pool)

    bad = jax.jit(aliased)
    good = jax.jit(aliased, donate_argnums=(0,))
    """
    found = _lint_file(tmp_path, "mod.py", code)
    assert [f.rule for f in found] == ["alias-needs-donation"]
    assert "aliased" in found[0].message


def test_lint_repo_is_clean():
    assert lint.run(["src", "tests"]) == []


def test_lint_cli_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("def f(counters):\n    counters['zzz'] = 1\n")
    assert lint.main([str(bad)]) == 1
    out = capsys.readouterr().out
    assert "undeclared-counter" in out and "zzz" in out
    assert lint.main(["src"]) == 0
