"""Unit tests for the InferCept core: waste equations, policy decisions,
queue mechanics, budgets."""
import pytest

from repro.configs import get_config
from repro.core import (CostModel, DurationEstimator, POLICIES, Scheduler,
                        waste)
from repro.core.request import Interception, Phase, Request, Segment
from repro.utils.hw import A100


def _cost(**kw):
    return CostModel(cfg=get_config("gpt-j-6b"), chip=A100, n_chips=1, **kw)


def _req(rid, prompt=100, gens=(10, 10), durations=(1.0,), ret=(5,),
         arrival=0.0, kind="qa"):
    segs = []
    for i, g in enumerate(gens[:-1]):
        segs.append(Segment(g, Interception(kind, durations[i % len(durations)],
                                            ret[i % len(ret)])))
    segs.append(Segment(gens[-1], None))
    return Request(rid=rid, arrival=arrival, prompt_len=prompt, segments=segs)


# ----------------------------------------------------------------------
# Equations 1-5
# ----------------------------------------------------------------------

def test_waste_equations_hand_values():
    # Eq.1: t_fwd=2s, C=10, M=4, C_other=30 -> 2*10*4 + 2*30*4 = 320
    assert waste.waste_discard(2.0, 10, 4.0, 30) == 320.0
    # Eq.2: T_int=5, C=10, M=4 -> 200
    assert waste.waste_preserve(5.0, 10, 4.0) == 200.0
    # Eq.3: t_swap=1, C_batch=40, M=4 -> 2*1*40*4 = 320
    assert waste.waste_swap(1.0, 40, 4.0) == 320.0
    # Eq.4 halves the self term and chunks the other term
    w = waste.waste_chunked_discard(2.0, 10, 4.0, 4, 0.4, 30)
    assert w == 2.0 * 10 * 4 / 2 + 4 * 0.4 * 30 * 4
    assert w < waste.waste_discard(2.0, 10, 4.0, 30)


def test_min_waste_decision_flips_with_duration():
    kw = dict(c_tokens=1000, m_bytes=1e5, t_fwd_c=0.05, n_chunks=2,
              t_fwd_chunk=0.03, c_other_tokens=5000)
    d_short, _ = waste.min_waste_decision(t_int_est=1e-4, **kw)
    d_long, _ = waste.min_waste_decision(t_int_est=60.0, **kw)
    assert d_short == "preserve" and d_long == "discard"


def test_estimator_modes():
    r = _req(0)
    r.current_int = Interception("math", 2.0, 5)
    r.t_call = 10.0
    oracle = DurationEstimator(mode="oracle")
    assert oracle.estimate(r, 11.0) == pytest.approx(1.0)
    dyn = DurationEstimator(mode="dynamic")
    assert dyn.estimate(r, 13.5) == pytest.approx(3.5)
    prof = DurationEstimator(mode="profile", profiles={"math": 9e-5})
    # floored at min_estimate
    assert prof.estimate(r, 11.0) == pytest.approx(prof.min_estimate)


# ----------------------------------------------------------------------
# Scheduler mechanics
# ----------------------------------------------------------------------

def test_fcfs_admission_and_saturation_chunking():
    cost = _cost()
    sched = Scheduler(POLICIES["infercept"], cost)
    S = cost.saturation_tokens
    r1 = _req(1, prompt=S * 2, arrival=0.0)
    r2 = _req(2, prompt=50, arrival=0.1)
    sched.submit(r1)
    sched.submit(r2)
    plan = sched.next_iteration(1.0)
    # chunked admission: r1 gets exactly S tokens, r2 waits (FCFS)
    assert plan.chunks == [(r1, S)]
    sched.apply_plan(plan, 1.1)
    plan = sched.next_iteration(1.2)
    assert (r1, S) in plan.chunks  # remaining half fills the whole budget
    sched.apply_plan(plan, 1.3)
    plan = sched.next_iteration(1.4)     # r1 now decoding, r2 gets budget
    assert any(r is r2 for r, _ in plan.chunks)
    assert any(r is r1 for r in plan.decode)


def test_vllm_full_prefill_no_chunking():
    cost = _cost()
    sched = Scheduler(POLICIES["vllm"], cost)
    r1 = _req(1, prompt=cost.saturation_tokens * 3)
    sched.submit(r1)
    plan = sched.next_iteration(0.0)
    assert plan.chunks == [(r1, r1.prompt_len)]  # monolithic prefill


def test_requeue_key_vllm_vs_improved():
    cost = _cost()
    for name, expect_original in [("vllm", False), ("improved_discard", True)]:
        sched = Scheduler(POLICIES[name], cost)
        r = _req(1, prompt=10, arrival=0.0)
        sched.submit(r)
        plan = sched.next_iteration(0.0)
        sched.apply_plan(plan, 0.1)       # prefill done -> running
        # decode until the interception fires
        t = 0.1
        for _ in range(20):
            plan = sched.next_iteration(t)
            ev = sched.apply_plan(plan, t + 0.01)
            t += 0.01
            if ev["intercepted"]:
                req, intc = ev["intercepted"][0]
                sched.notify_intercepted(req, intc, t)
                break
        assert r.phase == Phase.PAUSED
        sched.notify_resumed(r, t + 5.0)
        if expect_original:
            assert r.arrival_key == 0.0
        else:
            assert r.arrival_key == pytest.approx(t + 5.0)


def test_swap_budget_respected():
    cost = _cost()
    sched = Scheduler(POLICIES["infercept"], cost)
    # a paused request with a big context, one running decode request
    r1 = _req(1, prompt=20000, gens=(5, 5), durations=(100.0,))
    r1.phase = Phase.PAUSED
    r1.device_tokens = 20000
    r1.target_ctx = 20000
    r1.t_call = 0.0
    r1.current_int = Interception("chatbot", 100.0, 5)
    sched.live[1] = r1
    sched.paused.append(r1)
    r2 = _req(2, prompt=10)
    r2.phase = Phase.RUNNING
    r2.device_tokens = 10
    sched.live[2] = r2
    sched.running.append(r2)
    plan = sched.next_iteration(1.0)
    out_tokens = sum(n for _, n in plan.swap_out)
    t_iter = cost.t_fwd(max(1, plan.query_tokens), plan.context_tokens)
    budget = cost.swap_tokens_within(t_iter)
    assert 0 < out_tokens <= budget
    assert out_tokens < 20000  # pipelined across iterations, not all at once


def test_discard_after_partial_swap_clears_host_payload():
    """Regression: a discard landing mid-swap (partial host prefix already
    staged) must fold the host payload into recompute debt and zero it —
    the stale host_tokens used to double-hold CPU bytes and route the
    resume through the swap queue to restore a prefix whose suffix was
    debt."""
    cost = _cost()
    sched = Scheduler(POLICIES["infercept"], cost)
    r = _req(1, prompt=100)
    r.phase = Phase.PAUSED
    r.device_tokens = 60
    r.host_tokens = 40            # partial swap-out already landed
    r.target_ctx = 100
    r.t_call = 0.0
    r.current_int = Interception("math", 5.0, 5)
    r.pending_swap_out = 20
    sched.live[1] = r
    sched.paused.append(r)
    sched.swap_out_order.append(r)
    seen = {}
    # the hook must already observe the zeroed host payload (the engine
    # frees host page entries inside it)
    sched.on_discard = lambda req, n: seen.update(n=n, host=req.host_tokens)
    sched._discard(r, 1.0)
    assert r.host_tokens == 0 and r.device_tokens == 0
    assert r.pending_swap_out == 0 and r not in sched.swap_out_order
    assert sched._recompute_debt[1] == 100       # device AND host folded in
    assert seen == {"n": 100, "host": 0}
    assert sched.cpu_used() == 0                 # no double-held CPU bytes
    # resume routes through recompute, never the swap queue
    sched.notify_resumed(r, 10.0)
    assert r.phase == Phase.WAITING and r not in sched.swap_queue


def test_plan_swap_in_distinct_exhaustion_exits():
    """Regression: budget starvation used to exit through the same break
    as pool exhaustion. The two reasons are now distinct returns."""
    from repro.core.scheduler import IterationPlan
    cost = _cost()

    def fresh():
        sched = Scheduler(POLICIES["infercept"], cost)
        for rid in (1, 2):
            r = _req(rid, prompt=100, arrival=float(rid))
            r.phase = Phase.SWAPQ
            r.host_tokens = 50
            r.target_ctx = 100
            sched.live[rid] = r
            sched.swap_queue.append(r)
        return sched

    # link budget runs out first: the head request absorbs it all
    sched = fresh()
    plan = IterationPlan()
    assert sched._plan_swap_in(plan, 30, 1000) == "budget_exhausted"
    assert [(r.rid, n) for r, n in plan.swap_in] == [(1, 30)]

    # device pool runs out first (unbudgeted blocking restore)
    sched = fresh()
    plan = IterationPlan()
    assert sched._plan_swap_in(plan, None, 50) == "pool_exhausted"
    assert [(r.rid, n) for r, n in plan.swap_in] == [(1, 50)]
    assert plan.stall_s > 0                       # blocking restore stalls

    # ample budget and pool: the queue drains
    sched = fresh()
    plan = IterationPlan()
    assert sched._plan_swap_in(plan, 200, 1000) == "drained"
    assert [(r.rid, n) for r, n in plan.swap_in] == [(1, 50), (2, 50)]


def test_swap_budget_shared_across_directions():
    """Regression for the min-waste budget bookkeeping: swap-out and
    swap-in share one per-iteration link budget; the old code let each
    direction spend the full budget independently."""
    cost = _cost()
    sched = Scheduler(POLICIES["infercept"], cost)
    r1 = _req(1, prompt=20000, gens=(5, 5), durations=(100.0,))
    r1.phase = Phase.PAUSED
    r1.device_tokens = r1.target_ctx = 20000
    r1.t_call = 0.0
    r1.current_int = Interception("chatbot", 100.0, 5)
    sched.live[1] = r1
    sched.paused.append(r1)
    r2 = _req(2, prompt=20000, arrival=0.5)
    r2.phase = Phase.SWAPQ
    r2.host_tokens = 20000
    r2.target_ctx = 20000
    sched.live[2] = r2
    sched.swap_queue.append(r2)
    r3 = _req(3, prompt=10)
    r3.phase = Phase.RUNNING
    r3.device_tokens = 10
    sched.live[3] = r3
    sched.running.append(r3)
    plan = sched.next_iteration(100.0)
    moved = sum(n for _, n in plan.swap_out) + sum(n for _, n in plan.swap_in)
    t_iter = cost.t_fwd(max(1, plan.query_tokens), plan.context_tokens)
    assert 0 < moved <= cost.swap_tokens_within(t_iter)


def test_estimator_mode_flips_min_waste_decision():
    """§4.4 estimator x policy interaction on a Table-1-style long call:
    dynamic just after the intercept sees a tiny elapsed time and
    preserves; oracle (and a learned estimator fed realized pauses) see
    the long remaining duration and discard immediately. CPU capacity is
    pinched to zero so the budget-ordered swap branch stays out of the
    way and the Eq. 5 preserve/discard argmin decides alone."""
    cost = _cost()

    def setup(est):
        sched = Scheduler(POLICIES["infercept"], cost, estimator=est,
                          cpu_capacity_tokens=0)
        r = _req(1, prompt=20000, gens=(5, 5), durations=(60.0,),
                 kind="search")
        r.phase = Phase.PAUSED
        r.device_tokens = r.target_ctx = 20000
        r.t_call = 10.0
        r.current_int = Interception("search", 60.0, 5)
        sched.live[1] = r
        sched.paused.append(r)
        r2 = _req(2, prompt=10)
        r2.phase = Phase.RUNNING
        r2.device_tokens = 10
        sched.live[2] = r2
        sched.running.append(r2)
        return sched, r

    sched, r = setup(DurationEstimator(mode="dynamic"))
    sched.next_iteration(10.05)                # elapsed 0.05 s: looks short
    assert r.decision == "preserve"

    sched, r = setup(DurationEstimator(mode="oracle"))
    sched.next_iteration(10.05)                # 60 s remain: evict
    assert r.decision == "discard"

    est = DurationEstimator(mode="learned")
    est.observe("search", 60.0)                # one realized pause suffices
    sched, r = setup(est)
    sched.next_iteration(10.05)
    assert r.decision == "discard"

    est = DurationEstimator(mode="learned")    # cold start == dynamic
    sched, r = setup(est)
    sched.next_iteration(10.05)
    assert r.decision == "preserve"


def test_eviction_under_memory_pressure():
    cost = _cost()
    sched = Scheduler(POLICIES["vllm"], cost, gpu_capacity_tokens=150)
    r1 = _req(1, prompt=100, arrival=0.0)
    r2 = _req(2, prompt=49, arrival=1.0)
    sched.submit(r1)
    sched.submit(r2)
    t = 0.0
    for _ in range(60):
        plan = sched.next_iteration(t)
        if plan.empty:
            break
        sched.apply_plan(plan, t + 0.01)
        t += 0.01
    # both decoding toward 150-token cap forces an eviction of the later one
    assert sched.stats.evictions >= 1
    assert sched.gpu_used() <= 150


def test_eviction_reclaims_partial_prefills_before_the_running_request():
    """A later-arrival waiting request holding a partial chunked prefill is
    evicted before the running request: sparing it once starved the
    running request forever (evicted, recomputed into the remainder of the
    pool, evicted again — every iteration)."""
    sched = Scheduler(POLICIES["infercept"], _cost(),
                      gpu_capacity_tokens=120)
    run = _req(1, prompt=60, arrival=0.0)
    partial = _req(2, prompt=90, arrival=1.0)
    sched.submit(run)
    sched.submit(partial)
    sched.waiting.remove(run)
    run.device_tokens, run.phase = 60, Phase.RUNNING
    sched.running.append(run)
    partial.device_tokens = 60             # half its prompt, pool now full
    plan = sched.next_iteration(2.0)
    assert plan.decode == [run]
    assert partial.device_tokens == 0 and partial in sched.waiting
    assert sched.stats.evictions == 1
