"""Waste-attribution telemetry (DESIGN.md §13).

The contract under test: telemetry never perturbs the engine — token
streams and every legacy counter stay bit-identical with tracing on vs
off — while the always-on WasteLedger charges every wasted byte-second
to a cause, the simulator's ledger mirrors the engine's bit-for-bit for
token-granular policies, and the Perfetto export passes its own schema
validator.
"""
import copy
import json

import pytest

from repro.configs import get_config
from repro.core import POLICIES, CostModel
from repro.core.waste import waste_preserve, waste_swap
from repro.obs.check import check_breakdown
from repro.obs.check import main as check_main
from repro.obs.export import (format_stats_line, format_summary,
                              to_perfetto, validate_trace, write_trace)
from repro.obs.ledger import WASTE_CAUSES, WasteLedger, waste_report
from repro.obs.metrics import CounterView, Histogram, MetricsRegistry
from repro.obs.trace import NullTracer, SpanTracer
from repro.serving.engine import Engine
from repro.serving.workloads import make_workload
from repro.sim import simulate
from repro.utils.hw import A100, TPU_V5E


# ---------------------------------------------------------------------------
# metrics registry + compat views
# ---------------------------------------------------------------------------

def test_histogram_fixed_buckets():
    h = Histogram("h", edges=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 3.0, 100.0):   # 1.0 lands in the le=1.0 bucket
        h.observe(v)
    assert h.counts == [2, 0, 1, 1]    # counts[-1] is the overflow
    assert h.n == 4
    assert h.mean() == pytest.approx(104.5 / 4)


def test_registry_export_formats():
    reg = MetricsRegistry()
    reg.inc("reqs", 2)
    reg.inc("reqs")
    reg.gauge("depth", 1.5)
    reg.observe("lat_s", 0.0001)
    reg.observe("lat_s", 999.0)

    snap = reg.snapshot()
    assert snap["counters"]["reqs"] == 3
    assert snap["histograms"]["lat_s"]["count"] == 2

    prom = reg.to_prometheus()
    assert "# TYPE reqs counter" in prom
    assert "reqs 3" in prom
    assert "depth 1.5" in prom
    # cumulative le semantics: first edge already holds the tiny value,
    # +Inf holds everything
    assert 'lat_s_bucket{le="0.0005"} 1' in prom
    assert 'lat_s_bucket{le="+Inf"} 2' in prom


def test_counter_view_is_registry_backed():
    reg = MetricsRegistry()
    v = reg.view("engine_")
    assert isinstance(v, CounterView)
    v["x"] = 0
    v["x"] += 5                         # exact int arithmetic, no copies
    assert reg.counters["engine_x"] == 5  # lint: allow(undeclared-counter): registry unit-test scratch key
    assert isinstance(v["x"], int)
    v.update({"y": 1})
    assert set(v) == {"x", "y"} and len(v) == 2
    assert dict(v) == {"x": 5, "y": 1}
    del v["y"]
    assert "y" not in v and "engine_y" not in reg.counters
    assert v.registry is reg


def test_scheduler_stats_routes_to_registry():
    from repro.core.scheduler import SchedulerStats
    reg = MetricsRegistry()
    st = SchedulerStats(reg)
    st.discards += 3                    # legacy call-site shape
    st.recompute_tokens = 7
    assert reg.counters["sched_discards"] == 3
    assert reg.counters["sched_recompute_tokens"] == 7
    assert st.discards == 3 and st.recompute_tokens == 7
    assert "discards=3" in repr(st)


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_null_tracer_is_inert():
    t = NullTracer()
    assert not t.enabled
    t.span(("req", 1), "decode", 0.0, 1.0)
    t.async_begin("tool", 1, "tool", 0.0)
    t.async_end("tool", 1, "tool", 1.0)
    t.instant(("req", 1), "finish", 1.0)
    assert len(t) == 0


def test_span_tracer_records_and_drops_empty():
    t = SpanTracer()
    assert t.enabled
    t.span(("engine", "step"), "iter", 1.0, 1.0)     # zero-length: dropped
    assert len(t) == 0
    t.span(("engine", "step"), "iter", 1.0, 2.0)
    t.async_begin("tool", 5, "tool", 1.2)
    t.async_end("tool", 5, "tool", 1.8)
    t.instant(("req", 0), "finish", 2.0)
    assert len(t) == 4


def test_perfetto_export_and_validator():
    t = SpanTracer()
    t.span(("engine", "step"), "iter", 0.0, 1.0)
    t.span(("engine", "step"), "iter", 1.0, 2.0)
    t.span(("req", 0), "prefill", 0.0, 0.5)
    t.async_begin("tool", 7, "tool", 0.2)
    t.async_end("tool", 7, "tool", 1.7)
    obj = to_perfetto(t)
    assert validate_trace(obj) == []
    names = {ev.get("name") for ev in obj["traceEvents"]}
    assert {"iter", "prefill", "tool"} <= names
    # metadata rows label the fixed pid/tid layout
    metas = [ev for ev in obj["traceEvents"] if ev["ph"] == "M"]
    assert any(ev["args"].get("name") == "engine" for ev in metas)

    # the validator rejects overlapping spans on one track ...
    bad = SpanTracer()
    bad.span(("req", 0), "a", 0.0, 2.0)
    bad.span(("req", 0), "b", 1.0, 3.0)
    assert validate_trace(to_perfetto(bad))
    # ... and unbalanced async pairs
    dangling = SpanTracer()
    dangling.async_begin("tool", 1, "tool", 0.0)
    assert validate_trace(to_perfetto(dangling))


# ---------------------------------------------------------------------------
# waste ledger (unit)
# ---------------------------------------------------------------------------

def _cost():
    return CostModel(cfg=get_config("gpt-j-6b"), chip=A100, n_chips=1)


def test_ledger_cause_charges_and_crosscheck():
    cost = _cost()
    led = WasteLedger(cost, 10_000)
    m = cost.m_bytes
    led.charge_iteration(0.1, 0.0, False, 0, 64, 100, 500)
    assert led.causes["preserve_pinned"] == pytest.approx(0.1 * 100 * m)
    led.charge_iteration(0.2, 0.05, False, 32, 64, 0, 500)
    assert led.causes["recompute"] == pytest.approx(0.2 * 0.5 * 500 * m)
    assert led.causes["swap_stall"] == pytest.approx(0.05 * 500 * m)
    led.charge_iteration(0.1, 0.02, True, 0, 8, 0, 300)   # overlap engine
    assert led.causes["pipeline_bubble"] == pytest.approx(0.02 * 300 * m)
    led.charge_idle(1.0, 200, tool_wait=True)
    led.charge_idle(5.0, 200, tool_wait=False)   # arrival gap: free
    assert led.causes["tool_unoverlapped"] == pytest.approx(1.0 * 200 * m)
    assert led.idle_time == 6.0 and led.iterations == 3
    assert set(led.causes) == set(WASTE_CAUSES)
    # the independent accumulator agrees with the per-cause sum
    assert led.total_waste() == pytest.approx(led.total_check, rel=1e-9)
    assert 0.0 < led.waste_fraction()
    assert check_breakdown(waste_report(led)) == []


def test_ledger_intercept_records_eq5_branches():
    cost = _cost()
    led = WasteLedger(cost, 10_000)
    m = cost.m_bytes

    # oracle-exact prediction: preserve waste matches Eq. 2, zero error
    led.intercept_started(1, "math", t_call=10.0, predicted_s=2.0,
                          c_tokens=128, gpu_used_tokens=512)
    rec = led.intercept_finished(1, "preserve", t_done=12.0)
    assert rec.realized_s == 2.0
    assert rec.predicted_waste == pytest.approx(waste_preserve(2.0, 128, m))
    assert rec.realized_waste == rec.predicted_waste
    assert led.registry.histograms["estimator_abs_err_s"].mean() == 0.0
    assert led.registry.gauges["estimator_bias_s_math"] == 0.0

    # swap waste is duration-independent (Eq. 3): a 8s under-prediction
    # still lands in the estimator metrics, not the waste charge
    led.intercept_started(2, "search", 20.0, 1.0, 64, 256)
    rec2 = led.intercept_finished(2, "swap", 29.0)
    assert rec2.predicted_waste == rec2.realized_waste
    assert rec2.realized_waste == pytest.approx(
        waste_swap(cost.t_swap(64), 256, m))
    st = led.estimator_stats()
    assert st["search"]["bias_s"] == pytest.approx(-8.0)
    assert st["search"]["abs_err_s"] == pytest.approx(8.0)

    # finishing an unknown rid is a no-op, not an error
    assert led.intercept_finished(99, "preserve", 1.0) is None
    assert len(led.records) == 2


def test_check_breakdown_catches_tampering(tmp_path):
    cost = _cost()
    led = WasteLedger(cost, 1000)
    led.charge_iteration(0.1, 0.0, False, 0, 4, 50, 100)
    rep = waste_report(led)
    assert check_breakdown(rep) == []
    assert check_breakdown([rep, rep]) == []
    bad = dict(rep)
    bad["causes"] = dict(rep["causes"])
    bad["causes"]["recompute"] += 0.01 * rep["total_waste_check"] + 1.0
    assert check_breakdown(bad)
    assert check_breakdown({"causes": "nope"})

    good = tmp_path / "breakdown.json"
    good.write_text(json.dumps({"vllm": rep, "preserve": rep}))
    assert check_main([str(good)]) == 0
    broken = tmp_path / "bad.json"
    broken.write_text(json.dumps(bad))
    assert check_main([str(broken)]) == 1
    assert check_main([]) == 2


# ---------------------------------------------------------------------------
# engine integration: the identity contract + the sim mirror
# ---------------------------------------------------------------------------

# four policies spread across the engine variants (§9 fused, §8 prefix
# cache, §12 overlap) so the identity pin covers every code path that
# gained emission sites
CONFIGS = [
    ("vllm", {}),                           # discard + full recompute
    ("preserve", {"overlap": False}),       # serial step (§12 oracle)
    ("swap", {"fused": False}),             # unfused mixed batches
    ("infercept", {"prefix_cache": True}),  # min-waste + prefix cache
]


def _small_workload(n=3):
    reqs = make_workload(seed=7, n_requests=n, rate_rps=2.0, max_ctx=200)
    for r in reqs:
        r.prompt_len = min(r.prompt_len, 32)
        r.target_ctx = r.prompt_len
        for s in r.segments:
            s.gen_tokens = min(s.gen_tokens, 8)
            if s.interception:
                s.interception.returned_tokens = min(
                    s.interception.returned_tokens, 6)
        r.segments = r.segments[:2]
        if r.segments[-1].interception is not None:
            r.segments[-1].interception = None
    return reqs


@pytest.fixture(scope="module")
def traced_runs():
    cfg = get_config("llama3.2-1b", tiny=True)
    reqs = _small_workload()
    out = {}
    for name, kw in CONFIGS:
        runs = {}
        for key, tracer in (("on", SpanTracer()), ("off", None)):
            eng = Engine(cfg, POLICIES[name], page_size=16, n_pages=64,
                         max_model_len=192, seed=0, tracer=tracer, **kw)
            for r in copy.deepcopy(reqs):
                eng.add_request(r)
            fin = eng.run()
            assert len(fin) == len(reqs), (name, key)
            runs[key] = ({r.rid: eng.generated_text(r) for r in fin}, eng)
        out[name] = runs
    return out


def test_tracing_identity(traced_runs):
    """Streams, legacy counters, and the always-on ledger must be
    bit-identical with tracing on vs off."""
    for name, runs in traced_runs.items():
        (s_on, eng_on), (s_off, eng_off) = runs["on"], runs["off"]
        assert s_on == s_off, f"tracing perturbed streams under {name}"
        assert dict(eng_on.counters) == dict(eng_off.counters), name
        assert isinstance(eng_off.tracer, NullTracer)
        assert len(eng_off.tracer) == 0
        assert eng_on.ledger.causes == eng_off.ledger.causes, name
        assert eng_on.ledger.total_check == eng_off.ledger.total_check


def test_engine_traces_validate(traced_runs):
    for name, runs in traced_runs.items():
        _, eng = runs["on"]
        assert len(eng.tracer) > 0, name
        errs = validate_trace(to_perfetto(eng.tracer))
        assert errs == [], (name, errs[:5])


def test_trace_has_lifecycle_and_tool_spans(traced_runs):
    _, eng = traced_runs["infercept"]["on"]
    obj = to_perfetto(eng.tracer)
    spans = {ev["name"] for ev in obj["traceEvents"] if ev["ph"] == "X"}
    # "queued" appears only when a wait has nonzero duration — not
    # guaranteed on a tiny workload, so it isn't in the required set
    assert {"iter", "prefill", "decode"} <= spans
    begins = [ev for ev in obj["traceEvents"] if ev["ph"] == "b"]
    ends = [ev for ev in obj["traceEvents"] if ev["ph"] == "e"]
    # every intercept produced a balanced tool async span whose end
    # carries the Eq. 5 resolution
    assert len(begins) == len(ends) == len(eng.ledger.records) > 0
    for ev in ends:
        assert "branch" in ev["args"] and "realized_s" in ev["args"]
    for ev in begins:
        assert "predicted_s" in ev["args"]


def test_engine_ledger_invariants(traced_runs):
    for name, runs in traced_runs.items():
        _, eng = runs["off"]
        led = eng.ledger
        assert led.iterations > 0 and led.busy_time > 0, name
        # vllm can legitimately charge nothing on a tiny workload (the
        # recompute share is priced at the pre-commit batch occupancy,
        # which is 0 when the discarded request is alone); policies that
        # pin context must show preserve_pinned waste
        assert led.total_waste() >= 0, name
        if name in ("preserve", "infercept"):
            assert led.causes["preserve_pinned"] > 0, name
        rep = waste_report(led)
        assert check_breakdown(rep) == [], (name, check_breakdown(rep))
        # every interception was opened and closed
        assert not led._open, name
        assert rep["intercepts"]["n"] == len(led.records)


def test_engine_sim_ledger_mirror(traced_runs):
    """Token-granular policies: the simulator's always-on ledger equals
    the engine's bit-for-bit at matched capacity, and equals its own
    legacy SimResult waste fields."""
    cfg = get_config("llama3.2-1b", tiny=True)
    cost = CostModel(cfg=cfg, chip=TPU_V5E, n_chips=1)
    for name in ("vllm", "preserve"):
        _, eng = traced_runs[name]["off"]
        res = simulate(copy.deepcopy(_small_workload()), POLICIES[name],
                       cost, gpu_capacity_tokens=eng.sched.gpu_capacity)
        sl = res.ledger
        assert sl.causes == eng.ledger.causes, name
        assert sl.gpu_byte_seconds == eng.ledger.gpu_byte_seconds, name
        assert sl.total_check == eng.ledger.total_check, name
        assert sl.causes["preserve_pinned"] == res.waste_preserved, name
        assert sl.causes["recompute"] == res.waste_recompute, name
        assert sl.causes["swap_stall"] == res.waste_swap_stall, name


def test_format_summary_and_stats_line(traced_runs):
    _, eng = traced_runs["infercept"]["on"]
    s = format_summary(eng)
    assert "waste attribution" in s
    assert "intercepts" in s and "branches:" in s
    line = format_stats_line(eng)
    assert "iters=" in line and "waste=" in line
    # one registry spans the stack: engine counters + scheduler stats in
    # a single Prometheus dump
    prom = eng.metrics.to_prometheus()
    assert "engine_decode_tokens" in prom
    assert "sched_recompute_tokens" in prom


def test_trace_file_roundtrip_check(tmp_path, traced_runs):
    _, eng = traced_runs["infercept"]["on"]
    path = tmp_path / "trace.json"
    n = write_trace(eng.tracer, str(path))
    assert n > 0
    obj = json.loads(path.read_text())
    assert n == len(obj["traceEvents"])
    assert check_main([str(path)]) == 0


def test_session_latency_histograms(monkeypatch):
    """TTFT and inter-token gaps observed by the session client land in
    the engine's registry, on the host clock: with a fake clock that
    advances one second a read, every observation is a whole number of
    seconds, which the virtual clock never gives."""
    import itertools
    from repro.obs import trace as obs_trace
    from repro.serving.session import ScriptedClient
    ticks = itertools.count()
    monkeypatch.setattr(obs_trace, "host_ns",
                        lambda: next(ticks) * 1_000_000_000)
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = Engine(cfg, POLICIES["infercept"], page_size=16, n_pages=64,
                 max_model_len=192, seed=0)
    scripted = ScriptedClient(eng)
    handles = scripted.submit(copy.deepcopy(_small_workload()))
    batch = scripted.client.poll()
    assert batch.drained
    ttft = eng.metrics.histograms["session_ttft_s"]
    gaps = eng.metrics.histograms["session_token_gap_s"]
    assert ttft.n == len(handles)
    assert gaps.n > 0
    # the wait histograms observe the same host-clock waits as the
    # host_queue_wait counters
    waits = [eng.metrics.histograms[k] for k in
             ("engine_queue_wait_s", "engine_swapped_wait_s")
             if k in eng.metrics.histograms]
    assert sum(h.n for h in waits) == eng.metrics.counters[
        "host_queue_waits"] > 0
    assert sum(h.total for h in waits) == pytest.approx(
        eng.metrics.counters["host_queue_wait_s"])
    for h in [ttft, gaps] + waits:
        assert h.total >= h.n            # each at least one tick
        assert h.total == pytest.approx(round(h.total))
    assert not eng.host_clock._laps      # finished sessions drop stamps
    eng.close()


# ---------------------------------------------------------------------------
# the host clock inside Engine.step (obs.trace.HostClock)
# ---------------------------------------------------------------------------

PHASE_COUNTERS = ("host_plan_s", "host_dispatch_s", "host_swap_s",
                  "host_sync_s", "host_commit_s")


def _host(eng):
    return {k: v for k, v in eng.metrics.counters.items()
            if k.startswith("host_")}


@pytest.mark.parametrize("name", [c[0] for c in CONFIGS])
def test_host_clock_counts_engine_phases(traced_runs, name):
    """Every host_ counter the run's path reached is > 0; the phases'
    self times make up 95-100% of the step time; one counted step per
    iteration the engine charged."""
    for key in ("on", "off"):
        _, eng = traced_runs[name][key]
        h = _host(eng)
        reached = {"host_step_s", "host_steps", "host_plan_s",
                   "host_dispatch_s", "host_sync_s", "host_commit_s",
                   "host_queue_wait_s", "host_queue_waits"}
        if eng.counters["swap_bytes"]:
            reached.add("host_swap_s")
        else:
            assert h["host_swap_s"] == 0.0, name
        assert all(h[k] > 0 for k in reached), (name, h)
        phases = sum(h[k] for k in PHASE_COUNTERS)
        assert 0.95 * h["host_step_s"] <= phases <= h["host_step_s"], \
            (name, phases, h)
        assert h["host_steps"] == eng.ledger.iterations, name
        assert eng.counters["cost_step_s"] == pytest.approx(
            eng.ledger.busy_time), name
    assert any(traced_runs[n]["off"][1].counters["swap_bytes"]
               for n, _ in CONFIGS)


@pytest.mark.parametrize("name,kw", CONFIGS, ids=[c[0] for c in CONFIGS])
def test_host_clock_feeds_no_decision(traced_runs, monkeypatch, name, kw):
    """With the host clock frozen, streams, engine counters and the ledger
    are bit-identical to the real-clock run: no decision reads it."""
    from repro.obs import trace as obs_trace
    monkeypatch.setattr(obs_trace, "host_ns", lambda: 0)
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = Engine(cfg, POLICIES[name], page_size=16, n_pages=64,
                 max_model_len=192, seed=0, **kw)
    for r in copy.deepcopy(_small_workload()):
        eng.add_request(r)
    fin = eng.run()
    streams = {r.rid: eng.generated_text(r) for r in fin}
    s_real, eng_real = traced_runs[name]["off"]
    assert streams == s_real, name
    assert dict(eng.counters) == dict(eng_real.counters), name
    assert eng.ledger.causes == eng_real.ledger.causes, name
    assert eng.ledger.total_check == eng_real.ledger.total_check, name
    assert eng.ledger.gpu_byte_seconds == eng_real.ledger.gpu_byte_seconds
    frozen, real = _host(eng), _host(eng_real)
    for k in ("host_steps", "host_queue_waits"):
        assert frozen[k] == real[k] > 0, (name, k)
    assert all(v == 0 for k, v in frozen.items()
               if k not in ("host_steps", "host_queue_waits")), frozen


def test_engine_spans_in_profiler_trace(tmp_path):
    """A profile of a few fused steps holds the engine's phase spans on
    the host plane, each inside an engine.step event, read the way the
    benchmark reads a trace (jax.profiler.ProfileData over .xplane.pb)."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = Engine(cfg, POLICIES["infercept"], page_size=16, n_pages=64,
                 max_model_len=192, seed=0)
    for r in copy.deepcopy(_small_workload()):
        eng.add_request(r)
    eng.run(max_steps=4)                 # compiles out of the profile
    with jax.profiler.trace(str(tmp_path)):
        eng.run(max_steps=3)
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**",
                                         "*.xplane.pb"), recursive=True),
                  key=os.path.getmtime)[-1]
    events = [(plane.name, line.name, e.name, e.start_ns,
               e.start_ns + e.duration_ns, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("engine.")]
    steps = [e for e in events if e[2] == "engine.step"]
    assert len(steps) == 3
    assert all({"step_num", "query_tokens", "decode", "chunks"}
               <= set(e[5]) for e in steps)
    phases = [e for e in events if e[2] != "engine.step"]
    assert {"engine.plan", "engine.dispatch", "engine.sync",
            "engine.commit"} <= {e[2] for e in phases}
    for plane, line, name, a, b, _ in phases:
        assert any(s[0] == plane and s[1] == line and s[3] <= a
                   and b <= s[4] for s in steps), name
