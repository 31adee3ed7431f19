"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived packs the
figure-specific metrics as ';'-separated key=val pairs).

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only <substr>]

Paper targets (InferCept, ICML 2024):
  Table 1  — augmentation properties (interception time / count / context)
  Figure 2 — end-to-end: normalized latency, throughput, TTFT for
             {vLLM, ImprovedDiscard, Preserve, Swap, InferCept} x load
  Figure 3 — technique breakdown (+waste fractions)
  §3.2     — Discard 27% waste / 37-40% recompute time; Preserve ~50% mem
             held by paused >60% of time; Swap 26% waste
  §4.4     — dynamic estimator reaches 93% of oracle
  §5.1     — single-augment workloads (QA, Chatbot) + multi-GPU scaling
  kernels  — Pallas flash/GLA/swap-pack vs refs (interpret-mode checked,
             XLA-path timed)
  cache    — beyond-paper prefix-KV-cache sweep on the agent workload
             (hit rate / tokens saved vs prefix-share; JSON emitted)
"""
from __future__ import annotations

import argparse
import copy
import time

import numpy as np


def _row(name: str, us_per_call: float, derived: dict):
    d = ";".join(f"{k}={v}" for k, v in derived.items())
    print(f"{name},{us_per_call:.1f},{d}", flush=True)


def _cost(model_name="gpt-j-6b", chip_name="a100", n_chips=1):
    from repro.configs import get_config
    from repro.core import CostModel
    from repro.utils.hw import CHIPS
    return CostModel(cfg=get_config(model_name), chip=CHIPS[chip_name],
                     n_chips=n_chips)


def bench_table1_workload(quick=False):
    from repro.serving.workloads import (AUGMENT_SPECS, make_workload,
                                         workload_table)
    n = 200 if quick else 1000
    t0 = time.time()
    reqs = make_workload(seed=0, n_requests=n, rate_rps=2.0)
    stats = workload_table(reqs)
    dt = (time.time() - t0) / n * 1e6
    for kind, s in sorted(stats.items()):
        spec = AUGMENT_SPECS[kind]
        _row(f"table1_{kind}", dt, {
            "int_time_mean_s": round(s["int_time_mean"], 5),
            "paper_mean_s": spec.int_time[0],
            "n_int_mean": round(s["n_int_mean"], 2),
            "paper_n_int": spec.n_int[0],
            "ctx_mean": round(s["ctx_mean"], 0),
            "paper_ctx": spec.ctx_len[0],
        })


def _run_policies(policies, reqs, cost, profiles=None):
    from repro.sim import simulate
    out = {}
    for name, pol in policies.items():
        t0 = time.time()
        r = simulate(copy.deepcopy(reqs), pol, cost, profiles=profiles)
        out[name] = (r, time.time() - t0)
    return out


def bench_fig2_end2end(quick=False, model="gpt-j-6b", n_chips=1):
    from repro.core import POLICIES
    from repro.serving.workloads import make_workload
    cost = _cost(model, n_chips=n_chips)
    rates = [1.0, 2.0] if quick else [1.0, 2.0, 3.0, 4.0]
    n = 80 if quick else 200
    pols = {k: POLICIES[k] for k in
            ["vllm", "improved_discard", "preserve", "swap", "infercept"]}
    for rate in rates:
        reqs = make_workload(seed=1, n_requests=n, rate_rps=rate)
        res = _run_policies(pols, reqs, cost)
        base = res["vllm"][0]
        for name, (r, wall) in res.items():
            s = r.summary()
            _row(f"fig2_{model.replace('-', '_')}_rate{rate}_{name}",
                 wall / max(1, r.iterations) * 1e6, {
                     "norm_lat_p50": s["norm_latency_p50_s_per_tok"],
                     "tput_rps": s["throughput_rps"],
                     "ttft_p50": s["ttft_p50_s"],
                     "waste_frac": s["waste_fraction"],
                     "speedup_vs_vllm": round(
                         base.normalized_latency()
                         / max(1e-9, r.normalized_latency()), 2),
                 })


def bench_fig3_breakdown(quick=False):
    from repro.core import BREAKDOWN
    from repro.serving.workloads import make_workload
    cost = _cost()
    n = 80 if quick else 200
    reqs = make_workload(seed=2, n_requests=n, rate_rps=2.0)
    res = _run_policies({p.name: p for p in BREAKDOWN}, reqs, cost)
    prev = None
    for p in BREAKDOWN:
        r, wall = res[p.name]
        lat = r.normalized_latency()
        improv = 0.0 if prev is None else round((prev - lat) / prev * 100, 1)
        prev = lat
        _row(f"fig3_{p.name}", wall / max(1, r.iterations) * 1e6, {
            "norm_lat_p50": round(lat, 5),
            "improvement_pct_over_prev": improv,
            "waste_frac": round(r.waste_fraction(), 4),
        })


def bench_waste_s32(quick=False):
    """§3.2 waste characterization of the three primitive strategies."""
    from repro.core import POLICIES
    from repro.serving.workloads import make_workload
    # the paper's Fig.3 load point (2 rps, 6B model); waste fractions are
    # load-sensitive and grow toward saturation, so the load must match
    cost = _cost()
    n = 100 if quick else 150
    reqs = make_workload(seed=3, n_requests=n, rate_rps=2.0)
    res = _run_policies({k: POLICIES[k] for k in
                         ["vllm", "preserve", "swap", "infercept"]},
                        reqs, cost)
    paper = {"vllm": {"waste": 0.27, "recompute_time": 0.385},
             "preserve": {"waste": 0.30, "recompute_time": 0.0},
             "swap": {"waste": 0.26, "recompute_time": 0.0},
             "infercept": {"waste": 0.0069, "recompute_time": 0.0}}
    for name, (r, wall) in res.items():
        _row(f"s32_waste_{name}", wall / max(1, r.iterations) * 1e6, {
            "waste_frac": round(r.waste_fraction(), 4),
            "paper_waste": paper[name]["waste"],
            "recompute_time_frac": round(r.recompute_time_fraction(), 4),
            "stall_time_s": round(r.stall_time, 2),
        })


def bench_estimator(quick=False):
    """§4.4: dynamic estimation vs oracle (paper: 93%)."""
    from repro.core import POLICIES
    from repro.serving.workloads import make_workload, profile_means
    cost = _cost()
    n = 100 if quick else 200
    reqs = make_workload(seed=4, n_requests=n, rate_rps=3.0)
    res = _run_policies(
        {"dynamic": POLICIES["infercept"],
         "oracle": POLICIES["infercept_oracle"]},
        reqs, cost, profiles=profile_means())
    dyn = res["dynamic"][0]
    orc = res["oracle"][0]
    ratio = orc.normalized_latency() / max(1e-9, dyn.normalized_latency())
    _row("s44_estimator", res["dynamic"][1] * 1e6 / max(1, dyn.iterations), {
        "dynamic_norm_lat": round(dyn.normalized_latency(), 5),
        "oracle_norm_lat": round(orc.normalized_latency(), 5),
        "dynamic_vs_oracle": round(ratio, 3),
        "paper_claim": 0.93,
    })


def bench_single_augment(quick=False):
    from repro.core import POLICIES
    from repro.serving.workloads import make_workload
    cost = _cost()
    n = 60 if quick else 150
    for kind, rate in [("qa", 3.0), ("chatbot", 2.0)]:
        reqs = make_workload(seed=5, n_requests=n, rate_rps=rate,
                             kinds=(kind,))
        res = _run_policies({k: POLICIES[k] for k in ["vllm", "infercept"]},
                            reqs, cost)
        sp = (res["vllm"][0].normalized_latency()
              / max(1e-9, res["infercept"][0].normalized_latency()))
        _row(f"s51_single_{kind}", res["infercept"][1] * 1e6, {
            "infercept_norm_lat":
                round(res["infercept"][0].normalized_latency(), 5),
            "vllm_norm_lat": round(res["vllm"][0].normalized_latency(), 5),
            "speedup": round(sp, 2),
        })


def bench_kernels(quick=False):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.swap_pack import swap_pack

    key = jax.random.PRNGKey(0)

    def timed(fn, *args, n=3):
        fn(*args)  # compile
        t0 = time.time()
        for _ in range(n):
            jax.block_until_ready(fn(*args))
        return (time.time() - t0) / n * 1e6

    # flash attention (XLA-ref timing + interpret-mode check)
    B, Hkv, G, T, hd = 1, 2, 2, 256, 64
    q = jax.random.normal(key, (B, Hkv, G, T, hd), jnp.float32)
    k = jax.random.normal(key, (B, Hkv, T, hd), jnp.float32)
    v = jax.random.normal(key, (B, Hkv, T, hd), jnp.float32)
    us_ref = timed(jax.jit(lambda a, b, c: ref.flash_attention_ref(a, b, c)),
                   q, k, v)
    out = flash_attention(q, k, v, bq=64, bk=64, interpret=True)
    err = float(jnp.max(jnp.abs(out - ref.flash_attention_ref(q, k, v))))
    _row("kernel_flash_attention", us_ref,
         {"interpret_max_err": f"{err:.2e}",
          "shape": f"B{B}xHkv{Hkv}xG{G}xT{T}xhd{hd}"})

    # chunked GLA scan (mamba2 / mLSTM SSD core)
    from repro.kernels.gla_scan import gla_scan
    from repro.models.ssm import chunked_gla
    qg = jax.random.normal(key, (2, 2, 256, 64))
    vg = jax.random.normal(key, (2, 2, 256, 64))
    lag = -jnp.abs(jax.random.normal(key, (2, 2, 256))) * 0.2
    us_ref = timed(jax.jit(lambda a, b, c, d: chunked_gla(a, b, c, d, 128)),
                   qg, qg, vg, lag)
    yk, _ = gla_scan(qg, qg, vg, lag, chunk=128, interpret=True)
    yr, _ = chunked_gla(qg, qg, vg, lag, 128)
    err = float(jnp.max(jnp.abs(yk - yr)))
    _row("kernel_gla_scan", us_ref,
         {"interpret_max_err": f"{err:.2e}", "chunk": 128, "T": 256})

    # swap pack
    rng = np.random.default_rng(0)
    pool = jax.random.normal(key, (64, 16, 2, 64), jnp.bfloat16)
    ids = jnp.asarray(rng.choice(64, 16, replace=False), jnp.int32)
    us_ref = timed(jax.jit(lambda *a: ref.swap_pack_ref(*a)), pool, ids)
    out = swap_pack(pool, ids, interpret=True)
    ok = bool(jnp.array_equal(out, ref.swap_pack_ref(pool, ids)))
    _row("kernel_swap_pack", us_ref, {"exact_match": ok, "pages_moved": 16})


def bench_prefix_cache_sweep(quick=False):
    """Intercept-aware prefix cache (DESIGN.md §8): hit rate, recompute
    tokens saved, and throughput vs the no-cache baseline, swept over the
    agent workload's prefix-share ratio. Also writes
    benchmarks/prefix_cache_sweep.json next to this file."""
    import json
    import os
    from repro.core import POLICIES
    from repro.serving.workloads import make_agent_workload
    from repro.sim import simulate
    cost = _cost()
    n = 25 if quick else 60
    shares = [0.3, 0.6] if quick else [0.2, 0.4, 0.6, 0.8]
    results = []
    for share in shares:
        reqs = make_agent_workload(seed=11, n_sessions=n, rate_rps=2.0,
                                   prefix_share=share)
        for name in ["vllm", "infercept"]:
            pol = POLICIES[name]
            t0 = time.time()
            base = simulate(copy.deepcopy(reqs), pol, cost)
            cached = simulate(copy.deepcopy(reqs), pol, cost,
                              prefix_cache=True)
            wall = time.time() - t0
            rec_base = base.stats.recompute_tokens + base.stats.fresh_tokens
            rec_cached = (cached.stats.recompute_tokens
                          + cached.stats.fresh_tokens)
            row = {
                "prefix_share": share,
                "policy": name,
                "cache_hit_tokens": cached.stats.cache_hit_tokens,
                "cache_hit_rate": round(cached.cache_hit_rate(), 4),
                "prefill_tokens_nocache": rec_base,
                "prefill_tokens_cache": rec_cached,
                "recompute_tokens_nocache": base.stats.recompute_tokens,
                "recompute_tokens_cache": cached.stats.recompute_tokens,
                "tokens_saved_frac": round(
                    1.0 - rec_cached / max(1, rec_base), 4),
                "tput_rps_nocache": round(base.throughput_rps(), 4),
                "tput_rps_cache": round(cached.throughput_rps(), 4),
                "norm_lat_p50_nocache": round(base.normalized_latency(), 5),
                "norm_lat_p50_cache": round(cached.normalized_latency(), 5),
            }
            results.append(row)
            _row(f"prefix_cache_{name}_share{share}",
                 wall / max(1, base.iterations + cached.iterations) * 1e6,
                 {k: v for k, v in row.items()
                  if k not in ("prefix_share", "policy")})
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "prefix_cache_sweep.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)


def bench_serve_sweep(quick=False):
    """End-to-end serving through the first-class session API (DESIGN.md
    §11): a ScriptedClient replays the mixed Table-1 workload over the
    real engine for each scheduling policy and reports the paper's
    headline metrics — TTFT and normalized latency p50/p99 per policy —
    plus stream-identity against the legacy closed-loop engine. Writes
    benchmarks/serve_sweep.json."""
    import json
    import os
    from repro.configs import get_config
    from repro.core import POLICIES
    from repro.launch.serve import scale_to_budget
    from repro.serving.engine import Engine
    from repro.serving.session import ScriptedClient
    from repro.serving.workloads import make_workload

    cfg = get_config("llama3.2-1b", tiny=True)
    n = 6 if quick else 12
    reqs = scale_to_budget(
        make_workload(seed=9, n_requests=n, rate_rps=2.0, max_ctx=220),
        256, prompt_cap=48, gen_cap=12, ret_cap=8, max_segments=3)

    def pcts(vals):
        return (round(float(np.percentile(vals, 50)), 5),
                round(float(np.percentile(vals, 99)), 5))

    results = []
    policies = ["vllm", "preserve", "swap", "infercept"]
    legacy_streams = None
    for name in policies:
        # legacy closed loop: the stream-identity oracle (one policy is
        # enough — §6 pins cross-policy identity — but compare each)
        eng = Engine(cfg, POLICIES[name], page_size=16, n_pages=128,
                     max_model_len=256, seed=0)
        for r in copy.deepcopy(reqs):
            eng.add_request(r)
        fin = eng.run()
        assert fin.drained and len(fin) == len(reqs), f"legacy {name}"
        legacy_streams = {r.rid: eng.generated_text(r) for r in fin}

        eng2 = Engine(cfg, POLICIES[name], page_size=16, n_pages=128,
                      max_model_len=256, seed=0)
        sc = ScriptedClient(eng2)
        t0 = time.time()
        streams = sc.replay(copy.deepcopy(reqs))
        wall = time.time() - t0
        fin2 = eng2.finished
        assert len(fin2) == len(reqs), f"session {name} incomplete"
        metrics = [r.latency_metrics() for r in fin2]
        ttft_p50, ttft_p99 = pcts([m["ttft"] for m in metrics])
        nl_p50, nl_p99 = pcts([m["normalized"] for m in metrics])
        row = {
            "policy": name,
            "n_requests": len(reqs),
            "ttft_p50_s": ttft_p50,
            "ttft_p99_s": ttft_p99,
            "norm_lat_p50_s_per_tok": nl_p50,
            "norm_lat_p99_s_per_tok": nl_p99,
            "virtual_time_s": round(eng2.now, 3),
            "decode_tokens": eng2.counters["decode_tokens"],
            "streams_match_legacy": streams == legacy_streams,
            "wall_s": round(wall, 3),
        }
        results.append(row)
        _row(f"serve_sweep_{name}", wall * 1e6,
             {k: v for k, v in row.items()
              if k not in ("policy", "wall_s")})
        assert row["streams_match_legacy"], \
            f"session API diverged from the legacy engine under {name}"
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "serve_sweep.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)


def bench_overlap_sweep(quick=False):
    """Pipelined engine step (DESIGN.md §12): overlap-on vs overlap-off on
    the real engine for the swap-heavy policies — swap-hidden fraction
    (DMA bytes that fit under the model window), tool-overlap fraction
    (virtual tool pause coinciding with engine-busy time), pipeline
    bubbles, and p50/p99 normalized latency per mode; greedy token streams
    are asserted bit-identical overlap on vs off. Writes
    benchmarks/overlap_sweep.json."""
    import json
    import os
    from repro.configs import get_config
    from repro.core import POLICIES
    from repro.launch.serve import scale_to_budget
    from repro.serving.engine import Engine
    from repro.serving.workloads import make_workload

    cfg = get_config("llama3.2-1b", tiny=True)
    n = 6 if quick else 12
    reqs = scale_to_budget(
        make_workload(seed=13, n_requests=n, rate_rps=2.0, max_ctx=220),
        256, prompt_cap=48, gen_cap=12, ret_cap=8, max_segments=3)

    def pcts(vals):
        return (round(float(np.percentile(vals, 50)), 5),
                round(float(np.percentile(vals, 99)), 5))

    results = []
    for policy in ["swap", "infercept"]:
        streams = {}
        rows = {}
        for overlap in (True, False):
            eng = Engine(cfg, POLICIES[policy], page_size=16, n_pages=128,
                         max_model_len=256, seed=0, overlap=overlap)
            for r in copy.deepcopy(reqs):
                eng.add_request(r)
            t0 = time.time()
            fin = eng.run()
            wall = time.time() - t0
            assert fin.drained and len(fin) == len(reqs), (policy, overlap)
            streams[overlap] = {r.rid: eng.generated_text(r) for r in fin}
            metrics = [r.latency_metrics() for r in fin]
            nl_p50, nl_p99 = pcts([m["normalized"] for m in metrics])
            c = eng.counters
            st = eng.sched.stats
            planned_bytes = (st.swapped_out_tokens
                             + st.swapped_in_tokens) * eng.cost.m_bytes
            tool_s = c["tool_seconds"]
            rows[overlap] = {
                "policy": policy,
                "overlap": overlap,
                "swap_hidden_bytes": int(c["swap_overlap_bytes"]),
                "swap_planned_bytes": int(planned_bytes),
                "swap_hidden_frac": round(
                    c["swap_overlap_bytes"] / planned_bytes, 4)
                    if planned_bytes else 0.0,
                "tool_seconds": round(tool_s, 4),
                "tool_overlap_frac": round(
                    c["overlapped_tool_seconds"] / tool_s, 4)
                    if tool_s else 0.0,
                "pipeline_bubbles": int(c["pipeline_bubbles"]),
                "pipeline_bubble_s": round(c["pipeline_bubble_s"], 6),
                "norm_lat_p50_s_per_tok": nl_p50,
                "norm_lat_p99_s_per_tok": nl_p99,
                "virtual_time_s": round(eng.now, 4),
                "wall_s": round(wall, 3),
            }
        identical = streams[True] == streams[False]
        assert identical, f"overlap on/off streams diverged under {policy}"
        # overlap-on must actually hide swap DMA on swap-traffic policies
        assert rows[True]["swap_hidden_bytes"] > 0, policy
        assert rows[False]["swap_hidden_bytes"] == 0, policy
        for overlap in (True, False):
            rows[overlap]["streams_identical"] = identical
            results.append(rows[overlap])
            _row(f"overlap_sweep_{policy}_{'on' if overlap else 'off'}",
                 rows[overlap]["wall_s"] * 1e6,
                 {k: v for k, v in rows[overlap].items()
                  if k not in ("policy", "overlap", "wall_s")})
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "overlap_sweep.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)


def bench_waste_trace(quick=False):
    """Waste-attribution telemetry (DESIGN.md §13) on the real engine:
    per policy, run the Table-1-style workload traced and untraced and
    assert the streams and all legacy counters are bit-identical (the
    NullTracer identity contract), collect the WasteLedger breakdown,
    re-assert sum(causes) == total within float tolerance, check the
    engine<->simulator ledger mirror for the token-granular policies, and
    export + validate a Perfetto trace for infercept. Writes
    benchmarks/waste_breakdown.json and benchmarks/trace_infercept.json
    (the CI smoke re-validates both via repro.obs.check)."""
    import json
    import os
    from repro.configs import get_config
    from repro.core import POLICIES
    from repro.launch.serve import scale_to_budget
    from repro.obs.check import check_breakdown
    from repro.obs.export import validate_trace, write_trace
    from repro.obs.ledger import waste_report
    from repro.obs.trace import SpanTracer
    from repro.serving.engine import Engine
    from repro.serving.workloads import make_workload
    from repro.sim.simulator import simulate
    from repro.utils.hw import TPU_V5E
    from repro.core.costmodel import CostModel

    cfg = get_config("llama3.2-1b", tiny=True)
    n = 6 if quick else 12
    reqs = scale_to_budget(
        make_workload(seed=17, n_requests=n, rate_rps=2.0, max_ctx=220),
        256, prompt_cap=48, gen_cap=12, ret_cap=8, max_segments=3)

    def run(policy, tracer):
        eng = Engine(cfg, POLICIES[policy], page_size=16, n_pages=128,
                     max_model_len=256, seed=0, tracer=tracer)
        for r in copy.deepcopy(reqs):
            eng.add_request(r)
        fin = eng.run()
        assert fin.drained and len(fin) == len(reqs), policy
        return eng, {r.rid: eng.generated_text(r) for r in fin}

    out_dir = os.path.dirname(os.path.abspath(__file__))
    results = {}
    for policy in ["vllm", "preserve", "swap", "infercept"]:
        tracer = SpanTracer()
        t0 = time.time()
        eng, streams = run(policy, tracer)
        wall = time.time() - t0
        eng_off, streams_off = run(policy, None)
        assert streams == streams_off, \
            f"tracing perturbed the streams under {policy}"
        assert dict(eng.counters) == dict(eng_off.counters), \
            f"tracing perturbed the counters under {policy}"

        rep = waste_report(eng.ledger)
        rep["virtual_time_s"] = round(eng.now, 4)
        rep["trace_events"] = len(eng.tracer)
        results[policy] = rep
        assert not check_breakdown(rep), (policy, check_breakdown(rep))

        if policy in ("vllm", "preserve"):
            # token-granular policies: the simulator's ledger mirrors the
            # engine's bit-for-bit at matched capacity (swap policies
            # page-align their moves, the sim stays token-granular)
            cost = CostModel(cfg=cfg, chip=TPU_V5E, n_chips=1)
            res = simulate(copy.deepcopy(reqs), POLICIES[policy], cost,
                           gpu_capacity_tokens=eng.sched.gpu_capacity)
            sl = res.ledger
            assert sl.causes == eng.ledger.causes, policy
            assert sl.gpu_byte_seconds == eng.ledger.gpu_byte_seconds
            assert sl.total_check == eng.ledger.total_check, policy
            # and the sim's ledger equals its own legacy waste fields
            assert sl.causes["preserve_pinned"] == res.waste_preserved
            assert sl.causes["recompute"] == res.waste_recompute
            rep["sim_mirror"] = "exact"

        if policy == "infercept":
            trace_path = os.path.join(out_dir, "trace_infercept.json")
            n_ev = write_trace(eng.tracer, trace_path)
            with open(trace_path) as f:
                errs = validate_trace(json.load(f))
            assert not errs, errs[:5]
            rep["trace_file"] = os.path.basename(trace_path)
            rep["trace_events"] = n_ev

        _row(f"waste_trace_{policy}", wall * 1e6, {
            "total_waste_bs": round(rep["total_waste"], 4),
            "waste_fraction": round(rep["waste_fraction"], 6),
            "top_cause": max(rep["causes"], key=rep["causes"].get),
            "intercepts": rep["intercepts"]["n"],
            "trace_events": rep["trace_events"],
        })
    with open(os.path.join(out_dir, "waste_breakdown.json"), "w") as f:
        json.dump(results, f, indent=2)


def bench_predictive_sweep(quick=False):
    """Predictive intercept scheduling + speculative resume (DESIGN.md
    §14) on a saturated agent workload: the learned per-kind EMA
    estimator vs the paper's dynamic rule vs the oracle (gap_closed is
    the fraction of the dynamic->oracle normalized-latency gap the
    learned mode recovers; the PR's acceptance bar is >= 0.5), plus
    speculative-resume accept rates and grafted-token counts under a
    perfect and a templated predictor. Writes
    benchmarks/predictive_sweep.json next to this file."""
    import json
    import os
    from repro.core import POLICIES, DurationEstimator
    from repro.serving.api_executor import (OracleToolResultPredictor,
                                            TemplateToolResultPredictor)
    from repro.serving.workloads import make_agent_workload
    from repro.sim import simulate
    cost = _cost()
    vocab = 50_000
    cap = 30_000
    # saturated point: Poisson bursts of multi-turn sessions against a
    # pinched KV pool, where Eq. 5 evict-vs-preserve decisions (and thus
    # the duration estimate feeding them) control the latency
    reqs = make_agent_workload(
        seed=7, n_sessions=100, rate_rps=6.0, vocab=vocab, n_templates=6,
        system_prompt_len=300, kinds=("math", "qa", "chatbot", "image"),
        turns=(2, 4), turn_gap_s=4.0, hist_per_turn=80, prefix_share=0.6,
        gen_tokens=(60, 20), final_gen=(60, 20), max_tool_calls=4,
        max_ctx=4096)

    def run(label, **kw):
        t0 = time.time()
        r = simulate(copy.deepcopy(reqs), POLICIES["infercept"], cost,
                     gpu_capacity_tokens=cap, **kw)
        return label, r, time.time() - t0

    modes = [
        run("dynamic"),
        run("oracle", estimator=DurationEstimator(mode="oracle")),
        run("learned", estimator=DurationEstimator(mode="learned")),
    ]
    lat = {label: r.normalized_latency() for label, r, _ in modes}
    gap = lat["dynamic"] - lat["oracle"]
    gap_closed = ((lat["dynamic"] - lat["learned"]) / gap
                  if abs(gap) > 1e-9 else 1.0)
    results = {"estimator": [], "speculation": [],
               "gap_closed": round(gap_closed, 3),
               "meets_half_gap": bool(gap_closed >= 0.5)}
    for label, r, wall in modes:
        row = {"mode": label,
               "norm_lat_p50": round(r.normalized_latency(), 5),
               "norm_lat_p90": round(r.normalized_latency(90), 5),
               "tput_rps": round(r.throughput_rps(), 4),
               "waste_frac": round(r.waste_fraction(), 4)}
        results["estimator"].append(row)
        _row(f"predictive_{label}", wall / max(1, r.iterations) * 1e6,
             {**{k: v for k, v in row.items() if k != "mode"},
              "gap_closed": round(gap_closed, 3)})

    # speculative resume: perfect predictor (upper bound) vs a fixed
    # per-kind template (rejected forks), on the learned estimator. Two
    # memory regimes: with KV headroom a graft's skipped re-prefill is a
    # straight win; at a pinched pool the fork's grafted context competes
    # for the capacity InferCept is rationing, so speculation can LOSE —
    # the sweep reports both so the trade is visible
    preds = [("spec_oracle", OracleToolResultPredictor(vocab)),
             ("spec_template", TemplateToolResultPredictor(
                 {k: list(range(3)) for k in
                  ("math", "qa", "chatbot", "image")}))]
    for regime, regime_cap in [("headroom", None), ("saturated", cap)]:
        base_lat = None
        for label, pred in [("baseline", None)] + preds:
            t0 = time.time()
            kw = dict(estimator=DurationEstimator(mode="learned"),
                      gpu_capacity_tokens=regime_cap)
            if pred is not None:
                kw.update(speculate=True, predictor=pred, spec_vocab=vocab)
            r = simulate(copy.deepcopy(reqs), POLICIES["infercept"], cost,
                         **kw)
            wall = time.time() - t0
            if pred is None:
                base_lat = r.normalized_latency()
                continue
            validated = r.spec_accepted + r.spec_rejected
            row = {"predictor": label, "regime": regime,
                   "norm_lat_p50": round(r.normalized_latency(), 5),
                   "norm_lat_vs_base": round(
                       r.normalized_latency() / max(1e-9, base_lat), 3),
                   "spec_forks": r.spec_forks,
                   "accept_rate": round(r.spec_accepted / validated, 4)
                   if validated else 0.0,
                   "grafted_tokens": r.spec_grafted_tokens,
                   "speculation_wasted_bs":
                       round(r.ledger.causes["speculation_wasted"], 1)}
            results["speculation"].append(row)
            _row(f"predictive_{label}_{regime}",
                 wall / max(1, r.iterations) * 1e6,
                 {k: v for k, v in row.items()
                  if k not in ("predictor", "regime")})

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "predictive_sweep.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)


def bench_fault_sweep(quick=False, sanitize=False):
    """Fault-tolerant interception (DESIGN.md §15): goodput, p99
    normalized latency, and the waste breakdown vs injected tool-fault
    rate {0, 0.1, 0.3} under the deterministic chaos harness, with one
    scripted mid-run cancellation per point so the ``cancelled`` cause is
    populated. The sweep re-asserts the blast-radius contract in-line:
    every session that survives a faulty run emits the fault-free run's
    exact token stream. Writes benchmarks/fault_sweep.json — a
    name->report dict whose rows carry ``causes`` +
    ``total_waste_check`` so ``repro.obs.check`` re-validates the ledger
    invariant in CI.

    With ``sanitize=True`` every faulty point additionally runs under the
    KV-page sanitizer + lifecycle checker (DESIGN.md §16): the run must
    report ZERO findings (written to benchmarks/fault_sweep_findings.json
    for the CI artifact when it doesn't) and its streams must be
    bit-identical to the sanitize=False run at the same rate."""
    import json
    import os
    from repro.configs import get_config
    from repro.core import POLICIES
    from repro.core.request import InterceptDirective, SamplingParams
    from repro.serving.api_executor import (ChaosToolExecutor,
                                            VirtualTimeToolExecutor)
    from repro.serving.engine import Engine
    from repro.serving.session import InferCeptClient
    cfg = get_config("llama3.2-1b", tiny=True)
    n_sessions = 8 if quick else 20
    max_new = 24 if quick else 32

    def detector():
        fired = {}

        def det(req, tid, now):
            seen = fired.setdefault(req.rid, set())
            if req.output_tokens in (5, 12) \
                    and req.output_tokens not in seen:
                seen.add(req.output_tokens)
                return InterceptDirective(kind="math", duration_hint=0.05)
            return None
        return det

    def run(rate, sanitized=False):
        t0 = time.time()
        eng = Engine(cfg, POLICIES["infercept"], page_size=16, n_pages=128,
                     max_model_len=256, seed=0, sanitize=sanitized)
        cl = InferCeptClient(eng)
        tools = ChaosToolExecutor(
            VirtualTimeToolExecutor(cfg.vocab_size, n_tokens=4,
                                    duration=0.05),
            seed=11, failure_rate=rate, timeout_rate=rate / 2)
        hs = [cl.submit([10 + i, 11 + i, 12 + i, 13 + i],
                        detector=detector(), max_new_tokens=max_new,
                        tools=tools,
                        sampling=SamplingParams(tool_timeout_s=1.0,
                                                tool_retries=1,
                                                tool_backoff_s=0.01))
              for i in range(n_sessions)]
        # one deterministic mid-run cancellation so every point charges
        # the ``cancelled`` cause too
        cancel_rid, done = hs[1].rid, []

        def hook(e):
            req = e.sched.live.get(cancel_rid)
            if not done and req is not None and req.output_tokens >= 6:
                done.append(True)
                e.cancel_request(cancel_rid)
        eng.on_plan = hook
        cl.poll()
        wall = time.time() - t0
        assert all(h.done for h in hs)
        streams = {h.rid: cl.token_ids(h) for h in hs if h.finished}
        return eng, hs, streams, wall

    results = {}
    clean = None
    for rate in (0.0, 0.1, 0.3):
        eng, hs, streams, wall = run(rate, sanitized=sanitize)
        if sanitize:
            findings = [str(f) for f in eng.sanitizer.findings]
            if findings:
                fout = os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "fault_sweep_findings.json")
                with open(fout, "w") as f:
                    json.dump({"rate": rate, "findings": findings}, f,
                              indent=2)
            assert not findings, \
                f"sanitizer findings at rate {rate}: {findings[:5]}"
            # observation-only: the sanitized run's streams must match
            # the plain run's bit-for-bit
            _, _, streams_off, _ = run(rate, sanitized=False)
            assert streams == streams_off, \
                f"sanitize=True perturbed streams at rate {rate}"
        if rate == 0.0:
            clean = streams
        else:
            for rid, stream in streams.items():
                assert stream == clean[rid], \
                    f"blast radius: session {rid} diverged at rate {rate}"
        fins = [h.request for h in hs if h.finished]
        lat = [r.latency_metrics()["normalized"] for r in fins]
        row = {
            "failure_rate": rate,
            "timeout_rate": rate / 2,
            "sessions": n_sessions,
            "finished": len(fins),
            "failed": eng.counters["sessions_failed"],
            "cancelled": eng.counters["sessions_cancelled"],
            "tool_faults": eng.counters["tool_faults"],
            "tool_retries": eng.counters["tool_retries"],
            "tool_timeouts": eng.counters["tool_timeouts"],
            "goodput_tok_s": round(
                sum(r.output_tokens for r in fins) / max(1e-9, eng.now), 3),
            "norm_lat_p50": round(float(np.percentile(lat, 50)), 5),
            "norm_lat_p99": round(float(np.percentile(lat, 99)), 5),
            "waste_fraction": round(eng.ledger.waste_fraction(), 4),
            "causes": dict(eng.ledger.causes),
            "total_waste_check": eng.ledger.total_check,
        }
        results[f"rate_{rate}"] = row
        _row(f"fault_sweep_r{rate}", wall / max(1, n_sessions) * 1e6,
             {k: v for k, v in row.items()
              if k not in ("causes", "total_waste_check")})

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fault_sweep.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)


def bench_quant_sweep(quick=False):
    """Quantized KV pages (DESIGN.md §17): per-kv_dtype capacity and
    fidelity vs the fp32 pools on the agent workload.

    Per dtype: physical bytes/resident-token (per-page scale leaves
    priced in), max co-resident sessions at a FIXED byte pool (the fp32
    pool's physical size), per-page swap slab bytes (payload + scales,
    one contiguous DMA), run swap traffic, and the greedy-stream
    agreement rate vs the fp32 baseline at matched (rid, position) —
    exact equality is impossible under requantize-on-append, so the rate
    quantifies the bounded divergence. Every row carries ``causes`` +
    ``total_waste_check`` so ``repro.obs.check`` re-validates the ledger
    invariant in CI. Writes benchmarks/quant_sweep.json."""
    import json
    import os

    import jax

    from repro.configs import get_config
    from repro.core import POLICIES
    from repro.serving.engine import Engine
    from repro.serving.workloads import make_agent_workload
    cfg = get_config("llama3.2-1b", tiny=True)
    n_sessions = 2 if quick else 4
    reqs = make_agent_workload(
        seed=5, n_sessions=n_sessions, rate_rps=2.0, vocab=cfg.vocab_size,
        n_templates=2, system_prompt_len=50, turns=(2, 2), turn_gap_s=3.0,
        hist_per_turn=12, prefix_share=0.75, gen_tokens=(8, 3),
        final_gen=(8, 3), ret_tokens=(6, 2), max_tool_calls=2, max_ctx=240)
    max_ctx, n_pages, page = 256, 128, 16

    def run(kv_dtype):
        t0 = time.time()
        eng = Engine(cfg, POLICIES["infercept"], page_size=page,
                     n_pages=n_pages, max_model_len=max_ctx, seed=0,
                     prefix_cache=True, kv_dtype=kv_dtype)
        for r in copy.deepcopy(reqs):
            eng.add_request(r)
        fin = eng.run()
        assert len(fin) == len(reqs), kv_dtype
        streams = {r.rid: eng.generated_text(r) for r in fin}
        return eng, streams, time.time() - t0

    def slab_bytes(eng):
        # bytes one page moves through swap_pack: payload + scale leaves
        return int(sum(int(leaf.nbytes) // leaf.shape[1]
                       for leaf in jax.tree.leaves(eng.pools)))

    def agreement(streams, baseline):
        num = den = 0
        for rid, s in streams.items():
            b = baseline[rid]
            n = min(len(s), len(b))
            num += sum(1 for i in range(n) if s[i] == b[i])
            den += max(len(s), len(b))
        return num / max(1, den)

    base_eng, base_streams, base_wall = run(None)
    fixed_pool_bytes = base_eng.kv_token_bytes * n_pages * page
    results = {}
    for name in (None, "int8", "float8_e4m3", "float8_e5m2"):
        eng, streams, wall = (base_eng, base_streams, base_wall) \
            if name is None else run(name)
        tokens_at_fixed_pool = fixed_pool_bytes // eng.kv_token_bytes
        row = {
            "kv_dtype": name or "float32",
            "bytes_per_resident_token": eng.kv_token_bytes,
            "bytes_reduction_vs_fp32": round(
                base_eng.kv_token_bytes / eng.kv_token_bytes, 3),
            "swap_slab_bytes_per_page": slab_bytes(eng),
            "slab_reduction_vs_fp32": round(
                slab_bytes(base_eng) / slab_bytes(eng), 3),
            "max_coresident_sessions_fixed_pool":
                int(tokens_at_fixed_pool // max_ctx),
            "swap_bytes": eng.counters["swap_bytes"],
            "scale_reset_pages":
                eng.counters["kv_quant_scale_reset_pages"],
            "stream_agreement_vs_fp32": round(
                agreement(streams, base_streams), 4),
            "waste_fraction": round(eng.ledger.waste_fraction(), 4),
            "causes": dict(eng.ledger.causes),
            "total_waste_check": eng.ledger.total_check,
        }
        results[row["kv_dtype"]] = row
        _row(f"quant_sweep_{row['kv_dtype']}",
             wall / max(1, n_sessions) * 1e6,
             {k: v for k, v in row.items()
              if k not in ("causes", "total_waste_check")})
        if name is not None:
            assert 2 * eng.kv_token_bytes <= base_eng.kv_token_bytes, \
                f"{name}: quantized pools must at least halve KV bytes"
            assert 2 * slab_bytes(eng) <= slab_bytes(base_eng), name

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "quant_sweep.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)


def bench_multi_gpu_scaling(quick=False):
    """13B on 1 vs 2 GPUs, 70B on 4 (paper §5.1: distributed setting gains
    grow because more HBM per GPU is left for KV)."""
    from repro.core import POLICIES
    from repro.serving.workloads import make_workload
    combos = [("vicuna-13b", 1), ("vicuna-13b", 2)]
    if not quick:
        combos.append(("llama3-70b", 4))
    n = 60 if quick else 120
    for model, chips in combos:
        cost = _cost(model, n_chips=chips)
        reqs = make_workload(seed=6, n_requests=n, rate_rps=1.5,
                             max_ctx=4096)
        res = _run_policies({k: POLICIES[k] for k in ["vllm", "infercept"]},
                            reqs, cost)
        sp = (res["vllm"][0].normalized_latency()
              / max(1e-9, res["infercept"][0].normalized_latency()))
        _row(f"s51_{model.replace('-', '_')}_x{chips}",
             res["infercept"][1] * 1e6, {
                 "kv_capacity_tokens": cost.kv_capacity_tokens(),
                 "speedup_vs_vllm": round(sp, 2),
             })


ALL = [bench_table1_workload, bench_fig2_end2end, bench_fig3_breakdown,
       bench_waste_s32, bench_estimator, bench_single_augment,
       bench_kernels, bench_multi_gpu_scaling, bench_prefix_cache_sweep,
       bench_serve_sweep,
       bench_overlap_sweep, bench_waste_trace, bench_predictive_sweep,
       bench_fault_sweep, bench_quant_sweep]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--serve-sweep", action="store_true",
                    help="run only the session-API per-policy TTFT / "
                         "normalized-latency sweep "
                         "(alias for --only serve_sweep)")
    ap.add_argument("--overlap-sweep", action="store_true",
                    help="run only the pipelined-step overlap on/off sweep "
                         "(alias for --only overlap_sweep)")
    ap.add_argument("--waste-trace", action="store_true",
                    help="run only the waste-attribution telemetry sweep "
                         "(alias for --only waste_trace)")
    ap.add_argument("--predictive-sweep", action="store_true",
                    help="run only the learned-estimator / speculative-"
                         "resume sweep (alias for --only predictive_sweep)")
    ap.add_argument("--fault-sweep", action="store_true",
                    help="run only the chaos fault-injection sweep "
                         "(goodput / p99 latency / waste vs fault rate; "
                         "alias for --only fault_sweep)")
    ap.add_argument("--quant-sweep", action="store_true",
                    help="run only the quantized-KV capacity/fidelity "
                         "sweep (bytes per resident token, swap slab "
                         "bytes, stream agreement per kv_dtype; alias "
                         "for --only quant_sweep)")
    ap.add_argument("--sanitize", action="store_true",
                    help="run the fault sweep under the KV-page sanitizer "
                         "+ lifecycle checker (DESIGN.md §16): assert zero "
                         "findings and streams bit-identical to the "
                         "unsanitized run")
    args = ap.parse_args()
    if args.serve_sweep:
        args.only = "serve_sweep"
    if args.overlap_sweep:
        args.only = "overlap_sweep"
    if args.waste_trace:
        args.only = "waste_trace"
    if args.predictive_sweep:
        args.only = "predictive_sweep"
    if args.fault_sweep:
        args.only = "fault_sweep"
    if args.quant_sweep:
        args.only = "quant_sweep"
    print("name,us_per_call,derived")
    for fn in ALL:
        if args.only and args.only not in fn.__name__:
            continue
        if fn is bench_fault_sweep:
            fn(quick=args.quick, sanitize=args.sanitize)
        else:
            fn(quick=args.quick)


if __name__ == "__main__":
    main()
