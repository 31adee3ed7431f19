#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix,
each a file under ``bench/``. One process, in this order:

1. device check: a TPU whose ``device_kind`` is in ``bench/data/
   peaks.json``, with as many chips as the cell asks for; anything else
   exits 3 with no result (there is no CPU fallback);
2. set-up, counted in ``setup_s``: JAX's persistent compilation cache in
   the checkout's ``.jax_cache`` (or ``JAX_COMPILATION_CACHE_DIR``), the
   weights made on the device from the seed, the engine under test
   (InferCept policy, fused and overlapped step, bf16, page 16), every
   program the traffic can run compiled or loaded and run once, and the
   ramp: the traffic running at the cell's rate until the population of
   paused sessions is steady;
3. the measured window of ``--seconds``: the open loop of
   ``bench/harness/loop.py`` drives the session API, the profiler traces
   the window when ``--trace 1``;
4. the correctness check of ``bench/harness/check.py``, after the device
   memory peak is read and the engine is freed.

The last line of standard output is the result, one JSON object; the last
lines of standard error are the numbers compared, each with its limit.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

def quantile(values, q: float) -> float:
    """Nearest-rank quantile: the smallest value with at least a share q
    of the values at or below it."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


@dataclasses.dataclass
class Window:
    """What a per-layer metric reader gets (``bench/metrics/<name>.py``)."""
    seconds: float
    steps: list
    step_work: list
    delta: dict
    trace: object
    peaks: object
    dims: dict
    roofline: Callable


def dims_of(conf: dict) -> dict:
    """Sizes the roofline functions read; weights, activations and KV are
    all bfloat16."""
    return {"n_layers": int(conf["num_hidden_layers"]),
            "d_model": int(conf["hidden_size"]),
            "n_heads": int(conf["num_attention_heads"]),
            "n_kv_heads": int(conf["num_key_value_heads"]),
            "head_dim": int(conf["head_dim"]),
            "d_ff": int(conf["intermediate_size"]),
            "vocab": int(conf["vocab_size"]),
            "kv_bytes": 2, "act_bytes": 2}


def log(*a):
    print(*a, flush=True)


def end_to_end(rec, seconds: float, setup_s: float, wanted) -> dict:
    lo, hi = rec.t_open, rec.t_close
    turns = [t for t in rec.turns if lo <= t.due <= hi]
    waits = [(t.first if t.first is not None and t.first <= hi else hi)
             - t.due for t in turns]
    gaps = [g for t, g in rec.gaps if lo <= t <= hi]
    tokens = sum(1 for t in rec.token_times if lo <= t <= hi)
    values = {"setup_s": (setup_s, "s"),
              "tokens_per_s": (tokens / seconds, "tokens/s")}
    if waits:
        values["turn_p90_s"] = (quantile(waits, 0.9), "s")
    if gaps:
        values["itl_p99_s"] = (quantile(gaps, 0.99), "s")
    kinds = {}
    for t in turns:
        kinds[t.kind] = kinds.get(t.kind, 0) + 1
    late = [d for _, d in rec.lateness]
    log(f"window: {seconds:.1f} s, tokens={tokens}, turns={len(turns)} "
        f"{json.dumps(kinds, sort_keys=True)}, unanswered_turns="
        f"{sum(1 for t in turns if t.first is None or t.first > hi)}, "
        f"itl_gaps={len(gaps)}, steps={len(rec.steps)}")
    if waits:
        split = {}
        for kind in sorted(kinds):
            ws = [w for w, t in zip(waits, turns) if t.kind == kind]
            split[kind] = round(quantile(ws, 0.9), 6)
        log(f"turn latency s: mean={sum(waits) / len(waits):.4f} "
            f"p50={quantile(waits, 0.5):.4f} p75={quantile(waits, 0.75):.4f} "
            f"p90={quantile(waits, 0.9):.4f} max={max(waits):.4f}; "
            f"p90 by turn kind {json.dumps(split)}")
    if late:
        log(f"generator lateness s: p50={quantile(late, 0.5):.6f} "
            f"p99={quantile(late, 0.99):.6f} max={max(late):.6f} "
            f"over {len(late)} actions")
    done = [lv for lv in rec.sessions.values()
            if lv.finished is not None and lo <= lv.finished <= hi]
    if done:
        # the paper's normalized latency: end-to-end seconds less the tool
        # pauses, per generated token
        arrived = {t.sid: t.due for t in rec.turns if t.kind == "new"}
        norm = [(lv.finished - arrived[lv.spec.sid]
                 - sum(c.duration_s for c in lv.spec.tools))
                / max(1, len(lv.served)) for lv in done]
        log(f"normalized latency s/token (not a metric): sessions="
            f"{len(done)} p50={quantile(norm, 0.5):.4f} "
            f"p90={quantile(norm, 0.9):.4f}")
    return {k: {"value": v, "unit": u} for k, (v, u) in values.items()
            if k in wanted}


def run(args, cell=None, *, require_tpu: bool = True,
        use_cache: bool = True) -> dict:
    """One run of a cell (by default the one ``args.workload`` names);
    returns the result line's object. The CPU tests pass a small cell and
    ``require_tpu=False``."""
    import jax
    from harness import check, device, setup, spec, trace as tr
    from harness.loop import OpenLoop
    from harness.traffic import make_sessions

    if cell is None:
        cell = spec.resolve(args.workload)
    if require_tpu:
        devs, peaks = device.check_device(cell.chips)
    else:
        devs, peaks = jax.devices()[:1], device.load_peaks("TPU v5 lite")
    info = device.device_info(devs)
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']} peaks={peaks.kind}")
    counter = device.count_compiles()
    if use_cache:
        log(f"compile cache: {device.enable_compile_cache()}")
    conf, mix = cell.config, cell.traffic
    serving = conf["serving"]
    cfg = setup.model_config(conf, cell.config_name)

    t = time.perf_counter()
    params = setup.make_weights(conf, args.seed)
    jax.block_until_ready(params)
    log(f"setup: weights {time.perf_counter() - t:.3f} s, "
        f"{sum(x.nbytes for x in jax.tree.leaves(params))} B")
    t = time.perf_counter()
    eng = setup.build_engine(cfg, params, serving)
    from repro.serving.session import InferCeptClient
    client = InferCeptClient(eng)
    log(f"setup: engine {time.perf_counter() - t:.3f} s, pages="
        f"{eng.blocks.n_pages} max_pages={eng.max_pages}")
    t = time.perf_counter()
    c0 = counter.snapshot()
    warm = setup.warm_programs(eng, serving["warm"])
    c1 = counter.snapshot()
    log(f"setup: warm-up {time.perf_counter() - t:.3f} s, {warm}, "
        f"backend_compiles={c1[0] - c0[0]} cache_hits={c1[1] - c0[1]} "
        f"compile_s={c1[2] - c0[2]:.3f}")
    sessions = make_sessions(mix, args.seed, args.seconds,
                             int(conf["vocab_size"]))
    log(f"traffic: {args.workload} {len(sessions)} sessions at "
        f"{mix['rate_per_s']}/s, ramp {mix['ramp_s']} s, "
        f"{sum(s.n_turns for s in sessions)} turns")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None

    def snapshot():
        return {"c": dict(eng.metrics.counters), "k": counter.snapshot()}
    loop = OpenLoop(client, sessions, ramp_s=float(mix["ramp_s"]),
                    seconds=args.seconds, snapshot=snapshot,
                    trace_dir=trace_dir, track_work=bool(args.trace))
    rec = loop.run()
    setup_s = rec.t_open - T_START
    info["memory_peak_bytes"] = device.memory_peak_bytes(devs)
    ko, kc = rec.at_open["k"], rec.at_close["k"]
    log(f"compiles in window: backend={kc[0] - ko[0]} "
        f"cache_loads={kc[1] - ko[1]} compile_s={kc[2] - ko[2]:.3f}")
    delta = {k: v - rec.at_open["c"].get(k, 0)
             for k, v in rec.at_close["c"].items()
             if isinstance(v, (int, float))}
    log("window counters: " + json.dumps(
        {k: delta[k] for k in sorted(delta) if delta[k]}))
    breakdown = None
    if args.trace:
        t = time.perf_counter()
        events = tr.read_xplane(trace_dir)
        summary = tr.reduce(events)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: {len(events)} events, read in "
            f"{time.perf_counter() - t:.3f} s; layout "
            + json.dumps(tr.layout(events)))
        if summary is not None:
            info["busy_s"] = summary.busy_s
            info["window_s"] = summary.window_s
            top = sorted(summary.kernel_s.items(), key=lambda x: -x[1])[:8]
            log(f"trace: busy_s={summary.busy_s:.6f} window_s="
                f"{summary.window_s:.6f} op time by stem={top} calls="
                f"{[(k, summary.kernel_calls[k]) for k, _ in top]} "
                f"idle_by_span={summary.idle_by_span}")
            breakdown = {"device_ops": [list(x) for x in summary.top_ops],
                         "idle_gaps": [list(x) for x in summary.idle_gaps]}
        w = Window(seconds=args.seconds, steps=rec.steps,
                   step_work=rec.step_work, delta=delta, trace=summary,
                   peaks=peaks, dims=dims_of(conf),
                   roofline=spec.roofline)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"]).read(w)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = end_to_end(rec, args.seconds, setup_s,
                             {m["name"] for m in cell.end_to_end})
    attempted = sum(1 for t in rec.turns if rec.t_open <= t.due
                    <= rec.t_close)
    failed = sum(1 for lv in rec.sessions.values() if lv.failed)

    # correctness: the sample's streams, then free the engine's state
    picked = check.pick(rec.sessions, args.seed)
    samples = [check.Sample(lv.spec.sid, client.token_ids(lv.handle),
                            lv.served) for lv in picked]
    client.close()
    del client, eng, loop, rec, picked    # the handles hold the engine
    gc.collect()
    t = time.perf_counter()
    res = check.compare(params, conf, samples, control=bool(args.control))
    log(f"check: {res['sessions']} sessions, {res['tokens']} served tokens"
        f" in {time.perf_counter() - t:.3f} s; "
        + ("the float8_e4m3 reference in the program's place: "
           if args.control else "")
        + f"max_gap={res['max_gap']} mean_gap={res['mean_gap']} "
        f"mismatch_share={res['mismatch_share']} by_kind="
        f"{json.dumps(res['by_kind'])}; the program's max_gap="
        f"{res['program_max_gap']}")
    ok, checks = check.verdict(res, cell.limits)
    result = {"correct": ok and failed == 0, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # the control of the correctness check (bench/harness/check.py), never
    # used by the benchmark's own runs: the float8 reference is compared in
    # the program's place, so the run reads correct false
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from harness.device import DeviceError
    from harness.spec import SpecError
    try:
        result = run(args)
    except (DeviceError, SpecError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
