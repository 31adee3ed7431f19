#!/usr/bin/env python3
"""Find a cell's knee: the highest offered rate at which the admission
backlog does not grow across the window.

    python3 bench/knee.py --workload <cell> --rates 0.1,0.2,0.3 \
        --seconds 40 [--seed N]

One process pays the set-up once (weights, engine, warm-up), then for
each rate in turn runs the cell's traffic at that rate through the same
open loop as ``bench/run.py`` (ramp, then the window), prints one line,
and cancels every session still open before the next rate. The backlog is
the number of sessions that have arrived and have no first token yet; the
line gives it at the window's open and close, with the tokens per second
and the tails of the turn latency. Not part of a benchmark run: its table
sets the rate that the cell's traffic file states.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))


def backlog(rec, t: float) -> int:
    return sum(1 for tr in rec.turns if tr.kind == "new" and tr.due <= t
               and (tr.first is None or tr.first > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    from harness import device, setup, spec
    from harness.loop import OpenLoop
    from harness.traffic import make_sessions
    from repro.serving.session import InferCeptClient
    from run import quantile

    cell = spec.resolve(args.workload)
    devs, _ = device.check_device(cell.chips)
    device.enable_compile_cache()
    conf = cell.config
    t0 = time.perf_counter()
    params = setup.make_weights(conf, args.seed)
    eng = setup.build_engine(setup.model_config(conf, cell.config_name),
                             params, conf["serving"])
    client = InferCeptClient(eng)
    setup.warm_programs(eng, conf["serving"]["warm"])
    jax.block_until_ready(eng.pools)
    print(f"set-up {time.perf_counter() - t0:.1f} s", flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        sessions = make_sessions(mix, args.seed + i, args.seconds,
                                 int(conf["vocab_size"]))
        loop = OpenLoop(client, sessions, ramp_s=float(mix["ramp_s"]),
                        seconds=args.seconds,
                        snapshot=lambda: dict(eng.metrics.counters))
        rec = loop.run()
        lo, hi = rec.t_open, rec.t_close
        waits = [(t.first if t.first is not None and t.first <= hi else hi)
                 - t.due for t in rec.turns if lo <= t.due <= hi]
        new = [(t.first if t.first is not None and t.first <= hi else hi)
               - t.due for t in rec.turns
               if lo <= t.due <= hi and t.kind == "new"]
        tokens = sum(1 for t in rec.token_times if lo <= t <= hi)
        gaps = [g for t, g in rec.gaps if lo <= t <= hi]
        row = {"rate_per_s": rate, "tokens_per_s": tokens / args.seconds,
               "backlog_open": backlog(rec, lo),
               "backlog_close": backlog(rec, hi),
               "turns": len(waits),
               "turn_p50_s": quantile(waits, 0.5) if waits else None,
               "turn_p90_s": quantile(waits, 0.9) if waits else None,
               "new_p90_s": quantile(new, 0.9) if new else None,
               "itl_p99_s": quantile(gaps, 0.99) if gaps else None,
               "steps": len(rec.steps),
               **{k: rec.at_close.get(k, 0) - rec.at_open.get(k, 0)
                  for k in ("sched_fresh_tokens", "sched_recompute_tokens",
                            "sched_decode_tokens", "engine_swap_bytes")},
               "memory_peak_bytes": device.memory_peak_bytes(devs)}
        print("knee " + json.dumps(row), flush=True)
        for lv in rec.sessions.values():
            if lv.handle is not None:
                client.cancel(lv.handle, reason="next rate")
        client.poll(10)
    client.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
