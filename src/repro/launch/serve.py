"""Serving launcher: drive the intercept-aware engine through the
first-class session API (DESIGN.md §11).

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --tiny \
        --policy infercept --requests 8 --rate 2.0

Two clients share one engine, demonstrating the API/executor boundary:

  * a ``ScriptedClient`` replays the Table-1 workload — the legacy closed
    loop expressed as sessions whose interceptions fire by generated-token
    count and resume from the engine's virtual-time stub;
  * one live session with caller-driven interception: a detector pauses it
    mid-generation and a ``WallClockToolExecutor`` round-trips a real
    Python "tool", its measured wall-clock latency becoming the
    interception's virtual duration.

Real model, paged KV, virtual clock; on a TPU the paged path runs the
Pallas kernels, on the CPU their jnp mirrors. ``serve()`` is the part
``chip_smoke.py`` reuses at full width on the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List

import jax

from repro.configs import get_config
from repro.core import POLICIES
from repro.core.request import InterceptDirective, Segment
from repro.obs.export import format_stats_line, format_summary, write_trace
from repro.obs.trace import SpanTracer
from repro.serving.api_executor import (AsyncToolRuntime,
                                        WallClockToolExecutor)
from repro.serving.engine import Engine
from repro.serving.session import (InterceptEvent, SamplingParams,
                                   ScriptedClient)
from repro.serving.workloads import make_workload
from repro.utils.compile_cache import enable_compile_cache


def scale_to_budget(reqs, max_len: int, *, prompt_cap: int = 0,
                    gen_cap: int = 16, ret_cap: int = 8,
                    max_segments: int = 4):
    """Clamp scripted requests to a demo engine's context budget.
    ``prompt_cap`` defaults to max_len // 4."""
    prompt_cap = prompt_cap or max_len // 4
    for r in reqs:
        r.prompt_len = min(r.prompt_len, prompt_cap)
        if r.prompt_tokens is not None:
            # keep the prompt_len == len(prompt_tokens) invariant for
            # explicit-prompt (agent/session) workloads
            r.prompt_tokens = r.prompt_tokens[:r.prompt_len]
        r.target_ctx = r.prompt_len
        for s in r.segments:
            s.gen_tokens = min(s.gen_tokens, gen_cap)
            if s.interception:
                s.interception.returned_tokens = min(
                    s.interception.returned_tokens, ret_cap)
        r.segments = r.segments[:max_segments]
        # an empty script has no final segment to terminate on — give it
        # one instead of assuming segments[-1] exists
        if not r.segments:
            r.segments = [Segment(gen_tokens=8, interception=None)]
        elif r.segments[-1].interception is not None:
            r.segments[-1].interception = None
    return reqs


@dataclasses.dataclass
class ServeResult:
    handles: List[object]      # the scripted sessions
    live: object               # the live caller-driven session
    events: List[object]       # every event the run emitted
    wall_s: float              # host wall clock of the poll loop

    @property
    def sessions(self) -> List[object]:
        return self.handles + [self.live]


def serve(eng: Engine, reqs, sampling: SamplingParams, *,
          live_prompt_len: int = 32, stats_every: int = 0) -> ServeResult:
    """Replay ``reqs`` through a ScriptedClient and run one live session
    beside them, on ``eng``, until the engine drains. The live session
    (``sampling``, a prompt of ``live_prompt_len`` ids) is intercepted by
    a detector at its 8th generated token, and a WallClockToolExecutor
    round-trips a Python "tool" whose ids resume it."""
    scripted = ScriptedClient(eng, retain_events=True)
    handles = scripted.submit(reqs)
    client = scripted.client
    vocab = eng.cfg.vocab_size

    def detector(req, tid, now):
        if req.output_tokens == 8 and req.seg_idx == 0:
            return InterceptDirective(kind="tool", duration_hint=0.1,
                                      reason="detector")
        return None

    def calculator(call):
        return [(call.trigger_token_id or 0) % vocab, 7, 42]

    live = client.submit(
        [i % vocab for i in range(live_prompt_len)], sampling,
        detector=detector, max_new_tokens=24,
        tools=WallClockToolExecutor(calculator))

    t0 = time.time()
    if stats_every > 0:
        # bounded poll slices with a periodic one-line stats update; the
        # batch's drained flag says when the engine actually finished
        events = []
        while True:
            batch = client.poll(stats_every)
            events.extend(batch)
            print(format_stats_line(eng))
            if batch.drained:
                break
    else:
        events = client.poll()
    return ServeResult(handles, live, list(events), time.time() - t0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--policy", default="infercept",
                    choices=sorted(POLICIES))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pages", type=int, default=128)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for the live demo session")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass for the live demo session")
    ap.add_argument("--no-overlap", action="store_true",
                    help="serial engine step (the pipelined-step oracle, "
                         "DESIGN.md §12)")
    ap.add_argument("--tool-workers", type=int, default=2,
                    help="thread-pool size for off-thread tool execution "
                         "(0 = inline, the live tool blocks the loop)")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="record per-request spans and write a "
                         "Chrome/Perfetto trace_event JSON (open at "
                         "https://ui.perfetto.dev)")
    ap.add_argument("--stats-every", type=int, default=0, metavar="N",
                    help="print a one-line stats update every N engine "
                         "steps while serving (0 = off)")
    args = ap.parse_args()

    cfg = get_config(args.arch, tiny=args.tiny)
    reqs = scale_to_budget(
        make_workload(seed=0, n_requests=args.requests, rate_rps=args.rate,
                      max_ctx=args.max_len), args.max_len)

    cache_dir, _ = enable_compile_cache()
    eng = Engine(cfg, POLICIES[args.policy], page_size=args.page_size,
                 n_pages=args.pages, max_model_len=args.max_len,
                 overlap=not args.no_overlap,
                 tracer=SpanTracer() if args.trace else None)
    if args.tool_workers > 0:
        eng.async_tools = AsyncToolRuntime(max_workers=args.tool_workers)
    res = serve(eng, reqs,
                SamplingParams(temperature=args.temperature, top_k=16,
                               top_p=args.top_p, seed=1),
                stats_every=args.stats_every)
    live, events = res.live, res.events
    finished = [h for h in res.sessions if h.finished]
    intercepts = sum(isinstance(e, InterceptEvent) for e in events)
    print(f"device={jax.devices()[0].device_kind} "
          f"compile_cache={cache_dir}")
    print(f"policy={args.policy} finished={len(finished)}/"
          f"{len(res.sessions)} events={len(events)} "
          f"intercepts={intercepts} virtual_time={eng.now:.2f}s "
          f"wall={res.wall_s:.1f}s")
    st = eng.sched.stats
    print(f"decode_tokens={st.decode_tokens} recompute={st.recompute_tokens} "
          f"fresh={st.fresh_tokens} swapped_out={st.swapped_out_tokens} "
          f"preserves={st.preserves} discards={st.discards}")
    print(format_summary(eng))
    print(f"live session: state={live.state} "
          f"stream_len={len(live.client.token_ids(live))} "
          f"out={live.request.output_tokens}tok "
          f"paused={live.request.paused_time * 1e3:.2f}ms")
    for h in finished[:4]:
        m = h.request.latency_metrics()
        print(f"  rid={h.rid} out={m['output_tokens']}tok "
              f"norm_lat={m['normalized'] * 1e3:.2f}ms/tok "
              f"ttft={m['ttft']:.3f}s")
    if args.trace:
        n = write_trace(eng.tracer, args.trace)
        print(f"wrote {n} trace events to {args.trace} "
              f"(open at https://ui.perfetto.dev)")
    eng.close()


if __name__ == "__main__":
    main()
