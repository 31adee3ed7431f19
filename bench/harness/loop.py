"""The open loop that drives the session API on the host clock.

Sessions are submitted at their wall-clock due times; the engine advances
one step at a time through ``InferCeptClient.poll(1)``; at each intercept
the session is held for the tool's wall-clock duration and then resumed
with the tool's returned token ids. A detector fires the intercepts at the
scripted generated-token counts, with ``duration_hint`` set to the drawn
duration. Every latency is taken from when its input was due, not from
when the loop got round to sending it; how late the loop ran is recorded.

The engine's virtual clock is never read.

The loop's own spans (``bench.step``, ``bench.submit``, ``bench.resume``,
``bench.wait``, and ``bench.window`` around the measured window) go into
the profiler's trace when one is running.
"""
from __future__ import annotations

import contextlib
import dataclasses
import heapq
import itertools
import time
from typing import Callable, Dict, List, Optional

import jax

from harness.traffic import Session

clock = time.perf_counter


@dataclasses.dataclass
class Turn:
    sid: int
    kind: str                  # "new" | "preserve" | "swap" | "discard"
    due: float                 # host clock
    first: Optional[float] = None


@dataclasses.dataclass
class Live:
    spec: Session
    handle: object = None
    stage: int = 0             # calls fired so far
    turn: Optional[Turn] = None
    last_tok: Optional[float] = None
    served: List[tuple] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None
    failed: bool = False
    hw: int = 0                # KV high-water mark (tokens)
    computed: int = 0


@dataclasses.dataclass
class Record:
    """What the loop saw; times on the host clock."""
    t_open: float = 0.0
    t_close: float = 0.0
    turns: List[Turn] = dataclasses.field(default_factory=list)
    token_times: List[float] = dataclasses.field(default_factory=list)
    gaps: List[tuple] = dataclasses.field(default_factory=list)  # (t, gap)
    lateness: List[tuple] = dataclasses.field(default_factory=list)
    steps: List[tuple] = dataclasses.field(default_factory=list)  # (t0, t1)
    # per step in the window, when work is tracked: [(n_new, ctx, n_fresh,
    # delivered)] per sequence that computed
    step_work: List[list] = dataclasses.field(default_factory=list)
    sessions: Dict[int, Live] = dataclasses.field(default_factory=dict)
    at_open: dict = dataclasses.field(default_factory=dict)
    at_close: dict = dataclasses.field(default_factory=dict)


def _resume_kind(req) -> str:
    if req.host_tokens > 0:
        return "swap"
    if req.device_tokens == 0:
        return "discard"
    return "preserve"


class OpenLoop:
    def __init__(self, client, sessions: List[Session], *, ramp_s: float,
                 seconds: float, snapshot: Callable[[], dict],
                 trace_dir: Optional[str] = None, track_work: bool = False):
        from repro.core.request import InterceptDirective, SamplingParams
        self._directive = InterceptDirective
        self._sampling = SamplingParams()          # greedy
        self.client = client
        self.eng = client.engine
        self.sessions = sessions
        self.ramp_s = float(ramp_s)
        self.seconds = float(seconds)
        self.snapshot = snapshot
        self.trace_dir = trace_dir
        self.track_work = track_work
        self.rec = Record()
        self._by_rid: Dict[int, Live] = {}
        self._heap: list = []
        self._seq = itertools.count()

    # -- spans ------------------------------------------------------------
    def _span(self, name):
        if self.trace_dir is None:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    # -- actions ------------------------------------------------------------
    def _push(self, due: float, action: str, live: Live, arg=None):
        heapq.heappush(self._heap, (due, next(self._seq), action, live, arg))

    def _detector(self, live: Live):
        spec = live.spec

        def detect(req, token_id, now):
            k = live.stage
            if req.gen_in_seg < spec.stretches[k]:
                return None
            live.stage += 1
            if k == len(spec.tools):
                return "finish"
            tool = spec.tools[k]
            return self._directive(kind=tool.kind,
                                   duration_hint=tool.duration_s,
                                   reason="detector")
        return detect

    def _submit(self, live: Live, due: float):
        live.turn = Turn(live.spec.sid, "new", due)
        self.rec.turns.append(live.turn)
        live.handle = self.client.submit(
            live.spec.prompt_ids.tolist(), self._sampling,
            detector=self._detector(live), kind=live.spec.kind,
            buffer_events=False)
        self._by_rid[live.handle.rid] = live
        if live.handle.state == "rejected":
            live.failed = True

    def _resume(self, live: Live, due: float, k: int):
        live.turn = Turn(live.spec.sid,
                         _resume_kind(live.handle.request), due)
        self.rec.turns.append(live.turn)
        self.client.resume(live.handle,
                           live.spec.tools[k].returned_ids.tolist())

    def _on_events(self, events, t: float):
        from repro.serving.session import (CancelledEvent, FailedEvent,
                                           FinishEvent, InterceptEvent,
                                           RejectedEvent, TokenEvent)
        rec = self.rec
        for ev in events:
            live = self._by_rid.get(ev.rid)
            if live is None:
                continue
            if isinstance(ev, TokenEvent):
                live.served.append((ev.index, ev.token_id,
                                    live.turn.kind if live.turn else "new"))
                rec.token_times.append(t)
                if live.turn is not None and live.turn.first is None:
                    live.turn.first = t
                elif live.last_tok is not None:
                    rec.gaps.append((t, t - live.last_tok))
                live.last_tok = t
            elif isinstance(ev, InterceptEvent):
                live.last_tok = None
                k = live.stage - 1
                self._push(t + live.spec.tools[k].duration_s, "resume",
                           live, k)
            elif isinstance(ev, FinishEvent):
                live.finished = t
                live.last_tok = None
            elif isinstance(ev, (FailedEvent, CancelledEvent,
                                 RejectedEvent)):
                live.failed = True

    def _work(self, delivered: Dict[int, int]) -> list:
        """Tokens each session computed in the step just taken, from the
        engine's per-session KV counts: (new tokens, context after, fresh
        tokens among them, tokens delivered)."""
        out = []
        for rid, live in self._by_rid.items():
            if live.finished is not None and live.finished < self._t_step0:
                continue
            st = self.eng.kv.get(rid)
            if st is None:
                continue
            now = int(st.computed)
            before = live.computed if now >= live.computed else 0
            if now != live.computed:
                n_new = now - before
                fresh = max(0, now - max(before, live.hw))
                out.append((n_new, now, fresh, delivered.get(rid, 0)))
                live.hw = max(live.hw, now)
                live.computed = now
        return out

    # -- the loop ------------------------------------------------------------
    def run(self) -> Record:
        rec = self.rec
        eng = self.eng
        t0 = clock()
        for s in self.sessions:
            self._push(t0 + s.due_s, "submit", Live(spec=s))
        rec.t_open = t_open = t0 + self.ramp_s
        rec.t_close = t_close = t_open + self.seconds
        opened = False
        window = contextlib.ExitStack()
        tracing = False
        try:
            while True:
                now = clock()
                if not opened and self.trace_dir and not tracing \
                        and now >= t_open - 2.0:
                    jax.profiler.start_trace(self.trace_dir)
                    tracing = True
                    now = clock()
                if not opened and now >= t_open:
                    opened = True
                    rec.at_open = self.snapshot()
                    window.enter_context(self._span("bench.window"))
                if now >= t_close:
                    break
                while self._heap and self._heap[0][0] <= now:
                    due, _, action, live, arg = heapq.heappop(self._heap)
                    fire = clock()
                    if action == "submit":
                        with self._span("bench.submit"):
                            self._submit(live, due)
                    else:
                        with self._span("bench.resume"):
                            self._resume(live, due, arg)
                    if opened:
                        rec.lateness.append((due, fire - due))
                sig = (eng.counters["mixed_iterations"],
                       eng.counters["swap_bytes"])
                self._t_step0 = s0 = clock()
                with self._span("bench.step"):
                    events = self.client.poll(1)
                s1 = clock()
                stepped = bool(events) or sig != (
                    eng.counters["mixed_iterations"],
                    eng.counters["swap_bytes"])
                if stepped:
                    self._on_events(events, s1)
                    if opened:
                        rec.steps.append((s0, s1))
                        if self.track_work:
                            got: Dict[int, int] = {}
                            for ev in events:
                                if type(ev).__name__ == "TokenEvent":
                                    got[ev.rid] = got.get(ev.rid, 0) + 1
                            rec.step_work.append(self._work(got))
                    elif self.track_work:
                        self._work({})
                    continue
                nxt = min(self._heap[0][0] if self._heap else t_close,
                          t_close,
                          t_open if not opened else t_close)
                if self.trace_dir and not tracing:
                    nxt = min(nxt, t_open - 2.0)
                delay = nxt - clock()
                if delay > 0:
                    with self._span("bench.wait"):
                        time.sleep(delay)
            rec.at_close = self.snapshot()
        finally:
            window.close()
            if tracing:
                jax.profiler.stop_trace()
        rec.sessions = {lv.spec.sid: lv for lv in self._by_rid.values()}
        return rec
