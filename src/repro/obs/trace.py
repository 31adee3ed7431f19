"""Per-request span tracing on the virtual clock.

A span tracer records three event shapes, all timestamped in virtual
seconds (the engine's cost-model clock, so traces are bit-reproducible):

  * complete spans — ``span(track, name, t0, t1, args)``: one lifecycle
    phase of one track. Request tracks (``("req", rid)``) carry queued /
    swapped_wait / prefill / decode / swap_out / swap_in spans; the
    engine tracks carry per-iteration ``iter`` and ``idle`` spans
    (``("engine", "step")``) and staged-DMA windows
    (``("engine", "dma")``). Spans on one track never overlap — the
    export validator enforces it.
  * async spans — ``async_begin``/``async_end``: tool-call windows
    ``[t_call, resume]``, which DO overlap request-track swap spans (a
    paused context can be swapping while its tool runs), so they live in
    Chrome's async-event namespace keyed by (cat, id). The end event
    carries the intercept's Eq. 5 branch and its predicted vs realized
    waste charge.
  * instants — point markers (discard, resume, swap_in_failed).

``NullTracer`` is the engine default: ``enabled`` is False and every
method is a no-op, so tracing-off runs allocate nothing — emission sites
guard arg-dict construction on ``tracer.enabled``.

``HostClock`` is the other clock: the host's own, always on, owned by
every ``Engine``. Its spans time the phases of ``Engine.step`` with
``time.perf_counter_ns`` and enter a ``jax.profiler.TraceAnnotation`` of
the same name, so a profile shows them on the host plane on the device
trace's clock; each span's self time (its duration less its child spans')
goes to a ``host_``-prefixed registry counter (``HOST_COUNTER_SCHEMA``).
It also stamps the turns waiting in the engine and the session latencies.
The engine hands it names and keys and never reads a time back, so no
scheduling decision can depend on it.
"""
from __future__ import annotations

import time
from typing import Dict, Hashable, List, Optional, Tuple

Track = Tuple[str, Hashable]          # (group, key): ("req", rid), ...


class SpanTracer:
    enabled = True

    def __init__(self):
        # (track, name, t0, t1, args)
        self.spans: list = []
        # (phase "b"|"e", cat, id, name, t, args)
        self.asyncs: list = []
        # (track, name, t, args)
        self.instants: list = []

    def span(self, track: Track, name: str, t0: float, t1: float,
             args: Optional[dict] = None):
        if t1 > t0:
            self.spans.append((track, name, t0, t1, args))

    def async_begin(self, cat: str, aid: Hashable, name: str, t: float,
                    args: Optional[dict] = None):
        self.asyncs.append(("b", cat, aid, name, t, args))

    def async_end(self, cat: str, aid: Hashable, name: str, t: float,
                  args: Optional[dict] = None):
        self.asyncs.append(("e", cat, aid, name, t, args))

    def instant(self, track: Track, name: str, t: float,
                args: Optional[dict] = None):
        self.instants.append((track, name, t, args))

    def __len__(self):
        return len(self.spans) + len(self.asyncs) + len(self.instants)


class NullTracer(SpanTracer):
    """The allocation-free default: records nothing."""
    enabled = False

    def __init__(self):          # no lists
        pass

    def span(self, track, name, t0, t1, args=None):
        pass

    def async_begin(self, cat, aid, name, t, args=None):
        pass

    def async_end(self, cat, aid, name, t, args=None):
        pass

    def instant(self, track, name, t, args=None):
        pass

    def __len__(self):
        return 0


# the host clock (ns); every read of the serving path goes through here,
# so a test can put a fake in its place before building an engine
host_ns = time.perf_counter_ns

# span name -> the host_ counter its self time adds to
SPAN_COUNTERS: Dict[str, str] = {
    "engine.plan": "plan_s",
    "engine.dispatch": "dispatch_s",
    "engine.swap_out": "swap_s",
    "engine.swap_in": "swap_s",
    "engine.swap_collect": "swap_s",
    "engine.sync": "sync_s",
    "engine.commit": "commit_s",
}
STEP_SPAN = "engine.step"


class _Span:
    """One open span: a profiler annotation plus its clock reads."""

    __slots__ = ("_hc", "_key", "_tm", "_t0", "child")

    def __init__(self, hc: "HostClock", key: Optional[str], tm):
        self._hc = hc
        self._key = key
        self._tm = tm
        self.child = 0

    def __enter__(self):
        self._tm.__enter__()
        hc = self._hc
        hc._stack.append(self)
        self._t0 = hc._now()
        return self

    def __exit__(self, *exc):
        self._close(exc)
        return False

    def _close(self, exc) -> int:
        """Close the span; returns its duration in ns."""
        hc = self._hc
        dur = hc._now() - self._t0
        stack = hc._stack
        stack.pop()
        if stack:
            stack[-1].child += dur
        pending = hc._pending
        if pending is not None and self._key is not None:
            pending[self._key] = pending.get(self._key, 0) \
                + dur - self.child
        self._tm.__exit__(*exc)
        return dur


class _Step(_Span):
    """The ``engine.step`` span. Its phases' self times are held until it
    closes and reach the counters only if ``count`` marked the step as
    one whose plan was not empty."""

    __slots__ = ("_counted",)

    def __init__(self, hc: "HostClock", tm):
        super().__init__(hc, None, tm)
        self._counted = False

    def count(self, **args: int):
        """Count this step, and label its profiler event with ``args``
        (small integers: the plan's sizes)."""
        self._counted = True
        self._tm.set_metadata(**args)

    def __enter__(self):
        self._hc._pending = {}
        return super().__enter__()

    def __exit__(self, *exc):
        dur = self._close(exc)
        hc = self._hc
        pending, hc._pending = hc._pending, None
        if self._counted:
            c = hc.counters
            c["step_s"] += dur * 1e-9
            c["steps"] += 1
            for key, ns in pending.items():
                c[key] += ns * 1e-9
        return False


class HostClock:
    """The host's clock inside ``Engine.step`` (module docstring).

    ``span(name)`` opens a phase span; ``step()`` opens the step span
    around them. ``wait_begin`` / ``wait_end`` time a turn's wait in the
    engine into ``host_queue_wait_s`` / ``host_queue_waits`` and the wait
    histograms; ``lap`` times session latencies into a histogram."""

    def __init__(self, registry):
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
        from repro.obs.metrics import HOST_COUNTER_SCHEMA
        self._annotation = TraceAnnotation
        self._step_annotation = StepTraceAnnotation
        self._now = host_ns
        self.registry = registry
        self.counters = registry.view("host_")
        self.counters.update(HOST_COUNTER_SCHEMA)
        self._stack: List[_Span] = []
        self._pending: Optional[Dict[str, int]] = None
        self._n = 0
        self._waits: Dict[Hashable, list] = {}     # key -> [t0, kind]
        self._laps: Dict[Hashable, int] = {}       # key -> t

    def span(self, name: str) -> _Span:
        return _Span(self, SPAN_COUNTERS.get(name),
                     self._annotation(name))

    def step(self) -> _Step:
        self._n += 1
        return _Step(self, self._step_annotation(STEP_SPAN,
                                                 step_num=self._n))

    # -- waits in the engine -------------------------------------------
    def wait_begin(self, key: Hashable, kind: str = "queued"):
        """A turn waits from now, unless it is already waiting: then it
        keeps its first stamp and takes ``kind`` ("queued" or
        "swapped_wait")."""
        w = self._waits.get(key)
        if w is None:
            self._waits[key] = [self._now(), kind]
        else:
            w[1] = kind

    def wait_end(self, key: Hashable):
        """The turn's first model work is issued: count its wait."""
        w = self._waits.pop(key, None)
        if w is None:
            return
        s = (self._now() - w[0]) * 1e-9
        self.counters["queue_wait_s"] += s
        self.counters["queue_waits"] += 1
        self.registry.observe("engine_queue_wait_s" if w[1] == "queued"
                              else "engine_swapped_wait_s", s)

    def wait_drop(self, key: Hashable):
        """The turn ended without computing (finished, torn down)."""
        self._waits.pop(key, None)

    # -- latencies between stamps --------------------------------------
    def lap(self, key: Hashable, histogram: Optional[str] = None):
        """Observe the time since ``key``'s last lap into ``histogram``
        (none on the first lap, or with no histogram), and stamp now."""
        t = self._now()
        t0 = self._laps.get(key)
        if histogram is not None and t0 is not None:
            self.registry.observe(histogram, (t - t0) * 1e-9)
        self._laps[key] = t

    def lap_drop(self, key: Hashable):
        self._laps.pop(key, None)
