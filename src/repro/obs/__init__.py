"""Waste-attribution telemetry (DESIGN.md §13).

Five pieces, threaded through the serving stack:

  * ``MetricsRegistry`` (metrics.py) — counters / gauges / fixed-bucket
    histograms in seconds (latencies on the host clock, estimator errors
    on the virtual one). The engine's ad-hoc ``counters`` dict and the
    scheduler's ``SchedulerStats`` are thin compatibility views over one
    shared registry, so every legacy read keeps working while a single
    ``to_prometheus()`` dump exposes the whole stack.
  * ``HostClock`` (trace.py) — the host's own clock inside
    ``Engine.step``, always on: phase spans (``engine.plan`` /
    ``dispatch`` / ``swap_*`` / ``sync`` / ``commit``) that land in a
    ``jax.profiler`` trace beside the device ops and add their self time
    to ``host_``-prefixed counters, plus host-clock queue waits and
    session latencies. It is the only clock read of the serving path,
    and no decision reads it.
  * ``SpanTracer`` / ``NullTracer`` (trace.py) — per-request virtual-clock
    lifecycle spans (queued, prefill chunk, decode, swap, swapped-wait)
    plus engine-track pipeline/DMA/idle spans and tool-call async spans.
    ``NullTracer`` is the default: every emission site is guarded on
    ``tracer.enabled`` so the hot path stays allocation-free, and an
    identity test pins streams + counters bit-identical tracing on/off.
  * ``WasteLedger`` (ledger.py) — charges every wasted GPU byte-second to
    a cause (recompute / swap_stall / preserve_pinned / pipeline_bubble /
    tool_unoverlapped) and records per-intercept Eq. 5 branch waste,
    predicted vs realized (the §4.4 estimator-accuracy substrate).
    ``sim/simulator.py`` mirrors the same ledger bit-consistently.
  * exporters (export.py) — Chrome/Perfetto ``trace_event`` JSON, a
    Prometheus text dump, and the human-readable summary table; check.py
    is the CI smoke that loads a trace + breakdown back and re-asserts
    the cause-total invariant.

The package __init__ is lazy (PEP 562): ``repro.core.scheduler`` imports
``repro.obs.metrics`` while ``repro.obs.ledger`` imports
``repro.core.waste``, and deferring the submodule imports keeps either
entry order cycle-free.
"""
from __future__ import annotations

_EXPORTS = {
    "MetricsRegistry": "repro.obs.metrics",
    "CounterView": "repro.obs.metrics",
    "Histogram": "repro.obs.metrics",
    "DEFAULT_TIME_EDGES": "repro.obs.metrics",
    "ENGINE_COUNTER_SCHEMA": "repro.obs.metrics",
    "SCHED_COUNTER_SCHEMA": "repro.obs.metrics",
    "EXTRA_COUNTER_SCHEMA": "repro.obs.metrics",
    "HOST_COUNTER_SCHEMA": "repro.obs.metrics",
    "WASTE_CAUSE_SCHEMA": "repro.obs.metrics",
    "SpanTracer": "repro.obs.trace",
    "NullTracer": "repro.obs.trace",
    "HostClock": "repro.obs.trace",
    "WasteLedger": "repro.obs.ledger",
    "InterceptRecord": "repro.obs.ledger",
    "WASTE_CAUSES": "repro.obs.ledger",
    "waste_report": "repro.obs.ledger",
    "to_perfetto": "repro.obs.export",
    "write_trace": "repro.obs.export",
    "validate_trace": "repro.obs.export",
    "format_summary": "repro.obs.export",
    "format_stats_line": "repro.obs.export",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
    import importlib
    return getattr(importlib.import_module(mod), name)
