"""Reduction of a profiler trace to device busy time, kernel time and the
host activity in the device's idle gaps.

Input is a flat list of ``Ev`` (plane, line, name, start_ns, dur_ns,
text), read from the ``.xplane.pb`` the JAX profiler writes; ``text`` is
the event name with its string-valued stats, so a kernel is found by its
name whether the trace puts it in the event name or in a stat.

  * window: the host span ``bench.window`` (the measured window);
  * device ops: events on lines named ``XLA Ops`` of planes whose name
    starts with ``/device:`` — busy time is the union of their intervals
    inside the window, per device, averaged over devices;
  * an op's name is its HLO instruction's name (the text before " = ");
    ops that enclose other ops of their line (a ``while`` around a layer
    scan) count for busy time only;
  * kernel time: summed durations of device ops by the name's stem (the
    instruction name less its ``.<n>`` suffix: a Pallas kernel's stem is
    the name it was given, ``ragged_paged_attention``);
  * idle gaps: the complement of the busy union inside the window on the
    first device, cut at the edges of the loop's host spans
    (``bench.step``, ``bench.submit``, ``bench.resume``, ``bench.wait``)
    and labelled by the span each piece lies in.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
HOST_SPANS = ("bench.step", "bench.submit", "bench.resume", "bench.wait")
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass(frozen=True)
class Ev:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over devices
    n_devices: int
    kernel_s: Dict[str, float]
    kernel_calls: Dict[str, int]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    idle_by_span: Dict[str, float]


def read_xplane(trace_dir: str) -> List[Ev]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Ev(plane.name, line.name, e.name,
                              float(e.start_ns), float(e.duration_ns)))
    return out


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                 float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def op_name(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def stem(name: str) -> str:
    """``ragged_paged_attention.7`` -> ``ragged_paged_attention``."""
    base, _, tail = name.rpartition(".")
    return base if base and tail.isdigit() else name


def _containers(ops: List[Ev]) -> set:
    """Indices of ops that enclose a later op of the same device line."""
    out = set()
    order = sorted(range(len(ops)), key=lambda i: (ops[i].plane,
                                                    ops[i].start_ns,
                                                    -ops[i].dur_ns))
    stack: List[int] = []
    for i in order:
        e = ops[i]
        while stack and (ops[stack[-1]].plane != e.plane
                         or ops[stack[-1]].end_ns <= e.start_ns):
            stack.pop()
        if stack and e.end_ns <= ops[stack[-1]].end_ns:
            out.add(stack[-1])
        stack.append(i)
    return out


def reduce(events: List[Ev],
           window: Optional[Tuple[float, float]] = None) -> Optional[Summary]:
    """The trace's summary over the window; None when the trace holds no
    device op."""
    dev_ops = [e for e in events
               if e.plane.startswith(DEVICE_PREFIX) and e.line == OPS_LINE]
    if not dev_ops:
        return None
    if window is None:
        spans = [e for e in events if e.name == WINDOW_SPAN]
        if spans:
            window = (spans[0].start_ns, spans[0].end_ns)
        else:
            window = (min(e.start_ns for e in dev_ops),
                      max(e.end_ns for e in dev_ops))
    lo, hi = window
    per_dev: Dict[str, list] = defaultdict(list)
    kernel_ns: Dict[str, float] = defaultdict(float)
    kernel_n: Dict[str, int] = defaultdict(int)
    op_ns: Dict[str, float] = defaultdict(float)
    outer = _containers(dev_ops)
    for i, e in enumerate(dev_ops):
        a, b = _clip(e.start_ns, e.end_ns, lo, hi)
        if b <= a:
            continue
        per_dev[e.plane].append((a, b))
        if i in outer:
            continue
        name = op_name(e.name)
        op_ns[name] += b - a
        kernel_ns[stem(name)] += b - a
        kernel_n[stem(name)] += 1
    busy = {p: sum(b - a for a, b in union(iv)) for p, iv in per_dev.items()}
    first = sorted(per_dev)[0] if per_dev else None
    gaps: List[Tuple[float, float]] = []
    cur = lo
    for a, b in union(per_dev.get(first, [])):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    # each gap is cut at the host spans' edges: every piece is labelled by
    # the span it lies in ("untraced" where none does); the loop's spans
    # are sequential, so one forward sweep pairs them with the gaps
    host = sorted((e.start_ns, e.end_ns, e.name) for e in events
                  if e.name in HOST_SPANS
                  and not e.plane.startswith(DEVICE_PREFIX))
    labelled = []
    by_span: Dict[str, float] = defaultdict(float)
    j = 0
    for a, b in gaps:
        while j < len(host) and host[j][1] <= a:
            j += 1
        cur, k = a, j
        while cur < b:
            if k < len(host) and host[k][0] < b:
                s, e, name = host[k]
                if s > cur:
                    labelled.append(("untraced", (s - cur) * 1e-9))
                    cur = s
                end = min(b, e)
                if end > cur:
                    labelled.append((name, (end - cur) * 1e-9))
                    cur = end
                k += 1
            else:
                labelled.append(("untraced", (b - cur) * 1e-9))
                cur = b
    for name, secs in labelled:
        by_span[name] += secs
    labelled.sort(key=lambda x: -x[1])
    n = max(1, len(busy))
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy.values()) / n * 1e-9,
        n_devices=len(busy),
        kernel_s={k: v * 1e-9 for k, v in kernel_ns.items()},
        kernel_calls=dict(kernel_n),
        top_ops=sorted(((k, v * 1e-9) for k, v in op_ns.items()),
                       key=lambda x: -x[1])[:10],
        idle_gaps=labelled[:10],
        idle_by_span=dict(by_span))


def layout(events: List[Ev]) -> Dict[str, int]:
    """Event counts per (plane, line), to see what a trace holds."""
    out: Dict[str, int] = defaultdict(int)
    for e in events:
        out[f"{e.plane} | {e.line}"] += 1
    return dict(out)
