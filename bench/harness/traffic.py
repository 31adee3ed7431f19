"""Session traffic from a mix file: one general generator, driven by data.

A mix file (``bench/traffic/<name>.json``) names the arrival rate, the
ramp before the measured window, the augmentation kinds sessions draw
from, and the length distributions. Per-kind tool statistics come from
``bench/data/table1.json`` (the paper's Table 1).

The schedule — each session's kind, prompt length, generated stretches,
tool durations and returned lengths, and its arrival instant — is drawn
from the mix's ``shape_seed``; a run's ``--seed`` draws every token id
(and the weights). So every seed offers the same work at the same
instants and the same seed gives the same inputs: measured on one v5e,
runs of one schedule repeat within a few percent, while schedules drawn
per seed moved tokens/s by a third (PERF.md section 6).

Arrivals are an open-loop Poisson process conditioned on its count: the
``round(rate * (ramp + seconds))`` arrival instants are uniform on
``[0, ramp + seconds)``, sorted.

Length distributions (``{"dist": ..., "lo": a, "hi": b}``):
  * ``lognormal`` — median sqrt(a*b), with [a, b] at -2 and +2 sigma,
    clipped to [a, b];
  * ``uniform`` — integers uniform on [a, b];
  * ``table1`` — a clipped normal with the kind's Table-1 (mean, std) of
    the named statistic, clipped to [a, b].
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import List, Sequence

import numpy as np

DATA = Path(__file__).resolve().parents[1] / "data"


@dataclasses.dataclass
class ToolCall:
    kind: str
    duration_s: float
    returned_ids: np.ndarray


@dataclasses.dataclass
class Session:
    sid: int
    due_s: float              # arrival, seconds after the schedule starts
    kind: str
    prompt_ids: np.ndarray
    stretches: List[int]      # tokens generated before each call, then the
                              # final answer: len(stretches) == len(tools)+1
    tools: List[ToolCall]

    @property
    def context_len(self) -> int:
        return (len(self.prompt_ids) + sum(self.stretches)
                + sum(len(t.returned_ids) for t in self.tools))

    @property
    def n_turns(self) -> int:
        return 1 + len(self.tools)


@dataclasses.dataclass
class _Shape:
    kind: str
    prompt_len: int
    stretches: List[int]
    tools: List[tuple]        # (duration_s, returned_len)


def load_table1() -> dict:
    return json.loads((DATA / "table1.json").read_text())["kinds"]


def _lognormal_s(rng, mean: float, std: float) -> float:
    """A lognormal with the given linear-space mean and std."""
    if mean <= 0:
        return 0.0
    sigma2 = math.log(1.0 + (std * std) / (mean * mean))
    return float(rng.lognormal(math.log(mean) - sigma2 / 2.0,
                               math.sqrt(sigma2)))


def _clipped_normal(rng, mean, std, lo, hi) -> int:
    return int(min(hi, max(lo, round(rng.normal(mean, std)))))


def draw_length(rng, spec: dict, t1: dict) -> int:
    lo, hi = int(spec["lo"]), int(spec["hi"])
    dist = spec["dist"]
    if dist == "lognormal":
        mu = 0.5 * (math.log(lo) + math.log(hi))
        sigma = (math.log(hi) - math.log(lo)) / 4.0
        return int(min(hi, max(lo, round(rng.lognormal(mu, sigma)))))
    if dist == "uniform":
        return int(rng.integers(lo, hi + 1))
    if dist == "table1":
        mean, std = t1[spec["stat"]]
        return _clipped_normal(rng, mean, std, lo, hi)
    raise ValueError(f"unknown length distribution {dist!r}")


def _shape(rng, kind: str, mix: dict, t1: dict) -> _Shape:
    spec = t1[kind]
    n_calls = draw_length(rng, mix["tool_calls"], spec)
    stretches = [draw_length(rng, mix["stretch"], spec)
                 for _ in range(n_calls)]
    stretches.append(draw_length(rng, mix["final"], spec))
    if "first_context" in mix and n_calls:
        # Table 1's context length at the first interception is the prompt
        # plus the first generated stretch
        ctx0 = draw_length(rng, mix["first_context"], spec)
        prompt = max(mix.get("min_prompt", 16), ctx0 - stretches[0])
    else:
        prompt = draw_length(rng, mix["prompt"], spec)
    tools = [(_lognormal_s(rng, *spec["int_time"]),
              _clipped_normal(rng, *spec["ret_tokens"], 1, 10 ** 6))
             for _ in range(n_calls)]
    # keep the whole session inside the context cap: drop trailing calls
    # (down to the mix's least), then shorten the prompt
    cap = int(mix["max_context"])

    def total():
        return prompt + sum(stretches) + sum(r for _, r in tools)
    lo_calls = int(mix["tool_calls"]["lo"])
    while total() > cap and len(tools) > lo_calls:
        tools.pop()
        stretches.pop(-2)
    if total() > cap:
        prompt = max(16, prompt - (total() - cap))
    if total() > cap:
        raise ValueError(f"session of {total()} tokens cannot fit {cap}")
    return _Shape(kind, prompt, stretches, tools)


def n_sessions(mix: dict, seconds: float) -> int:
    return max(1, int(round(float(mix["rate_per_s"])
                            * (float(mix["ramp_s"]) + float(seconds)))))


def master_shapes(mix: dict, n: int) -> List[_Shape]:
    """The session shapes: kinds in equal shares (round-robin over the
    mix's kinds), everything else drawn from the mix's ``shape_seed``."""
    t1 = load_table1()
    rng = np.random.default_rng(int(mix["shape_seed"]))
    kinds: Sequence[str] = mix["kinds"]
    return [_shape(rng, kinds[i % len(kinds)], mix, t1) for i in range(n)]


def make_sessions(mix: dict, seed: int, seconds: float,
                  vocab: int) -> List[Session]:
    """The run's sessions, sorted by due time."""
    n = n_sessions(mix, seconds)
    shapes = master_shapes(mix, n)
    sched = np.random.default_rng([int(mix["shape_seed"]), 1])
    order = sched.permutation(n)
    horizon = float(mix["ramp_s"]) + float(seconds)
    dues = np.sort(sched.uniform(0.0, horizon, n))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    out = []
    for sid, (idx, due) in enumerate(zip(order, dues)):
        s = shapes[idx]
        out.append(Session(
            sid=sid, due_s=float(due), kind=s.kind,
            prompt_ids=rng.integers(0, vocab, s.prompt_len, dtype=np.int64),
            stretches=list(s.stretches),
            tools=[ToolCall(s.kind, d, rng.integers(0, vocab, r,
                                                    dtype=np.int64))
                   for d, r in s.tools]))
    return out
