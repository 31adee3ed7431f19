"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed, a sample drawn from the seed of the sessions
that served tokens by then (always with the longest finished one, and
finished sessions before open ones) is replayed
through the float32 reference (``bench/reference``): one forward pass over
each session's whole stream (prompt, generated and returned tokens) gives
the reference's logits before every served token. All traffic is greedy,
so the program served its own argmax; the gap of a served token is how far
its reference logit lies below the reference's best. The number compared
is the widest gap over the sample.

The control (``control=True``, never in the benchmark's own runs) puts the
reference computed in float8_e4m3 (``bench/reference``) in the program's
place: at each of the same positions it reads the gap of the token the
float8 reference puts first, and those gaps are the numbers compared, so
a control run reads ``correct`` false. The program's own widest gap is
kept beside them as ``program_max_gap``. A comparison that cannot tell
that control from the program could not catch a change to a lower
precision.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

# the sample: sessions are added until this many served tokens are in it
SAMPLE_TOKENS = 600
SAMPLE_SESSIONS = 8


@dataclasses.dataclass
class Sample:
    sid: int
    tokens: List[int]          # the whole stream the engine holds
    served: List[tuple]        # (index, token id, turn kind)


def pick(sessions: Dict[int, object], seed: int) -> List[object]:
    """The sessions to compare. Call it once the window has closed: the
    loop stops there, so every served token counted is one the window's
    timed path produced."""
    served = sorted((lv for lv in sessions.values()
                     if not lv.failed and lv.served),
                    key=lambda lv: lv.spec.sid)
    if not served:
        return []
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 7])
    done = [lv for lv in served if lv.finished is not None]
    first = max(done or served, key=lambda lv: (len(lv.served),
                                                lv.spec.sid))
    order = [served[i] for i in rng.permutation(len(served))]
    order.sort(key=lambda lv: lv.finished is None)   # finished ones first
    out, n = [first], len(first.served)
    for lv in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_SESSIONS:
            break
        if lv is not first:
            out.append(lv)
            n += len(lv.served)
    return out


def compare(params, conf: dict, samples: List[Sample],
            control: bool = False) -> dict:
    from reference.model import logits_at
    gaps: List[float] = []
    control_gaps: List[float] = []
    by_kind: Dict[str, List[float]] = {}
    mismatched = 0
    stream_errors = 0
    for s in samples:
        idx = np.array([i for i, _, _ in s.served], np.int64)
        tok = np.array([t for _, t, _ in s.served], np.int64)
        stream = np.asarray(s.tokens, np.int64)
        stream_errors += int(np.sum(stream[idx] != tok))
        logits = logits_at(params, conf, stream[:int(idx.max()) + 1],
                           idx - 1)
        best = logits.max(axis=-1)
        g = best - logits[np.arange(len(tok)), tok]
        gaps.extend(g.tolist())
        if control:
            low = logits_at(params, conf, stream[:int(idx.max()) + 1],
                            idx - 1, fp8=True).argmax(axis=-1)
            g = best - logits[np.arange(len(tok)), low]
            control_gaps.extend(g.tolist())
        mismatched += int(np.sum(g > 0))
        for (_, _, kind), v in zip(s.served, g.tolist()):
            by_kind.setdefault(kind, []).append(v)
    compared = control_gaps if control else gaps
    n = len(compared)
    return {
        "sessions": len(samples), "tokens": n,
        "stream_errors": stream_errors,
        "max_gap": max(compared) if n else float("nan"),
        "mean_gap": float(np.mean(compared)) if n else float("nan"),
        "mismatch_share": mismatched / n if n else float("nan"),
        "program_max_gap": max(gaps) if gaps else float("nan"),
        "by_kind": {k: {"tokens": len(v), "max_gap": max(v)}
                    for k, v in sorted(by_kind.items())},
    }


def verdict(result: dict, limits: dict) -> tuple:
    """(correct, checks): every limited number at or under its limit, at
    least one token compared, and every served token in the stream."""
    checks = {name: {"value": result[name], "limit": float(lim)}
              for name, lim in limits.items() if not name.startswith("_")}
    ok = result["tokens"] > 0 and result["stream_errors"] == 0 and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    return bool(ok), checks
