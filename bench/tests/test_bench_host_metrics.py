"""The per-layer metrics read from the engine's host-clock counters
(``host_*``, ``engine_cost_step_s``): each reader on a synthetic window,
and all of them from a traced toy run of the harness on the CPU."""
from __future__ import annotations

import pytest

import tiny
import run as bench_run
from harness import spec

PHASES = ("plan_ms", "dispatch_ms", "swap_ms", "sync_ms", "commit_ms")
NAMES = [f"{p}.steady" for p in PHASES] + ["queue_wait_ms.steady",
                                           "cost_model_error.steady"]


def _window(delta):
    return bench_run.Window(seconds=1.0, steps=[], step_work=[], delta=delta,
                            trace=None, peaks=None, dims={}, roofline=None)


# (metric, a window's counter deltas, the value, deltas whose denominator
# is 0)
CASES = [
    ("plan_ms.steady", {"host_plan_s": 0.5, "host_steps": 10}, 50.0,
     {"host_plan_s": 0.0, "host_steps": 0}),
    ("dispatch_ms.steady", {"host_dispatch_s": 0.25, "host_steps": 5}, 50.0,
     {"host_dispatch_s": 0.0, "host_steps": 0}),
    ("swap_ms.steady", {"host_swap_s": 0.01, "host_steps": 4}, 2.5,
     {"host_swap_s": 0.0, "host_steps": 0}),
    ("sync_ms.steady", {"host_sync_s": 1.0, "host_steps": 8}, 125.0,
     {"host_sync_s": 0.0, "host_steps": 0}),
    ("commit_ms.steady", {"host_commit_s": 0.03, "host_steps": 3}, 10.0,
     {"host_commit_s": 0.0, "host_steps": 0}),
    ("queue_wait_ms.steady",
     {"host_queue_wait_s": 3.0, "host_queue_waits": 12}, 250.0,
     {"host_queue_wait_s": 0.0, "host_queue_waits": 0}),
    ("cost_model_error.steady",
     {"host_step_s": 2.0, "engine_cost_step_s": 0.5}, 75.0,
     {"host_step_s": 0.0, "engine_cost_step_s": 0.5}),
]


@pytest.mark.parametrize("name,delta,value,empty", CASES,
                         ids=[c[0] for c in CASES])
def test_reader_on_a_synthetic_window(name, delta, value, empty):
    read = spec.metric_reader(name).read
    assert read(_window(delta)) == pytest.approx(value)
    # a program that keeps none of these counters reads
    # nothing, and does not raise
    assert read(_window({})) is None
    assert read(_window({"engine_swap_bytes": 1})) is None
    for key in delta:
        assert read(_window({k: v for k, v in delta.items()
                             if k != key})) is None
    assert read(_window(empty)) is None


def test_entries_name_their_readers():
    entries = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    for name in NAMES:
        m = entries[name]
        assert m["source"] == "program_counter"
        assert m["workloads"] == ["deepseek-7b-pp2.tools-pinched"]
        assert callable(spec.metric_reader(name).read)


def test_traced_toy_run_reads_every_host_metric(capsys):
    """A traced run at toy size: the seven metrics come out of the window's
    counters, and the step's phases add up to the loop's step time less
    the poll's own overhead."""
    cell = tiny.cell()
    cell.per_layer = [{"name": n, "unit": "ms"} for n in NAMES] \
        + [{"name": "step_ms.steady", "unit": "ms"}]
    args = bench_run.parse(["--workload", "tiny.tools", "--seed",
                            str(2 ** 31 + 5), "--seconds", "4",
                            "--trace", "1"])
    res = bench_run.run(args, cell, require_tpu=False, use_cache=False)
    assert res["correct"], res["checks"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) == set(NAMES) | {"step_ms.steady"}, got
    assert all(v >= 0 for v in got.values()), got
    assert got["sync_ms.steady"] > 0 and got["dispatch_ms.steady"] > 0
    phases = sum(got[f"{p}.steady"] for p in PHASES)
    assert 0.5 * got["step_ms.steady"] < phases \
        <= 1.05 * got["step_ms.steady"], got
