"""The reader the saturated cell adds, ``pad_share`` (the engine's bucket
padding): on a synthetic window, and from the window counters a traced
toy run records, on a pool with no host KV space tight enough to
discard."""
from __future__ import annotations

import json

import pytest

import tiny
import run as bench_run
from harness import spec

NAME = "pad_share.saturated"


def _window(delta):
    return bench_run.Window(seconds=1.0, steps=[], step_work=[], delta=delta,
                            trace=None, peaks=None, dims={}, roofline=None)


def test_reader_on_a_synthetic_window():
    read = spec.metric_reader(NAME).read
    delta = {"engine_pad_tokens": 30, "engine_step_tokens": 120}
    assert read(_window(delta)) == pytest.approx(25.0)
    # a program without the padding counters (the parent of this reader)
    # reads nothing, and does not raise
    assert read(_window({})) is None
    assert read(_window({"engine_step_tokens": 120})) is None
    assert read(_window({"engine_pad_tokens": 30})) is None
    assert read(_window({"engine_pad_tokens": 0,
                         "engine_step_tokens": 0})) is None


def test_entry_names_its_reader():
    m = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}[NAME]
    assert m["source"] == "program_counter"
    assert m["moves"] == "tokens_per_s"
    assert m["workloads"] == ["qwen2-72b-pp20.agent-saturated"]


def test_traced_toy_run_reads_the_recorded_window(capsys):
    """A traced toy run with no host KV space on a 24-page pool (the size
    at which the waiting queue once stalled): the run serves on, its
    evicted contexts are recomputed, and the reader's value is the share
    of the window's recorded counter deltas."""
    cell = tiny.cell(n_pages=24, host_kv_tokens=0)
    cell.per_layer = [{"name": NAME, "unit": "%"}]
    args = bench_run.parse(["--workload", "tiny.tools", "--seed",
                            str(2 ** 31 + 9), "--seconds", "4",
                            "--trace", "1"])
    res = bench_run.run(args, cell, require_tpu=False, use_cache=False)
    assert res["correct"], res["checks"]
    line = next(x for x in capsys.readouterr().out.splitlines()
                if x.startswith("window counters: "))
    delta = json.loads(line[len("window counters: "):])
    assert delta.get("sched_recompute_tokens", 0) > 0, delta
    got = res["metrics"][NAME]["value"]
    assert got == pytest.approx(100.0 * delta.get("engine_pad_tokens", 0)
                                / delta["engine_step_tokens"])
    assert 0 < got < 100
