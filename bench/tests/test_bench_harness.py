"""The harness on the CPU: every name in BENCHMARK.json resolves to its
files, the contract's shape holds, and the traffic generator is
deterministic per seed and reproduces Table 1."""
from __future__ import annotations

import json
import math
import re

import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
from harness import spec, traffic

BM = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("w", BM["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(w):
    cell = spec.resolve(w["name"])
    assert cell.chips in (1, 4)
    assert cell.config["serving"]["n_pages"] > 0
    assert set(cell.limits) == {"max_gap"}
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]).read)
    for m in cell.end_to_end:
        assert m["name"] in ("setup_s", "tokens_per_s", "turn_p90_s",
                             "itl_p99_s")
    sessions = traffic.make_sessions(cell.traffic, 5, 10.0,
                                     int(cell.config["vocab_size"]))
    assert all(s.context_len <= int(cell.config["serving"]["max_model_len"])
               - 16 for s in sessions)


@pytest.mark.parametrize("kernel", ["ragged_paged_attention", "step"])
def test_roofline_functions_resolve(kernel):
    mod = spec.roofline(kernel)
    assert callable(getattr(mod, "cost", None) or getattr(mod, "flops"))


def test_contract_shape():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= BM["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            moved = e2e[m["moves"]]
            assert w in moved.get("workloads", [w])
    for w in BM["workloads"]:
        assert len(w["why"]) <= 200
    for c in BM["configs"]:
        conf = json.loads((spec.ROOT / c["file"]).read_text())
        assert sorted(c["reduced"]) == sorted(conf["reduced"])
        for key in c["reduced"]:
            assert conf["published"][key] != conf[key]
        assert conf["source"] == c["source"]


def test_weights_match_stated_sizes():
    import jax
    from harness import setup
    for c in BM["configs"]:
        conf = json.loads((spec.ROOT / c["file"]).read_text())
        shapes = jax.eval_shape(lambda conf=conf: setup.make_weights(conf, 0))
        leaves = jax.tree.leaves(shapes)
        assert sum(math.prod(x.shape) for x in leaves) \
            == conf["weights"]["params"]
        assert sum(math.prod(x.shape) * x.dtype.itemsize for x in leaves) \
            == conf["weights"]["bytes_bf16"]
        L = conf["num_hidden_layers"]
        kv = L * 2 * conf["num_key_value_heads"] * conf["head_dim"] * 2
        assert kv == conf["kv_bytes_per_token"]
        serving = conf["serving"]
        assert serving["pool_bytes"] == serving["n_pages"] \
            * serving["page_size"] * kv


def _shape_key(sessions):
    return sorted((s.kind, len(s.prompt_ids), tuple(s.stretches),
                   tuple((round(t.duration_s, 9), len(t.returned_ids))
                         for t in s.tools)) for s in sessions)


def test_one_seed_gives_the_same_sessions():
    mix = spec.resolve(BM["workloads"][0]["name"]).traffic
    big = 2 ** 33 + 17
    a = traffic.make_sessions(mix, big, 20.0, 1000)
    b = traffic.make_sessions(mix, big, 20.0, 1000)
    c = traffic.make_sessions(mix, big + 1, 20.0, 1000)
    assert [s.due_s for s in a] == [s.due_s for s in b]
    assert all(np.array_equal(x.prompt_ids, y.prompt_ids)
               for x, y in zip(a, b))
    assert not all(np.array_equal(x.prompt_ids, y.prompt_ids)
                   for x, y in zip(a, c))
    # another seed offers the same work at the same instants
    assert _shape_key(a) == _shape_key(c)
    assert [(s.due_s, s.kind, len(s.prompt_ids)) for s in a] == \
        [(s.due_s, s.kind, len(s.prompt_ids)) for s in c]


def test_traffic_reproduces_table1():
    """Tool durations and returned lengths per kind match Table 1's means
    within sampling error (4 standard errors)."""
    t1 = traffic.load_table1()
    mix = dict(spec.resolve(BM["workloads"][0]["name"]).traffic,
               tool_calls={"dist": "uniform", "lo": 1, "hi": 1},
               max_context=100000)
    shapes = traffic.master_shapes(mix, 6 * 400)
    for kind in mix["kinds"]:
        durs = [d for s in shapes if s.kind == kind for d, _ in s.tools]
        rets = [r for s in shapes if s.kind == kind for _, r in s.tools]
        mean, std = t1[kind]["int_time"]
        assert abs(np.mean(durs) - mean) <= 4 * std / math.sqrt(len(durs))
        rmean, rstd = t1[kind]["ret_tokens"]
        assert abs(np.mean(rets) - rmean) <= 4 * rstd / math.sqrt(len(rets))
    assert len({s.kind for s in shapes}) == len(mix["kinds"])


def test_arrivals_are_poisson_conditioned_on_count():
    mix = spec.resolve(BM["workloads"][0]["name"]).traffic
    s = traffic.make_sessions(mix, 3, 40.0, 1000)
    horizon = mix["ramp_s"] + 40.0
    assert len(s) == round(mix["rate_per_s"] * horizon)
    dues = [x.due_s for x in s]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < horizon
