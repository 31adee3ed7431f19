"""A run of the harness at a toy size on the CPU, past its look for a
chip: sound, ``correct`` comes out true; with the timed path broken
underneath, false — once for each fault a served cell can have. (The
exchange between chips is a fault no one-chip cell can have.) A fault's
widest gap is also held against each benchmark cell's own limit: the
toy's sound runs read ~0.01-0.06, its faults 4.5 and more."""
from __future__ import annotations

import contextlib
from unittest import mock

import jax.numpy as jnp
import pytest

import tiny
import run as bench_run
from harness import spec

CELL_LIMITS = sorted({spec.resolve(w["name"]).limits["max_gap"]
                      for w in spec.load_benchmark()["workloads"]})


def _state_unchanged():
    """The step leaves the KV pools as they were: no token's K/V lands."""
    from repro.kernels import ops
    return mock.patch.object(ops, "kv_append_op",
                             lambda k, v, *a, **kw: (k, v))


def _wrap_step(change):
    from repro.models import LM
    orig = LM.forward_mixed_paged

    def step(self, params, tokens, tok_seq, tok_pos, q_last, *a, **kw):
        tok_pos, after = change(tok_pos)
        sampled, logits, pools = orig(self, params, tokens, tok_seq,
                                      tok_pos, q_last, *a, **kw)
        return after(sampled), logits, pools
    return mock.patch.object(LM, "forward_mixed_paged", step)


def _half_batch():
    """The latter half of each step's real tokens is left out."""
    def change(tok_pos):
        real = tok_pos >= 0
        rank = jnp.cumsum(real)
        drop = real & (rank > jnp.sum(real) // 2)
        return jnp.where(drop, -1, tok_pos), lambda s: s
    return _wrap_step(change)


def _token_altered():
    """The first sequence's token of every step is altered where it is
    sampled."""
    V = tiny.CONFIG["vocab_size"]
    return _wrap_step(lambda tp: (tp, lambda s: s.at[0].set((s[0] + 1) % V)))


FAULTS = {"sound": contextlib.nullcontext, "state_unchanged": _state_unchanged,
          "half_batch": _half_batch, "token_altered": _token_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_correct_catches_faults(fault):
    args = bench_run.parse(["--workload", "tiny.tools", "--seed",
                            str(2 ** 33 + 11), "--seconds", "4",
                            "--trace", "0"])
    with FAULTS[fault]():
        res = bench_run.run(args, tiny.cell(), require_tpu=False,
                            use_cache=False)
    assert res["checks"]["max_gap"]["limit"] == 0.1
    assert res["correct"] is (fault == "sound"), res["checks"]
    if fault != "sound":
        assert res["checks"]["max_gap"]["value"] > max(CELL_LIMITS)


def test_no_host_kv_space_never_swaps(capsys):
    """A deployment that gives swapped KV no host memory
    (``host_kv_tokens`` 0) warms no swap program and, under traffic that
    pauses, moves no byte to the host; its run is still correct."""
    from harness import setup
    cell = tiny.cell(host_kv_tokens=0, n_pages=64,
                     warm={"max_batch": 8, "max_tokens": 128})
    conf = cell.config
    eng = setup.build_engine(setup.model_config(conf, "tiny"),
                             setup.make_weights(conf, 1), conf["serving"])
    assert eng.sched.cpu_capacity == 0
    assert setup.warm_programs(eng, conf["serving"]["warm"]) \
        == {"fused_buckets": 26, "swap_sizes": 0}
    del eng
    args = bench_run.parse(["--workload", "tiny.tools", "--seed", "7",
                            "--seconds", "4", "--trace", "0"])
    res = bench_run.run(args, cell, require_tpu=False, use_cache=False)
    assert res["correct"], res["checks"]
    out = capsys.readouterr().out
    counters = [ln for ln in out.splitlines()
                if ln.startswith("window counters:")]
    assert counters and "swap_bytes" not in counters[0], counters
