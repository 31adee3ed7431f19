"""Trace reduction, peaks table, FLOP/byte functions and the device check,
on the CPU."""
from __future__ import annotations

import subprocess
import sys

import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
from harness import device, spec
from harness.trace import Ev, read_xplane, reduce, union

DEV, HOST = "/device:TPU:0", "/host:CPU"


def _op(name, a, b, plane=DEV):
    return Ev(plane, "XLA Ops", name, a, b - a)


def _span(name, a, b):
    return Ev(HOST, "python3", name, a, b - a)


def test_union_merges_overlaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]


def test_reduce_busy_kernels_and_idle_gaps():
    events = [
        _span("bench.window", 0, 1000),
        _span("bench.step", 0, 600), _span("bench.wait", 600, 1000),
        _op("%ragged_paged_attention.3 = bf16[8] custom-call(%p.1)", 100,
            200),
        _op("fusion.1 = bf16[8] fusion(%ragged_paged_attention.3)", 150,
            300),                           # overlaps: union 100..300
        _op("kv_append", 400, 450),
        _op("fusion.2", 900, 1100),         # clipped to the window
        _op("fusion.9", 0, 40, plane="/device:TPU:1"),
        _op("while.2", 50, 480, plane="/device:TPU:1"),   # a container
        _op("fusion.8", 60, 70, plane="/device:TPU:1"),
        Ev(DEV, "XLA Modules", "jit_step", 0, 1000),
    ]
    s = reduce(events)
    assert s.window_s == pytest.approx(1000e-9)
    # device 0 busy 100..300, 400..450, 900..1000 = 350; device 1:
    # 0..40 and 50..480 = 470
    assert s.busy_s == pytest.approx(410e-9)
    assert s.n_devices == 2
    assert s.kernel_s["ragged_paged_attention"] == pytest.approx(100e-9)
    assert s.kernel_s["kv_append"] == pytest.approx(50e-9)
    assert s.kernel_calls["ragged_paged_attention"] == 1
    assert s.kernel_calls["kv_append"] == 1
    # idle on device 0: 0..100, 300..400 and 450..600 in the step span,
    # 600..900 in the wait span (the gap 450..900 is cut at 600)
    assert s.idle_gaps[0] == ("bench.wait", pytest.approx(300e-9))
    assert s.idle_gaps[1] == ("bench.step", pytest.approx(150e-9))
    assert s.idle_by_span["bench.step"] == pytest.approx(350e-9)
    assert s.idle_by_span["bench.wait"] == pytest.approx(300e-9)
    assert dict(s.top_ops)["fusion.9"] == pytest.approx(40e-9)
    assert dict(s.top_ops)["fusion.1"] == pytest.approx(150e-9)
    assert "while.2" not in dict(s.top_ops)


def test_reduce_without_device_ops_reads_nothing():
    assert reduce([_span("bench.window", 0, 10)]) is None


def test_recorded_cpu_trace(tmp_path):
    """A trace the JAX profiler records here: the loop's spans are found
    on the host plane; the CPU has no device plane, so the reduction
    reads nothing (a CPU run never yields a device metric)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    events = read_xplane(str(tmp_path))
    names = {e.name for e in events}
    assert {"bench.window", "bench.step"} <= names
    assert reduce(events) is None


def test_ragged_attention_flops_and_bytes():
    fn = spec.roofline("ragged_paged_attention")
    dims = {"n_heads": 32, "n_kv_heads": 8, "head_dim": 128,
            "kv_bytes": 2, "act_bytes": 2}
    # a decode at context 1000 and a 4-token chunk ending at context 10
    flops, nbytes = fn.cost([(1, 1000), (4, 10)], dims)
    visible = 1000 + (7 + 8 + 9 + 10)
    assert flops == 4 * 32 * 128 * visible
    assert nbytes == (1000 + 10) * 2 * 8 * 128 * 2 + 5 * 2 * 32 * 128 * 2


def test_step_flops():
    fn = spec.roofline("step")
    dims = {"n_layers": 2, "d_model": 8, "d_ff": 16, "vocab": 100,
            "n_heads": 2, "n_kv_heads": 1, "head_dim": 4}
    per_token = 2 * 2 * (2 * 8 * 8 + 2 * 8 * 4 + 3 * 8 * 16)
    # one decode at context 5 that delivered its token; 3 recomputed
    # tokens count nothing
    assert fn.flops([(1, 5, 1), (0, 40, 0)], dims) == \
        per_token + 4 * 2 * 2 * 4 * 5 + 2 * 8 * 100


def test_peaks_table():
    p = device.load_peaks("TPU v5 lite")
    assert (p.flops_bf16, p.hbm_bytes_per_s) == (197e12, 819e9)
    with pytest.raises(device.DeviceError):
        device.load_peaks("TPU v9 imaginary")


def test_command_refuses_the_cpu():
    """No TPU: exit code non-zero and no result line."""
    out = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload",
         spec.load_benchmark()["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not out.stdout.strip().startswith("{")
