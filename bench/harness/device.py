"""The chip a run measures: device check, peaks, compile cache, compiles.

A run needs a TPU whose ``device_kind`` is in ``bench/data/peaks.json``
and at least as many chips as its cell asks for. Anything else is an
error before any work: there is no CPU fallback.
"""
from __future__ import annotations

import dataclasses
import json
import os

from harness.spec import BENCH, ROOT

CACHE_DIR = ROOT / ".jax_cache"


class DeviceError(RuntimeError):
    pass


@dataclasses.dataclass(frozen=True)
class Peaks:
    kind: str
    flops_bf16: float           # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float


def load_peaks(kind: str) -> Peaks:
    table = json.loads((BENCH / "data" / "peaks.json").read_text())["kinds"]
    if kind not in table:
        raise DeviceError(f"device_kind {kind!r} is not in the peaks table "
                          f"(bench/data/peaks.json: {sorted(table)})")
    e = table[kind]
    return Peaks(kind, float(e["peak_flops_bf16"]),
                 float(e["hbm_bandwidth_bytes_per_s"]), float(e["hbm_bytes"]))


def check_device(chips: int):
    """(devices, Peaks) of the TPU the run measures; raises DeviceError."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise DeviceError(f"needs a TPU; JAX found {dev.platform} "
                          f"({dev.device_kind})")
    if len(devs) < chips:
        raise DeviceError(f"cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips], load_peaks(dev.device_kind)


def device_info(devs) -> dict:
    dev = devs[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    return max(peaks) if peaks else 0


class CompileCounter:
    """Counts compiles (backend compiles and persistent-cache hits, each a
    stall when it happens in the window), from JAX's monitoring events."""

    def __init__(self):
        self.backend_compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.compile_s = 0.0

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1
            self.compile_s += secs

    def snapshot(self) -> tuple:
        return (self.backend_compiles, self.cache_hits, self.compile_s)


def count_compiles() -> CompileCounter:
    import jax
    counter = CompileCounter()
    jax.monitoring.register_event_listener(counter._event)
    jax.monitoring.register_event_duration_secs_listener(counter._duration)
    return counter


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    when set, else at the checkout's fixed ``.jax_cache``; returns the
    directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
