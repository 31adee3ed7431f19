"""Resolve ``BENCHMARK.json`` entries to their files, by name.

Every configuration, traffic mix, per-layer metric reader, kernel roofline
function and correctness limit sits in a file of its own, so a later cell
or metric is added as files and entries, without editing any file here:

  configuration      bench/configs/<config>.json
  traffic mix        bench/traffic/<traffic>.json
  correctness limits bench/checks/<workload>.json
  per-layer metric   bench/metrics/<name>.py, else bench/metrics/<stem>.py
                     where <stem> is the name up to its first "." (the
                     split names ``step_ms.steady`` / ``step_ms.saturated``
                     share one reader)
  kernel roofline    bench/roofline/<kernel>.py
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class SpecError(RuntimeError):
    """A name in BENCHMARK.json that resolves to no file, or a bad file."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no {path}")
    return json.loads(path.read_text())


def _json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(path.parents[2])}")
    return json.loads(path.read_text())


def _load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.parent.name + "_" + path.stem.replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench: Path = BENCH) -> ModuleType:
    for stem in (name, name.split(".")[0]):
        path = bench / "metrics" / f"{stem}.py"
        if path.is_file():
            return _load_module(path)
    raise SpecError(f"no reader bench/metrics/{name}.py for metric {name}")


def roofline(kernel: str, bench: Path = BENCH) -> ModuleType:
    path = bench / "roofline" / f"{kernel}.py"
    if not path.is_file():
        raise SpecError(f"no roofline function bench/roofline/{kernel}.py")
    return _load_module(path)


def metrics_for(entries: List[dict], workload: str) -> List[dict]:
    return [m for m in entries if workload in m.get("workloads", [workload])]


def resolve(workload: str, root: Path = ROOT) -> Cell:
    bm = load_benchmark(root)
    cells: Dict[str, dict] = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; "
                        f"known: {sorted(cells)}")
    w = cells[workload]
    bench = root / "bench"
    configs = {c["name"]: c for c in bm["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload} names unknown config "
                        f"{w['config']!r}")
    cfg_path = root / configs[w["config"]]["file"]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config_name=w["config"], config=_json(cfg_path),
        traffic_name=w["traffic"],
        traffic=_json(bench / "traffic" / f"{w['traffic']}.json"),
        limits=_json(bench / "checks" / f"{workload}.json"),
        end_to_end=metrics_for(bm["end_to_end"], workload),
        per_layer=metrics_for(bm["per_layer"], workload))
