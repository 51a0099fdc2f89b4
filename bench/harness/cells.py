"""Find everything a cell needs by name, from data.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
configuration's file is the one it lists, the mix is
``bench/traffic/<traffic>.json`` and each per-layer metric is read by
``bench/metrics/<metric name>.py``.  A new cell, mix or metric is new
files plus new entries: nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]      # the metrics this cell reports untraced
    per_layer: List[Dict]       # ... and traced, each with its "read"


def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _covers(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``read(trace, run)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no reader for per-layer metric {name!r} "
                                f"at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _covers(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = []
    for m in bench["per_layer"]:
        if _covers(m, name) and m["moves"] in reported:
            per_layer.append(dict(m, read=load_reader(
                m["name"], os.path.join(root, "bench"))))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)
