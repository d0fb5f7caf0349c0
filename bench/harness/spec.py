"""Finds what a cell is made of, by name, from ``BENCHMARK.json``.

A cell names a configuration (``configs`` entry, whose ``file`` is the
configuration as run) and a traffic mix (``bench/traffic/<traffic>.json``).
Each metric is read by ``bench/metrics/<name>.py`` (or, for
``<base>.<part>``, by its base's reader).  The configuration's
``reference`` names its plain reference, ``bench/reference/<reference>.py``.
Adding a cell, a configuration, a mix or a metric therefore adds files and
entries and edits none.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
from dataclasses import dataclass
from typing import List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    kind: str                       # "end_to_end" | "per_layer"
    workloads: Optional[List[str]]
    moves: Optional[str] = None
    layer: Optional[str] = None
    bound: Optional[float] = None


@dataclass
class Cell:
    name: str
    chips: int
    config: dict                    # the configuration file's contents
    config_name: str
    traffic: dict
    traffic_name: str
    metrics: List[Metric]           # every metric this cell reports


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _metrics(bench: dict, cell: str) -> List[Metric]:
    out = []
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            wl = m.get("workloads")
            if wl is None or cell in wl:
                out.append(Metric(name=m["name"], unit=m["unit"],
                                  better=m["better"], source=m["source"],
                                  kind=kind, workloads=wl,
                                  moves=m.get("moves"), layer=m.get("layer"),
                                  bound=m.get("bound")))
    return out


def resolve(workload: str, bench: Optional[dict] = None,
            root: str = ROOT, bench_dir: str = BENCH_DIR) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(traffic_path(w["traffic"], bench_dir)) as f:
        traffic = json.load(f)
    return Cell(name=workload, chips=w["chips"], config=config,
                config_name=w["config"], traffic=traffic,
                traffic_name=w["traffic"], metrics=_metrics(bench, workload))


def traffic_path(name: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "traffic", f"{name}.json")


def metric_path(name: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "metrics", f"{name}.py")


def reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``.  A
    quantity split by cell, ``<base>.<part>``, is read by its base's
    reader unless it has a file of its own."""
    path = metric_path(name, bench_dir)
    if not os.path.exists(path) and "." in name:
        path = metric_path(name.split(".")[0], bench_dir)
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference(config: dict):
    """The plain reference module the configuration names."""
    return importlib.import_module(f"reference.{config['reference']}")


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; have {sorted(table['devices'])}")
    return table["devices"][device_kind]
