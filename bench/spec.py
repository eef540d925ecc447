"""What one cell is, read from ``BENCHMARK.json`` and the files it names.

A cell names a configuration (``configs`` entry -> its ``file``) and a
traffic mix (``bench/traffic/<traffic>.json``). Its end-to-end metrics
are the ``end_to_end`` entries that list it (or list no cells); its
per-layer metrics are the ``per_layer`` entries that list it (or list no
cells), each read by ``bench/metrics/<name>.py``. Nothing here knows a
cell, configuration, traffic mix or metric by name.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List, NamedTuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class Metric(NamedTuple):
    spec: Dict                  # the BENCHMARK.json entry
    read: object                # per-layer reader's ``read(run)``; or None


class Cell(NamedTuple):
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Metric]


def _applies(entry: Dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_reader(spec: Dict):
    """Import ``bench/metrics/<name>.py`` and check that what it declares
    (source, layer, the metric it moves) is what BENCHMARK.json says."""
    path = os.path.join(BENCH_DIR, "metrics", spec["name"] + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + spec["name"].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    for key in ("source", "layer", "moves", "unit", "better"):
        if getattr(mod, key.upper()) != spec[key]:
            raise ValueError(f"{path}: {key} {getattr(mod, key.upper())!r} "
                             f"but BENCHMARK.json says {spec[key]!r}")
    return mod.read


def load_cell(name: str, benchmark_file: str = None) -> Cell:
    with open(benchmark_file or os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(
        name, int(cell["chips"]), config, traffic,
        [m for m in bench["end_to_end"] if _applies(m, name)],
        [Metric(m, load_reader(m)) for m in bench["per_layer"]
         if _applies(m, name)])
