"""Finds a cell's parts by the names ``BENCHMARK.json`` gives them.

* ``bench/configs/<config>.json``  — the deployment: graph, index, server;
* ``bench/traffic/<traffic>.json`` — the query mix, read by
  ``bench.traffic_gen``;
* ``bench/cells/<cell>.json``      — what belongs to one pairing: its
  offered rate, found by a sweep on the chip;
* ``bench/metrics/<metric>.py``    — one reader per per-layer metric.

A later cell, mix, deployment or metric is new files and new manifest
entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    rate: dict
    end_to_end: list     # manifest entries this cell reports
    per_layer: list


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(MANIFEST)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, man: dict | None = None) -> Cell:
    """Resolve one workload of the manifest into its files."""
    man = man or manifest()
    by_name = {w["name"]: w for w in man["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in {MANIFEST.name}; "
                       f"have {sorted(by_name)}")
    w = by_name[name]
    e2e = [m for m in man["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in man["per_layer"]
                 if m["moves"] in reported and _reports(m, name)]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(BENCH / "configs" / f"{w['config']}.json"),
                traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                rate=load_json(BENCH / "cells" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
