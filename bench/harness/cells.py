"""Resolve a cell of ``BENCHMARK.json`` into the files that define it.

A cell names a configuration and a traffic mix; each is a file of its own,
found by name: ``<bench>/configs/<config>.json`` (the file that
``BENCHMARK.json``'s configuration entry lists) and
``<bench>/traffic/<mix>.json``. Each per-layer metric is read by
``<bench>/metrics/<metric>.py``; the generator, column kinds and
predicate kinds a configuration or mix names are files of their own too
(``kinds.py``), found under the same ``<bench>``. A later cell,
configuration, mix, metric, generator, column kind or predicate kind is
added as new files and entries, with no edit to these.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Callable, List

from harness.kinds import Kinds


@dataclass
class Metric:
    name: str
    unit: str
    read: Callable = None      # per-layer metrics: the reader's read()


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]
    kinds: Kinds


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reader(bench_dir: str, name: str) -> Callable:
    return Kinds(bench_dir).module("metrics", name).read


def resolve(benchmark_json: str, cell: str) -> Cell:
    """The cell ``cell`` of ``benchmark_json``, with its configuration,
    mix and metric readers loaded from the files beside it."""
    root = os.path.dirname(os.path.abspath(benchmark_json))
    spec = _load_json(benchmark_json)
    bench_dir = os.path.join(root, spec["paths"][0])
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell not in cells:
        raise KeyError(f"no workload {cell!r} in {benchmark_json}; "
                       f"have {sorted(cells)}")
    w = cells[cell]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    mix = _load_json(os.path.join(bench_dir, "traffic",
                                  f"{w['traffic']}.json"))
    kinds = Kinds(bench_dir)
    e2e = [Metric(m["name"], m["unit"]) for m in spec["end_to_end"]]
    layer = [Metric(m["name"], m["unit"],
                    kinds.module("metrics", m["name"]).read)
             for m in spec["per_layer"]]
    return Cell(name=cell, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=e2e, per_layer=layer, kinds=kinds)
