"""Find a cell's files by name.

``BENCHMARK.json`` names each cell with its configuration and traffic mix.
Everything else about a cell lives in files of its own, found by name, so
that a new cell, configuration, mix or per-layer metric is a new file:

    bench/configs/<config>.json   sizes as served, source, reduced, assumed
    bench/traffic/<traffic>.json  parameters of the one traffic generator
    bench/cells/<cell>.json       the cell's slab pools and limits
    bench/reference/<name>.py     the plain reference a configuration names
    bench/metrics/<metric>.py     one reader per per-layer metric
"""
from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    cell: dict
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)

    def metrics(self, trace: bool) -> List[dict]:
        """The metrics a run of this cell reports: end-to-end ones without
        the trace, per-layer ones with it."""
        ms = self.per_layer if trace else self.end_to_end
        return [m for m in ms if self.name in m.get("workloads", [self.name])]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = confs[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"],
        config=_load(os.path.join(root, conf["file"])),
        traffic_name=w["traffic"],
        traffic=_load(os.path.join(BENCH_DIR, "traffic",
                                   w["traffic"] + ".json")),
        cell=_load(os.path.join(BENCH_DIR, "cells", name + ".json")),
        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])


def reference_module(config: Dict):
    """The plain reference the configuration names: ``leaves(conf)`` (its
    tensors in the served layout, per layer), ``layer`` (one layer's
    forward pass), ``gaps`` (``common.gaps`` over itself) and
    ``program_fields(conf)`` (the program's config fields it fixes); where
    the model has them, ``state_flops(conf)`` (a state-space layer's
    FLOPs a token beyond its weights), ``embed_scale(conf)`` and
    ``logits_divisor(conf)``."""
    return importlib.import_module("bench.reference." + config["reference"])


def metric_module(name: str):
    """The per-layer metric ``name``'s reader module: ``read(run) -> float
    | None``, and ``KERNEL`` (name substrings) for a kernel's roofline."""
    return importlib.import_module("bench.metrics." + name)


def load_peaks() -> dict:
    """Peak rates by ``device_kind``, with their source."""
    return _load(os.path.join(BENCH_DIR, "peaks.json"))
