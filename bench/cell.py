"""A benchmark cell, found by name: its entry in ``BENCHMARK.json`` and
the files that entry names.

Nothing here knows a particular cell.  A cell names a configuration
(``configs/<name>.json``) and a traffic mix (``traffic/<name>.json``);
the traffic names its lattice generator (``generators/<name>.py``) and
its optimizer's FLOP rule (``flops/<optimizer>.py``); the configuration
names its model kind's FLOP rule (``flops/<kind>.py``); each per-layer
metric is ``metrics/<name>.py``; the comparison's limits are
``limits/<cell>.json``.  Adding a cell, a mix, a model or a metric is
adding files and a ``BENCHMARK.json`` entry.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(root, kind, name):
    """``<root>/bench/<kind>/<name>.py`` as a module."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # BENCHMARK.json metric entries it reports
    per_layer: list
    root: str = ROOT
    _modules: dict = field(default_factory=dict)

    def module(self, kind, name):
        key = (kind, name)
        if key not in self._modules:
            self._modules[key] = load_module(self.root, kind, name)
        return self._modules[key]

    @property
    def generator(self):
        return self.module("generators", self.traffic["generator"]).generate

    def metric_reader(self, name):
        return self.module("metrics", name).read

    def forward_flops_per_frame(self):
        return self.module("flops", self.config["kind"]) \
            .forward_flops_per_frame(self.config)

    def update_flops(self):
        rule = self.module("flops", self.traffic["optimizer"]["name"])
        return rule.update_flops(self.forward_flops_per_frame(),
                                 self.traffic)


def _reported_in(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name, root=ROOT, *, limits=True):
    """The cell ``name`` of ``<root>/BENCHMARK.json``; ``limits=False``
    loads one whose limits are not set yet, for its calibration."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     f"{w['traffic']}.json"))
    path = os.path.join(root, "bench", "limits", f"{name}.json")
    if limits and not os.path.exists(path):
        raise FileNotFoundError(
            f"cell {name!r} has no limits ({path}): they are set from its "
            f"own readings on the chip (bench/calibrate.py)")
    limits = load_json(path) if limits else {}
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reported_in(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reported_in(m, name)],
                root=root)
