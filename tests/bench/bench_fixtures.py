"""Helpers and fixtures of the benchmark's CPU tests: a checkout-like root
holding a copy of ``bench/`` and tiny cells (small widths, short
utterances) made from the benchmark's configuration and traffic files,
with the real cell's limits.

A plain module, imported by name, and not a ``conftest.py``: the suite's
other test files import ``tests/conftest.py`` as ``conftest``, and a
second module of that name would shadow it."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"input_dim": 8, "hidden_dim": 16, "num_outputs": 20}
TINY_TRAFFIC = {"frames": 32, "grad_batch": 8, "cg_batch": 4, "pool": 2}
# the envelope (arcs, fan, levels, width) of each tiny cell's lattices
TINY_ENVELOPE = {"sausage": [24, 3, 8, 3], "dag": [64, 9, 8, 9]}
# tiny cell -> (configuration, traffic mix); every tiny cell runs under
# the limits of the one chip cell, lstm-asr.nghf-mpe.sausage.  The TDNN's
# DAG mix and the four-chip mix are not cells of BENCHMARK.json and have
# no limits of their own, but their paths run here: the four-chip one on a
# 1x1 mesh
LIMITS = "lstm-asr.nghf-mpe.sausage"
TINY_CELLS = {
    "tiny-lstm": ("lstm-asr", "nghf-mpe.sausage"),
    "tiny-tdnn": ("tdnn-asr", "nghf-mpe.dag"),
    "tiny-lstm-dp": ("lstm-asr", "nghf-mpe.sausage.dp4")}


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f)


def cell_of_files(name):
    """The cell ``<configuration>.<traffic mix>`` made of the benchmark's
    files alone, whether or not BENCHMARK.json has it (no limits)."""
    from bench.cell import Cell
    config, mix = name.split(".", 1)
    return Cell(name=name, chips=1,
                config=_load(os.path.join(REPO, "bench", "configs",
                                          f"{config}.json")),
                traffic=_load(os.path.join(REPO, "bench", "traffic",
                                           f"{mix}.json")),
                limits={}, end_to_end=[], per_layer=[], root=REPO)


def make_root(path):
    """A root with ``bench/`` and a BENCHMARK.json of tiny cells."""
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    real = _load(os.path.join(REPO, "BENCHMARK.json"))
    bench = dict(real, configs=[], workloads=[])
    for tiny, (config, mix) in TINY_CELLS.items():
        cfg = _load(os.path.join(REPO, "bench", "configs", f"{config}.json"))
        cfg.update(TINY, unfold=5)
        cfg_file = f"bench/configs/{tiny}.json"
        _dump(cfg, os.path.join(path, cfg_file))
        traffic = _load(os.path.join(REPO, "bench", "traffic", f"{mix}.json"))
        traffic.update(TINY_TRAFFIC,
                       envelope=TINY_ENVELOPE[traffic["generator"]])
        if traffic.get("mesh"):
            traffic["mesh"] = [1, 1]
        _dump(traffic, os.path.join(path, "bench", "traffic",
                                    f"{tiny}.json"))
        shutil.copy(os.path.join(REPO, "bench", "limits", f"{LIMITS}.json"),
                    os.path.join(path, "bench", "limits", f"{tiny}.json"))
        bench["configs"].append({"name": tiny,
                                 "source": "https://arxiv.org/abs/2103.07554",
                                 "file": cfg_file, "reduced": [],
                                 "why": f"{config} at a tiny size"})
        bench["workloads"].append({"name": tiny, "config": tiny,
                                   "traffic": tiny, "chips": 1,
                                   "why": f"{mix} at a tiny size"})
    _dump(bench, os.path.join(path, "BENCHMARK.json"))
    return path


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("bench_root")))
