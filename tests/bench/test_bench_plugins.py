"""A cell made only of new files — a configuration, a traffic mix, a
generator, a per-layer metric and limits, plus BENCHMARK.json entries —
is found by name and runs, with no edit to a file the harness has."""
from bench_fixtures import make_root  # first: it puts the checkout on sys.path

import json
import os
import shutil

import jax
import pytest

from bench import run
from bench.cell import load_cell

GENERATOR = '''"""Sausages whose every segment has one more alternative."""
from bench.generators.sausage import generate as _sausage


def generate(rng, *, frames, num_states, n_alt=3, **kw):
    return _sausage(rng, frames=frames, num_states=num_states,
                    n_alt=n_alt + 1, **kw)
'''

METRIC = '''"""updates_traced (count): the updates of the traced window."""


def read(run):
    return float(run.updates)
'''


def test_a_cell_added_as_files_is_found_and_runs(tmp_path):
    root = make_root(str(tmp_path))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "generators", "wider.py"), "w") as f:
        f.write(GENERATOR)
    with open(os.path.join(b, "metrics", "updates_traced.py"), "w") as f:
        f.write(METRIC)
    with open(os.path.join(b, "traffic", "tiny-lstm.json")) as f:
        traffic = json.load(f)
    traffic.update(generator="wider", trace_updates=3,
                   envelope=[32, 4, 8, 4])
    with open(os.path.join(b, "traffic", "wider.json"), "w") as f:
        json.dump(traffic, f)
    shutil.copy(os.path.join(b, "limits", "tiny-lstm.json"),
                os.path.join(b, "limits", "tiny-lstm.wider.json"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny-lstm.wider",
                               "config": "tiny-lstm", "traffic": "wider",
                               "chips": 1, "why": "a fixture cell"})
    bench["per_layer"].append({"name": "updates_traced", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "trainer loop (host)",
                               "moves": "update_ms",
                               "workloads": ["tiny-lstm.wider"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = load_cell("tiny-lstm.wider", root=root)
    assert cell.traffic["generator"] == "wider"
    assert [m["name"] for m in cell.per_layer] == [
        "step_mfu", "device_idle_share", "host_gap_ms", "updates_traced"]
    out = run.execute(cell, 2**31 + 3, 0.2, 1, devices=jax.devices()[:1])
    assert out["metrics"] == {"updates_traced": {"value": 3.0,
                                                 "unit": "count"}}
    assert out["correct"] is True
    # the other cells are untouched by the new one
    assert load_cell("tiny-lstm", root=root).traffic["generator"] == \
        "sausage"


def test_a_cell_without_limits_of_its_own_is_refused(tmp_path):
    # the limits come from the cell's own readings; a new entry that has
    # none must not run under another cell's
    root = make_root(str(tmp_path))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny-lstm.dp4", "config": "tiny-lstm",
                               "traffic": "tiny-lstm-dp", "chips": 4,
                               "why": "a cell with no readings yet"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    with pytest.raises(FileNotFoundError, match="no limits"):
        load_cell("tiny-lstm.dp4", root=root)
    # its calibration, which sets them, loads it without
    assert load_cell("tiny-lstm.dp4", root=root, limits=False).limits == {}
