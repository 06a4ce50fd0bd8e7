"""The TDNN cell's per-layer readers: its own three (``output_layer_ms``,
``tdnn_splice_ms``, ``tdnn_hidden_ms``) and the shared ones it reports
(``step_mfu`` on its FLOP rule, ``lattice_stats_ms`` on DAG lattices):
the value worked out by hand on a synthetic trace that has their scopes,
and nothing on a trace without them."""
from bench_fixtures import REPO, cell_of_files  # first: the checkout on sys.path

import json
import os

import pytest

from bench import trace as tr
from bench.cell import load_module

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = ("step_mfu", "output_layer_ms", "tdnn_splice_ms",
           "tdnn_hidden_ms", "lattice_stats_ms")
SCOPED = READERS[1:4]


def _events(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _read(metric, events, updates=2, update_flops=1.97e5):
    run = tr.Run(events, updates=updates, chips=1,
                 update_flops=update_flops, device_kind="TPU v5 lite")
    return load_module(REPO, "metrics", metric).read(run)


def test_tdnn_cell_flop_rule_by_hand():
    # F = 2 x (80*5*1000 + 3 x 1000*2*1000 + 1000*1000 + 1000*6000)
    #   = 26.8 M per frame; F x (3 x 128*512 + 32*512 x (3 x 12 + 9 + 1))
    cell = cell_of_files("tdnn-asr.nghf-mpe.dag")
    assert cell.forward_flops_per_frame() == 26_800_000
    assert cell.update_flops() == 26_800_000 * (3 * 65_536 + 16_384 * 46)
    assert cell.update_flops() == pytest.approx(2.547e13, rel=1e-3)


def test_tdnn_readers_by_hand():
    # one device, two updates, window [0, 2000) ns.  tdnn_splice: fusion.1
    # 100 (forward gather) + fusion.6 50 (its transpose) + fusion.8 30 (in
    # a product) = 180; output_layer: fusion.2 200 + 50 of its second run
    # inside the window, fusion.5 150 (transpose), fusion.7 90 (JVP),
    # fusion.10 100 (a candidate's forward) = 590; tdnn_hidden: fusion.13
    # 60 (JVP) + fusion.14 40 (transpose) = 100; lattice_stats: fusion.3
    # 40 + .4 60 + .9 70 + .11 50 = 220; while.1 holds fusion.7-11 and is
    # not a leaf.  Per update, in ms:
    events = _events("trace_synthetic_tdnn_scopes.json")
    assert _read("tdnn_splice_ms", events) == pytest.approx(90e-6)
    assert _read("output_layer_ms", events) == pytest.approx(295e-6)
    assert _read("tdnn_hidden_ms", events) == pytest.approx(50e-6)
    assert _read("lattice_stats_ms", events) == pytest.approx(110e-6)
    # 2 updates x 1.97e5 FLOPs over 2 us x 197 TFLOP/s: 0.1 %
    assert _read("step_mfu", events) == pytest.approx(0.1)


def test_tdnn_scope_readers_need_their_scopes():
    # the LSTM cell's scoped trace has lattice_stats but no TDNN scope
    lstm = _events("trace_synthetic_scopes.json")
    for metric in SCOPED:
        assert _read(metric, lstm) is None, metric
    # a program without the scopes (the parent's): op names, no scopes
    events = _events("trace_synthetic_tdnn_scopes.json")
    events["scopes"] = {d: {n: "jit(sequence_step)/dot_general"
                            for n in names}
                        for d, names in events["scopes"].items()}
    for metric in SCOPED:
        assert _read(metric, events) is None, metric
    # the whole step's share needs no scope
    assert _read("step_mfu", events) == pytest.approx(0.1)


def test_tdnn_readers_find_nothing_without_a_device_or_a_window():
    events = _events("trace_synthetic_tdnn_scopes.json")
    no_ops = dict(events, ops={})
    for metric in READERS:
        assert _read(metric, no_ops) is None, metric
    no_window = dict(events, spans=[s for s in events["spans"]
                                    if s[2] != "bench.window"])
    for metric in READERS:
        assert _read(metric, no_window) is None, metric
