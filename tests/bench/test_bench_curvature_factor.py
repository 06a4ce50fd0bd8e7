"""The ``curvature_factor_ms`` reader: the value worked out by hand on a
synthetic trace whose factor build (scope
``curvature_product/curvature_factor``, before the solves) sits beside the
products inside the solve's loop, and nothing on a trace without the
scope."""
from bench_fixtures import REPO  # first: the checkout on sys.path

import json
import os

import pytest

from bench import trace as tr
from bench.cell import load_module

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _events(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _read(metric, events, updates=2):
    run = tr.Run(events, updates=updates, chips=1, update_flops=1.0,
                 device_kind="TPU v5 lite")
    return load_module(REPO, "metrics", metric).read(run)


def test_curvature_factor_reader_by_hand():
    # one device, two updates, window [0, 2000) ns.  curvature_factor:
    # fusion.3 40 (the forward) + fusion.4 80 + fusion.5 50 (the lattice
    # gradients) + fusion.6 20, and in the second update fusion.4 80 +
    # the 20 of fusion.3 inside the window = 290; the products in the
    # loop add fusion.7 100 + 100 and fusion.8 30 (the factor's apply).
    # Per update, in ms:
    events = _events("trace_synthetic_factor_scopes.json")
    assert _read("curvature_factor_ms", events) == pytest.approx(145e-6)
    # the nested scope counts in its stage too: 290 + 230
    assert _read("curvature_product_ms", events) == pytest.approx(260e-6)
    # the factor's lattice gradients are lattice statistics: fusion.2 60
    # (gradient stage) + 80 + 50 + 80 (factor) + fusion.10 80 (candidate)
    assert _read("lattice_stats_ms", events) == pytest.approx(175e-6)
    # and the stages still partition: none of it is CG vector work
    assert _read("cg_vector_ms", events) == pytest.approx(15e-6)
    assert _read("grad_stage_ms", events) == pytest.approx(80e-6)
    assert _read("candidate_eval_ms", events) == pytest.approx(65e-6)


def test_curvature_factor_reader_needs_its_scope():
    # the same program without the nested scope (the products' factor
    # built inside each product): the stage reads, the factor does not
    events = _events("trace_synthetic_factor_scopes.json")
    events["scopes"] = {d: {n: p.replace("curvature_factor/", "")
                            for n, p in names.items()}
                        for d, names in events["scopes"].items()}
    assert _read("curvature_factor_ms", events) is None
    assert _read("curvature_product_ms", events) == pytest.approx(260e-6)
    # the stage fixture, a program whose factor runs inside each product
    assert _read("curvature_factor_ms",
                 _events("trace_synthetic_scopes.json")) is None
    # and nothing without a device or a window
    assert _read("curvature_factor_ms", dict(events, ops={})) is None
