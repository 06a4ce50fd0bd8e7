"""A whole run on the CPU at a tiny size, past the harness's look for a
chip: the result line's keys, and the program agreeing with the plain
reference."""
from bench_fixtures import tiny_root  # noqa: F401 (a fixture; first import)

import json

import jax
import pytest

from bench import run
from bench.cell import load_cell

SEED = 2**31 + 29
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _execute(root, name, trace, **kw):
    cell = load_cell(name, root=root)
    out = run.execute(cell, SEED, 0.3, trace, devices=jax.devices()[:1],
                      **kw)
    json.dumps(out)                      # one JSON object
    return out


@pytest.mark.parametrize("name", ["tiny-lstm", "tiny-tdnn"])
def test_program_agrees_with_the_reference(tiny_root, name):
    out = _execute(tiny_root, name, 0)
    assert list(out) == KEYS + ["checks"]
    assert set(out["metrics"]) == {"update_ms", "setup_s"}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["device"]["count"] == 1
    # f32 on both sides at this size: the gaps are rounding (the two sum
    # in different orders; 1.1e-5 seen on the gradient norm)
    checks = out["checks"]
    assert set(checks) == set(load_cell(name, root=tiny_root).limits)
    for name, c in checks.items():
        assert c["value"] < (1e-3 if name == "change_gap" else 1e-4), name
    assert out["correct"] is True


def test_traced_run_adds_the_breakdown_and_device_times(tiny_root):
    out = _execute(tiny_root, "tiny-lstm-dp", 1)
    assert list(out) == KEYS + ["breakdown", "checks"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["device"]["window_s"] > 0
    assert "busy_s" in out["device"]
    # the CPU has no device trace: the per-layer readers find nothing and
    # the harness leaves their metrics out
    assert out["metrics"] == {}
    assert out["correct"] is True
