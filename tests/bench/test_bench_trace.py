"""The reduction from device events to the per-layer numbers, on a
hand-made trace whose values are worked out by hand, and on events
recorded from a chip trace."""
from bench_fixtures import REPO  # first: it puts the checkout on sys.path

import gzip
import json
import os

import pytest

from bench import trace as tr
from bench.cell import load_module

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SPANS = ("bench.fetch_batch", "bench.step_call", "bench.read_metrics")


def _events(name):
    path = os.path.join(DATA, name)
    opener = gzip.open if name.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def _read(metric, run):
    return load_module(REPO, "metrics", metric).read(run)


@pytest.fixture
def synthetic():
    # F such that two updates on two chips in 1 us are half the peak
    return tr.Run(_events("trace_synthetic.json"), updates=2, chips=2,
                  update_flops=98.5e6, device_kind="TPU v5 lite")


def test_interval_arithmetic():
    assert tr.union([[5, 7, "a"], [0, 2, "b"], [1, 3, "c"]]) == [[0, 3], [5, 7]]
    assert tr.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert tr.clip([[0, 5], [8, 12]], 2, 10) == [[2, 5], [8, 10]]


def test_busy_and_idle_by_hand(synthetic):
    # device 0: [100,250] [280,400] [600,700] [900,1000] = 470 ns, while.1
    # [595,720] holding fusion.4 adds nothing: its own 25 ns are idle;
    # device 1: [0,600] = 600 ns; window 1000 ns
    assert [o[2] for o in tr.leaves(synthetic.events["ops"]["0"])] == [
        "fusion.1", "fusion.2", "fusion.3", "all-reduce.1", "fusion.4",
        "fusion.5"]
    assert synthetic.window_s() == pytest.approx(1e-6)
    assert synthetic.busy_s() == pytest.approx(535e-9)
    assert _read("device_idle_share", synthetic) == pytest.approx(46.5)


def test_host_gap_by_hand(synthetic):
    # device 0's programs end at 410 and start again at 590
    assert synthetic.host_gaps_s() == pytest.approx([180e-9])
    assert _read("host_gap_ms", synthetic) == pytest.approx(180e-6)


def test_exposed_collectives_by_hand(synthetic):
    # device 0: all-reduce [300,400] less fusion.3 [280,330] = 70 ns;
    # device 1: all-gather [500,600], nothing else then = 100 ns;
    # mean 85 ns over 2 updates
    assert synthetic.collective_exposed_s() == pytest.approx(85e-9)
    assert _read("collective_exposed_ms", synthetic) == pytest.approx(
        42.5e-6)


def test_step_mfu_by_hand(synthetic):
    assert _read("step_mfu", synthetic) == pytest.approx(50.0)


def test_breakdown_by_hand(synthetic):
    b = synthetic.breakdown(SPANS)
    assert b["device_ops"][0] == ["fusion.9", pytest.approx(250e-9)]
    assert dict(b["device_ops"])["fusion.5"] == pytest.approx(50e-9)
    assert b["idle_gaps"] == [
        ["bench.read_metrics", pytest.approx(200e-9)],
        ["bench.fetch_batch", pytest.approx(200e-9)],
        ["bench.step_call", pytest.approx(100e-9)],
        ["outside the bench's spans", pytest.approx(30e-9)]]


def test_readers_find_nothing_without_device_events():
    events = {"ops": {}, "modules": {},
              "spans": [[0, 1000, "bench.window"]]}
    run = tr.Run(events, updates=2, chips=1, update_flops=1e9,
                 device_kind="cpu")
    for metric in ("step_mfu", "device_idle_share", "host_gap_ms",
                   "collective_exposed_ms"):
        assert _read(metric, run) is None


@pytest.fixture
def recorded():
    # 7.3 ms of a trace of the lstm-asr cell around the boundary between two
    # updates, measured on one TPU v5e chip: 3,181 device events, two
    # program runs, the bench's host spans
    return tr.Run(_events("trace_lstm_v5e.json.gz"), updates=1, chips=1,
                  update_flops=1.0, device_kind="TPU v5 lite")


def test_recorded_trace_busy_idle_and_gap(recorded):
    assert recorded.window_s() == pytest.approx(7.260991e-3)
    # a loop's own time is idle: while.1305, whole in the slice, spends
    # 8 us outside its body's operations; while.1306 runs past the end of
    # the slice, which holds none of its later operations, so 1.23 ms of
    # it read idle here (in a whole trace the loops' own time is ~0.5 %)
    assert not any(o[2].startswith("while")
                   for o in tr.leaves(recorded.events["ops"]["0"]))
    assert recorded.busy_s() == pytest.approx(3.033581e-3)
    assert _read("device_idle_share", recorded) == pytest.approx(
        58.22084, rel=1e-6)
    # the first program ends 2.98 ms before the next starts
    assert recorded.host_gaps_s() == pytest.approx([2.984528e-3])
    assert recorded.collective_exposed_s() is None


def test_recorded_trace_breakdown(recorded):
    b = recorded.breakdown(SPANS)
    # the LSTM step's matmul fusions lead; a loop's self time is what its
    # body's operations leave (while.1306's is large only because the
    # slice cuts its body off)
    assert b["device_ops"][0] == ["convolution_add_fusion.60",
                                  pytest.approx(1.326028e-3)]
    assert [n for n, _ in b["device_ops"][:3]] == [
        "convolution_add_fusion.60", "convolution_add_fusion.61",
        "while.1306"]
    # the device waits while the host reads the update's metrics back
    assert b["idle_gaps"][0] == ["bench.read_metrics",
                                 pytest.approx(2.991151e-3)]
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == 10
