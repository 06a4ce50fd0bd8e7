"""Per-stage device time (``bench/stages.py``): the reduction from device
events and the program's named scopes to the five stage readers, on a
hand-made trace worked out by hand, on events recorded from a chip trace,
on the old fixtures (which carry no scopes), and on a live CPU program."""
from bench_fixtures import REPO  # first: it puts the checkout on sys.path

import gzip
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench import stages
from bench import trace as tr
from bench.cell import load_module

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# the per-stage readers; the first four and the unscoped remainder
# partition the busy time, lattice_stats_ms cuts across the first three
STAGES = ("grad_stage_ms", "curvature_product_ms", "candidate_eval_ms",
          "cg_vector_ms", "lattice_stats_ms")
PARTITION = ("grad_stage", "curvature_product", "candidate_eval",
             "cg_solve")


def _events(name):
    path = os.path.join(DATA, name)
    opener = gzip.open if name.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def _read(metric, run):
    return load_module(REPO, "metrics", metric).read(run)


def _run(events, updates, chips):
    return tr.Run(events, updates=updates, chips=chips, update_flops=1.0,
                  device_kind="TPU v5 lite")


@pytest.fixture
def scoped():
    # two updates on two chips; the scope paths are the programs' op_names
    return _run(_events("trace_synthetic_scopes.json"), 2, 2)


def test_scope_components_unwrap_transforms():
    assert stages.scope_components(
        "jit(sequence_step)/cg_solve/while/body/"
        "transpose(jvp(curvature_product))/vmap(lattice_stats)/mul") >= {
        "cg_solve", "curvature_product", "lattice_stats", "while"}
    assert "jvp" not in stages.scope_components("a/jvp(b)")


def test_stage_times_by_hand(scoped):
    # device 0's leaves (while.1 holds fusion.3-6 and is not one):
    # grad_stage fusion.1 100 + fusion.2 50 (transposed lattice
    # statistics) + fusion.1 again, 10 inside the window = 160;
    # curvature_product fusion.3 100 + fusion.4 40 = 140; candidate_eval
    # fusion.6 60 (in the solve) + fusion.7 50 (the zero update) = 110;
    # the solve's vector work fusion.5 20; lattice_stats fusion.2 50 +
    # fusion.4 40 + fusion.6 60 = 150; unscoped fusion.8 20 and copy.1 20.
    # Device 1: grad_stage 200, curvature_product 100.  Per update (2)
    # and device (2), in ms:
    assert _read("grad_stage_ms", scoped) == pytest.approx(90e-6)
    assert _read("curvature_product_ms", scoped) == pytest.approx(60e-6)
    assert _read("candidate_eval_ms", scoped) == pytest.approx(27.5e-6)
    assert _read("cg_vector_ms", scoped) == pytest.approx(5e-6)
    assert _read("lattice_stats_ms", scoped) == pytest.approx(37.5e-6)
    # the four stages and device 0's unscoped 40 ns are the busy time:
    # (470 + 300) / 2 devices
    stages_s = sum(_read(m, scoped) for m in STAGES[:4]) * 1e-3 * 2
    assert scoped.busy_s() == pytest.approx(385e-9)
    assert stages_s + 40e-9 / 2 == pytest.approx(385e-9)


def test_stage_readers_need_the_scope_in_the_program(scoped):
    # a program without the scopes (an earlier commit's) has op names but
    # none of the stages: its readers find nothing
    events = _events("trace_synthetic_scopes.json")
    events["scopes"] = {d: {n: "jit(sequence_step)/dot_general"
                            for n in names}
                        for d, names in events["scopes"].items()}
    for metric in STAGES:
        assert _read(metric, _run(events, 2, 2)) is None
    # the other readers read what they read without scopes
    del events["scopes"]
    assert _read("device_idle_share", _run(events, 2, 2)) == \
        _read("device_idle_share", scoped)


@pytest.mark.parametrize("fixture,updates,chips", [
    ("trace_synthetic.json", 2, 2), ("trace_lstm_v5e.json.gz", 1, 1)])
def test_old_fixtures_have_no_stage_readings(fixture, updates, chips):
    # their programs are not held by this process and they carry no
    # scopes: every stage reader finds nothing, the old ones are unchanged
    events = _events(fixture)
    run = _run(events, updates, chips)
    assert "scopes" not in events
    for metric in STAGES:
        assert _read(metric, run) is None
    assert stages.scopes(run, {"0": tr.leaves(events["ops"]["0"])}) == {}


def _pb(*fields):
    """A protobuf message from (number, int | bytes) fields."""
    def varint(v):
        out = b""
        while True:
            out += bytes([v & 0x7F | (0x80 if v > 0x7F else 0)])
            v >>= 7
            if not v:
                return out
    out = b""
    for number, v in fields:
        if isinstance(v, int):
            out += varint(number << 3) + varint(v)
        else:
            out += varint(number << 3 | 2) + varint(len(v)) + v
    return out


def test_instructions_without_a_name_take_a_named_neighbour():
    # entry: a scan loop (op_name .../while) calling computation 2; a loop
    # XLA made for a relayout, without metadata, calling computation 3,
    # whose result reaches the named reshape it implements; an unnamed
    # copy that nothing reads; a cumulative sum named from a fresh name
    # stack.  Computation 2: a named dot, an unnamed dynamic-update-slice
    # feeding the loop's unnamed tuple.  Computation 3: an unnamed
    # dynamic-update-slice.
    def inst(iid, name, op=None, operands=(), called=()):
        f = [(1, name.encode()), (35, iid)]
        if op:
            f.append((7, _pb((2, op.encode()))))
        if operands:
            f.append((36, b"".join(bytes([o]) for o in operands)))
        if called:
            f.append((38, b"".join(bytes([c]) for c in called)))
        return _pb(*f)

    def comp(cid, *insts):
        return _pb((1, b"c"), (5, cid), *((2, i) for i in insts))

    loop = "jit(f)/grad_stage/while"
    reshape = "jit(f)/grad_stage/jvp(lattice_stats)/reshape"
    module = _pb(
        (3, comp(2, inst(20, "fusion.3", loop + "/body/dot"),
                 inst(21, "dynamic-update-slice.9", operands=(20,)),
                 inst(22, "tuple.22", operands=(20, 21)))),
        (3, comp(3, inst(30, "dynamic-update-slice.7"))),
        (3, comp(1, inst(10, "while.1", loop, called=(2,)),
                 inst(11, "while.4", called=(3,)),
                 inst(12, "get-tuple-element.5", operands=(11,)),
                 inst(13, "bitcast.6", reshape, operands=(12,)),
                 inst(14, "copy.2"),
                 inst(15, "fusion.8", "reduce_window_sum",
                      operands=(13,)))))
    assert stages.op_names(module) == {
        "while.1": loop, "fusion.3": loop + "/body/dot",
        "dynamic-update-slice.9": loop, "tuple.22": loop,
        "while.4": reshape, "get-tuple-element.5": reshape,
        "bitcast.6": reshape, "dynamic-update-slice.7": reshape,
        "copy.2": "", "fusion.8": ""}


def test_scopes_read_from_the_live_program():
    """Where the events carry no scopes, the op names come from the HLO of
    the program this process holds, the one whose module ran them."""
    @jax.jit
    def stage_probe(x):
        with jax.named_scope("grad_stage"):
            y = jnp.sin(x) @ x
        with jax.named_scope("cg_solve"):
            return jnp.cos(y) @ y

    x = jnp.ones((8, 8))
    stage_probe(x).block_until_ready()
    (module,) = [m for e in jax.devices()[0].client.live_executables()
                 for m in e.hlo_modules() if m.name == "jit_stage_probe"]
    names = stages.op_names(module.as_serialized_hlo_module_proto())
    grad = [n for n, op in names.items()
            if "grad_stage" in stages.scope_components(op)]
    solve = [n for n, op in names.items()
             if "cg_solve" in stages.scope_components(op)]
    events = {"ops": {"0": [[0, 100, grad[0]], [100, 250, solve[0]]]},
              "modules": {"0": [[0, 250, "jit_stage_probe(3)"]]},
              "spans": [[0, 1000, "bench.window"]]}
    run = _run(events, 1, 1)
    assert _read("grad_stage_ms", run) == pytest.approx(100e-6)
    assert _read("cg_vector_ms", run) == pytest.approx(150e-6)
    # another program's name finds nothing
    events["modules"]["0"][0][2] = "jit_other(3)"
    assert _read("grad_stage_ms", _run(events, 1, 1)) is None


@pytest.fixture
def recorded_scopes():
    # 10.8 ms of a trace of the lstm-asr cell around the boundary between
    # two updates, on one TPU v5e chip, with the scope paths of its 302
    # distinct operations: 4,491 device events
    return _run(_events("trace_lstm_v5e_scopes.json.gz"), 1, 1)


def test_recorded_trace_stage_times(recorded_scopes):
    run = recorded_scopes
    # the end of one update (the last product, the evaluations, the
    # solve's vector work) and the start of the next (its gradient stage)
    assert _read("grad_stage_ms", run) == pytest.approx(0.013785)
    assert _read("curvature_product_ms", run) == pytest.approx(2.76185)
    assert _read("candidate_eval_ms", run) == pytest.approx(3.235961)
    assert _read("cg_vector_ms", run) == pytest.approx(0.759725)
    assert _read("lattice_stats_ms", run) == pytest.approx(1.8e-5)
    # the four stages and the unscoped rest (the update's final apply and
    # norms) partition the busy time
    lo, hi = run.window
    names = run.events["scopes"]["0"]
    rest = sum(min(e, hi) - max(s, lo)
               for s, e, n in tr.leaves(run.events["ops"]["0"])
               if e > lo and s < hi and not stages.scope_components(
                   names.get(n, "")).intersection(PARTITION))
    assert rest == pytest.approx(477932)
    staged = sum(_read(m, run) for m in STAGES[:4]) * 1e6
    assert staged + rest == pytest.approx(run.busy_s() * 1e9, rel=1e-9)
    # ... and the readings the trace gave without scopes stand
    assert run.busy_s() == pytest.approx(7.249253e-3)
    assert _read("host_gap_ms", run) == pytest.approx(3.492359)
