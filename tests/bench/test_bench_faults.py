"""The comparison catches a broken timed path, and the lower-precision
control (the reference computed in bfloat16 in the program's place): each
must come out not correct under the real cells' limits."""
from bench_fixtures import tiny_root  # noqa: F401 (a fixture; first import)

import jax
import jax.numpy as jnp
import pytest

from bench import compare, run
from bench.cell import load_cell
from bench.reference import model

SEED = 2**31 + 47


@pytest.mark.parametrize("name,fault", [
    ("tiny-lstm", "unchanged"),
    ("tiny-tdnn", "half_batch"),
    ("tiny-lstm-dp", "no_exchange"),
])
def test_a_planted_fault_reads_not_correct(tiny_root, name, fault):
    cell = load_cell(name, root=tiny_root)
    out = run.execute(cell, SEED, 0.2, 0, devices=jax.devices()[:1],
                      fault=fault)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("name", ["tiny-lstm", "tiny-tdnn"])
def test_the_bfloat16_control_reads_not_correct(tiny_root, name):
    cell = load_cell(name, root=tiny_root)
    dev = jax.devices()[:1]
    ref = run.reference_updates(cell, SEED, dev)
    ctl = run.reference_updates(cell, SEED, dev, dtype=jnp.bfloat16)
    initial = jax.device_get(model.make_weights(cell.config, SEED))
    correct, checks = compare.verdict(compare.readings(ctl, ref, initial),
                                      cell.limits)
    assert correct is False, checks
