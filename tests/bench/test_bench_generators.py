"""The traffic generators: deterministic per seed, one envelope per pool,
and padding that changes no lattice statistic."""
from bench_fixtures import cell_of_files  # first: checkout on sys.path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import lattices
from bench.reference import lattice as ref_lattice

SEED = 2**31 + 11           # above 32 signed bits


ENVELOPE = {"sausage": [48, 3, 16, 3], "dag": [128, 9, 16, 9]}


def _pool(cell, seed, **traffic):
    t = dict(cell.traffic, frames=64, grad_batch=4, cg_batch=2, pool=2,
             envelope=ENVELOPE[cell.traffic["generator"]])
    t.update(traffic)
    return lattices.make_pool(cell.generator, seed, t, cell.config)


@pytest.fixture(scope="module", params=["lstm-asr.nghf-mpe.sausage",
                                        "tdnn-asr.nghf-mpe.dag"])
def cell(request):
    return cell_of_files(request.param)


def _leaves(pool):
    return [np.asarray(x) for g, c in pool for b in (g, c)
            for x in (b["feats"], *b["lattice"].values())]


def test_same_seed_same_pool(cell):
    a, env_a = _pool(cell, SEED)
    b, env_b = _pool(cell, SEED)
    assert env_a == env_b
    for x, y in zip(_leaves(a), _leaves(b), strict=True):
        np.testing.assert_array_equal(x, y)


def test_other_seed_other_pool(cell):
    a, _ = _pool(cell, SEED)
    b, _ = _pool(cell, SEED + 1)
    assert not np.array_equal(a[0][0]["feats"], b[0][0]["feats"])
    assert not np.array_equal(a[0][0]["lattice"]["label"],
                              b[0][0]["lattice"]["label"])


def test_pool_pads_to_the_fixed_envelope(cell):
    pool, drawn = _pool(cell, SEED)
    A, P, L, W = ENVELOPE[cell.traffic["generator"]]
    assert all(d <= e for d, e in zip(drawn, (A, P, L, W)))
    for grad, cg in pool:
        for b in (grad, cg):
            lat = b["lattice"]
            assert lat["start_t"].shape[1:] == (A,)
            assert lat["preds"].shape[1:] == lat["succs"].shape[1:] == (A, P)
            assert lat["level_arcs"].shape[1:] == (L, W)
            assert b["feats"].shape[1:] == (64, cell.config["input_dim"])
        assert grad["feats"].shape[0] == 4 and cg["feats"].shape[0] == 2


def test_a_lattice_outside_the_envelope_is_drawn_again():
    cell = cell_of_files("tdnn-asr.nghf-mpe.dag")
    wide, drawn = _pool(cell, SEED)
    narrow, _ = _pool(cell, SEED, envelope=[drawn[0] - 1, 9, 16, 9])
    arcs = narrow[0][0]["lattice"]["arc_mask"].sum(axis=1)
    assert narrow[0][0]["lattice"]["start_t"].shape[1] == drawn[0] - 1
    assert arcs.max() <= drawn[0] - 1


def test_an_envelope_no_lattice_fits_is_an_error():
    cell = cell_of_files("lstm-asr.nghf-mpe.sausage")
    with pytest.raises(ValueError, match="fits the envelope"):
        _pool(cell, SEED, envelope=[47, 3, 16, 3])


def test_dag_lattices_are_ragged_before_padding():
    cell = cell_of_files("tdnn-asr.nghf-mpe.dag")
    rng = np.random.default_rng(SEED)
    arcs = {cell.generator(rng, frames=64, num_states=20,
                           **cell.traffic["lattice"])["start_t"].shape[0]
            for _ in range(8)}
    assert len(arcs) > 1


def test_padding_changes_no_statistic():
    cell = cell_of_files("tdnn-asr.nghf-mpe.dag")
    rng = np.random.default_rng(SEED)
    lat = cell.generator(rng, frames=48, num_states=20,
                         **cell.traffic["lattice"])
    lat["level_arcs"] = lattices.levelize_arcs(lat["preds"], lat["is_start"],
                                               lat["arc_mask"])
    A = lat["start_t"].shape[0]
    padded = lattices.pad_lattice(lat, (A + 7, 12, 60, 15))
    lp = jnp.asarray(np.log(rng.dirichlet(np.ones(20), size=(1, 48))),
                     jnp.float32)

    def stats(one):
        batch = {k: jnp.asarray(v)[None] for k, v in one.items()}
        return ref_lattice.stats(batch, lp, 0.5)

    for x, y in zip(stats(lat), stats(padded)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)


def test_levelize_copy_agrees_with_the_program():
    from repro.losses.lattice import levelize_arcs
    cell = cell_of_files("tdnn-asr.nghf-mpe.dag")
    rng = np.random.default_rng(SEED)
    for _ in range(4):
        lat = cell.generator(rng, frames=96, num_states=50,
                             **cell.traffic["lattice"])
        np.testing.assert_array_equal(
            lattices.levelize_arcs(lat["preds"], lat["is_start"],
                                   lat["arc_mask"]),
            levelize_arcs(lat["preds"], lat["is_start"], lat["arc_mask"]))
