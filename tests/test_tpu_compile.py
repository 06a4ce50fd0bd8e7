"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

Every main-path Pallas kernel at full-width shapes, and the full-width
jitted NGHF sequence step of ``lstm-asr`` on the Pallas lattice backend
with fused CG vector work, are lowered and compiled by the TPU compiler
for one chip of a ``v5e:2x2`` topology.  Interpret mode — what every
other kernel test runs on CPU — cannot see what Mosaic refuses
(unaligned blocks, unsupported primitives, VMEM overflow); these tests
can, at no chip time.

The topology is described inside a module-scoped fixture (never at
import time): only one process at a time may load the TPU library, and
a test file that touched it while being collected would make the test
workers collect different tests.  The persistent compilation cache is
off while these tests run — an executable compiled for a described chip
can be written to it but not read back without one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import cg_fused, dispatch, lattice_fb

# full-width shapes: the paper's 6000-output acoustic models on 128- and
# 256-frame utterances; sausage lattices as the trainer builds them
# (``asr_batch``: 4-frame segments x 3 alternatives) and general DAGs as
# ``make_random_dag_lattice`` builds them at T=256
K = 6000
SAUSAGE = dict(B=32, T=128, A=96, L=32, W=3, P=3)
DAG = dict(B=8, T=256, A=199, L=55, W=9, P=9)
V5E_HBM_BYTES = 16 * 2**30           # device memory of one v5e chip


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e:2x2 topology, with the persistent
    compilation cache off for the duration of the module."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return compiled


def _sausage_args(chip):
    s = SAUSAGE
    tile = _spec((s["B"], s["L"], s["W"]), jnp.float32, chip)
    return (tile, tile, tile)


def _dag_args(chip, with_final):
    d = DAG
    tile = _spec((d["B"], d["L"], d["W"]), jnp.float32, chip)
    frontier = _spec((d["B"], d["L"], d["W"], d["P"]), jnp.int32, chip)
    return (tile,) * (5 if with_final else 4) + (frontier,)


def _loss_only_args(chip, dims, dag):
    B, T, A = dims["B"], dims["T"], dims["A"]
    i32 = lambda *shp: _spec(shp, jnp.int32, chip)        # noqa: E731
    f32 = lambda *shp: _spec(shp, jnp.float32, chip)      # noqa: E731
    flag = _spec((B, A), jnp.bool_, chip)
    args = [f32(B, T, K), i32(B, A), i32(B, A), i32(B, A), f32(B, A),
            f32(B, A), flag]
    if dag:
        args += [flag, flag]
    args.append(i32(B, dims["L"], dims["W"]))
    if dag:
        args.append(i32(B, dims["L"], dims["W"], dims["P"]))
    return tuple(args)


def _lstm_asr_param_count():
    from repro.configs.acoustic import get_acoustic_config
    from repro.models import acoustic
    acfg = get_acoustic_config("lstm-asr")
    shapes = jax.eval_shape(
        lambda: acoustic.init_params(acfg, jax.random.PRNGKey(0)))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


KERNELS = {
    "sausage_forward": lambda chip: (
        lambda s, c, m: lattice_fb.sausage_forward(s, c, m, interpret=False),
        _sausage_args(chip)),
    "sausage_backward": lambda chip: (
        lambda s, c, m: lattice_fb.sausage_backward(s, c, m,
                                                    interpret=False),
        _sausage_args(chip)),
    "sausage_loss_only": lambda chip: (
        lambda *a: lattice_fb.sausage_loss_only(*a, kappa=0.5,
                                                interpret=False),
        _loss_only_args(chip, SAUSAGE, dag=False)),
    "dag_forward": lambda chip: (
        lambda *a: lattice_fb.dag_forward(*a, interpret=False),
        _dag_args(chip, with_final=True)),
    "dag_backward": lambda chip: (
        lambda *a: lattice_fb.dag_backward(*a, interpret=False),
        _dag_args(chip, with_final=False)),
    "dag_loss_only": lambda chip: (
        lambda *a: lattice_fb.dag_loss_only(*a, kappa=0.5, interpret=False),
        _loss_only_args(chip, DAG, dag=True)),
    "cg_fused_update": lambda chip: (
        lambda a, x, v, r, bv: cg_fused.cg_fused_update(a, x, v, r, bv,
                                                        interpret=False),
        (_spec((), jnp.float32, chip),)
        + (_spec((_lstm_asr_param_count(),), jnp.float32, chip),) * 4),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, chip):
    fn, args = KERNELS[name](chip)
    mem = _compile(fn, args).memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < V5E_HBM_BYTES


def test_nghf_sequence_step_compiles_for_v5e(chip, monkeypatch):
    """The full-width ``lstm-asr`` MPE update (80 -> 2x1000 LSTM -> 1000
    FF -> 6000 outputs) with ``backend="pallas"`` and ``cg_fused=True``,
    as ``train.py --lattice-backend pallas --cg-fused`` jits it."""
    from repro.configs.acoustic import get_acoustic_config
    from repro.core.optim import config_for
    from repro.data.synthetic import asr_batch
    from repro.launch import steps
    from repro.models import acoustic

    # the program asks the default backend (CPU here) whether kernels
    # compile; the described chip is a TPU, so answer for it
    monkeypatch.setattr(dispatch, "compiled_backend", lambda: True)
    acfg = get_acoustic_config("lstm-asr")
    ocfg = config_for("nghf", cg_iters=8, ng_iters=4, lam=1.0,
                      cg_fused=True)
    fn, opt = steps.build_sequence_step(
        acfg, ocfg, loss="mpe", kappa=0.5, backend="pallas",
        share_counts=None)
    place = lambda t: jax.tree.map(                         # noqa: E731
        lambda x: _spec(x.shape, x.dtype, chip), t)
    params = place(jax.eval_shape(
        lambda: acoustic.init_params(acfg, jax.random.PRNGKey(0))))
    state = place(jax.eval_shape(opt.init, params))
    kw = dict(num_frames=SAUSAGE["T"], num_states=acfg.num_outputs,
              input_dim=acfg.input_dim)
    grad_batch = place(asr_batch(0, batch=SAUSAGE["B"], **kw))
    cg_batch = place(asr_batch(1, batch=8, **kw))
    compiled = steps.jit_train_step(fn).lower(
        params, state, grad_batch, cg_batch).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3   # DAG pair + CG update
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < V5E_HBM_BYTES


# the benchmark cell tdnn-asr.nghf-mpe.dag: gradient batch 128 x 512
# frames, CG batch 32 x 512, general-DAG lattices padded to the envelope
# (arcs, fan, levels, width) = (528, 9, 128, 9)
TDNN_CELL = dict(grad=128, cg=32, T=512, A=528, P=9, L=128, W=9)


def _dag_batch(chip, B, acfg):
    c = TDNN_CELL
    A, T = c["A"], c["T"]
    i32 = lambda *shp: _spec(shp, jnp.int32, chip)        # noqa: E731
    f32 = lambda *shp: _spec(shp, jnp.float32, chip)      # noqa: E731
    flag = _spec((B, A), jnp.bool_, chip)
    from repro.losses.lattice import Lattice
    lattice = Lattice(
        start_t=i32(B, A), end_t=i32(B, A), label=i32(B, A), lm=f32(B, A),
        corr=f32(B, A), preds=i32(B, A, c["P"]), succs=i32(B, A, c["P"]),
        is_start=flag, is_final=flag, arc_mask=flag, ref_states=i32(B, T),
        num_ref_units=f32(B), level_arcs=i32(B, c["L"], c["W"]))
    return {"feats": f32(B, T, acfg.input_dim), "labels": i32(B, T),
            "lattice": lattice}


def test_tdnn_dag_sequence_step_compiles_for_v5e(chip, monkeypatch,
                                                 record_property):
    """The full-width ``tdnn-asr`` MPE update (80 -> five spliced
    1000-wide layers -> 6000 outputs, the model's stated matmul precision)
    on DAG lattices at the benchmark cell's shapes, ``backend="auto"``
    (the levelized engine under jit), as the cell jits it: fits one
    chip's memory."""
    from repro.configs.acoustic import get_acoustic_config
    from repro.core.optim import config_for
    from repro.launch import steps
    from repro.models import acoustic

    # jit_train_step asks the default backend (CPU here) whether to pass
    # the TPU compiler options; the described chip is a TPU
    monkeypatch.setattr(dispatch, "compiled_backend", lambda: True)
    acfg = get_acoustic_config("tdnn-asr")
    ocfg = config_for("nghf", cg_iters=8, ng_iters=4, lam=1.0,
                      preconditioner="share_counts")
    place = lambda t: jax.tree.map(                         # noqa: E731
        lambda x: _spec(x.shape, x.dtype, chip), t)
    params = place(jax.eval_shape(
        lambda: acoustic.init_params(acfg, jax.random.PRNGKey(0))))
    fn, opt = steps.build_sequence_step(
        acfg, ocfg, loss="mpe", kappa=0.5, backend="auto",
        share_counts=acoustic.share_counts(acfg, params))
    state = place(jax.eval_shape(opt.init, params))
    grad_batch = _dag_batch(chip, TDNN_CELL["grad"], acfg)
    cg_batch = _dag_batch(chip, TDNN_CELL["cg"], acfg)
    compiled = steps.jit_train_step(fn).lower(
        params, state, grad_batch, cg_batch).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    record_property("temp_size_in_bytes", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < V5E_HBM_BYTES


def test_arc_scores_gather_in_place_for_v5e(chip):
    """``arc_scores`` with its JVP and VJP at the benchmark cell's CG
    shape (16 x 512 frames, 6000 outputs, 384 arcs) gathers from the
    cumsum in place: no relayout loop (``while`` over
    ``dynamic-update-slice``) and no flat or zero-row padded copy of the
    (16, 513, 6000) grid, which took 792 MB of temp memory."""
    from repro.lattice_engine.common import arc_scores
    from repro.losses.lattice import Lattice

    B, T, A = 16, 512, 384
    lp = _spec((B, T, K), jnp.float32, chip)
    idx = _spec((B, A), jnp.int32, chip)

    def fn(log_probs, start, end, label, tangent, cotangent):
        lat = Lattice(start, end, label, *(None,) * 9)
        score = lambda x: arc_scores(lat, x, 0.5)           # noqa: E731
        y, dy = jax.jvp(score, (log_probs,), (tangent,))
        _, vjp = jax.vjp(score, log_probs)
        return y, dy, vjp(cotangent)[0]

    compiled = jax.jit(fn).lower(
        lp, idx, idx, idx, lp, _spec((B, A), jnp.float32, chip)).compile()
    text = compiled.as_text()
    assert " while(" not in text
    assert "dynamic-update-slice(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 600e6
