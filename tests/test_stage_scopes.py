"""The NGHF update's named stages, its candidate-evaluation counter, and
the trainer loop's host spans.

The stages are ``jax.named_scope``s where the work is traced
(``grad_stage``, ``curvature_product``, ``candidate_eval``, ``cg_solve``,
``lattice_stats``); a profiler trace attributes each device operation to
them through its HLO ``op_name``.  They change metadata only: the
compiled program keeps every instruction and fusion."""
import contextlib
import glob
import re

import jax
import numpy as np
import pytest

from repro.configs.acoustic import LSTM
from repro.core.optim import SecondOrderConfig
from repro.data.synthetic import asr_batch
from repro.launch.steps import build_sequence_step
from repro.models import acoustic

CFG = LSTM.smoke().replace(hidden_dim=16, num_outputs=12)
STAGES = ("grad_stage", "curvature_product", "candidate_eval", "cg_solve",
          "lattice_stats")
SPANS = ("train.make_batch", "train.update", "train.read_metrics",
         "train.checkpoint", "train.rebuild")


def _step_args():
    socfg = SecondOrderConfig(method="nghf", cg_iters=3, ng_iters=2)
    params = acoustic.init_params(CFG, jax.random.PRNGKey(0))
    counts = acoustic.share_counts(CFG, params)
    fn, opt = build_sequence_step(CFG, socfg, loss="mpe", kappa=0.5,
                                  share_counts=counts)
    kw = dict(num_frames=16, num_states=CFG.num_outputs,
              input_dim=CFG.input_dim)
    return fn, opt, (params, opt.init(params), asr_batch(0, batch=8, **kw),
                     asr_batch(1, batch=4, **kw))


def _compiled_text(fn, args):
    # JAX's persistent compilation cache keys a program on its HLO without
    # the op_name metadata: with the cache on (an earlier test in the
    # process may turn it on), the program compiled without scopes would
    # load the scoped one's executable.  So compile afresh.
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def step():
    fn, opt, args = _step_args()
    return opt, _compiled_text(fn, args)


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def _holds(op_name, scope):
    # JAX wraps a scope traced under a transform: jvp(lattice_stats)
    return scope in re.split(r"[/()]", op_name)


def test_sequence_step_names_every_stage(step):
    names = _op_names(step[1])
    for stage in STAGES:
        assert any(_holds(n, stage) for n in names), stage
    # under autodiff the gradient stage's lattice statistics run forward
    # and transposed
    assert any("grad_stage/" in n and "transpose(jvp(lattice_stats))" in n
               for n in names)
    assert any(_holds(n, "cg_solve") and _holds(n, "curvature_product")
               and _holds(n, "lattice_stats") for n in names)
    # the zero update's evaluation runs outside the solve
    assert any(_holds(n, "candidate_eval") and not _holds(n, "cg_solve")
               for n in names)


def _counts(text):
    body = [ln for ln in text.splitlines() if " = " in ln
            and not ln.lstrip().startswith(("HloModule", "//"))]
    return len(body), sum(" fusion(" in ln for ln in body)


def test_scopes_change_no_instruction(step, monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    fn, _, args = _step_args()
    plain = _compiled_text(fn, args)
    assert not any(_holds(n, s) for n in _op_names(plain) for s in STAGES)
    assert _counts(plain) == _counts(step[1])


def test_cg_evals_counts_the_evaluations_that_ran():
    # the optimiser's own metrics keep the per-iteration candidate losses
    # that the jitted step's scalar metrics drop
    _, opt, args = _step_args()
    m = jax.jit(opt.step)(*args)[2]
    losses = np.asarray(m["cg_losses"])
    # every finite candidate loss, plus the zero update's evaluation
    assert int(m["cg_evals"]) == int(np.isfinite(losses).sum()) + 1
    assert m["cg_evals"].dtype == np.int32


def test_train_sequence_profile_dir_traces_the_loop_spans(tmp_path):
    from repro.launch.train import train_sequence

    trace = tmp_path / "trace"
    _, log = train_sequence(
        acfg=CFG, optimizer="nghf", loss="mpe", steps=2, batch=4,
        cg_batch=4, frames=16, cg_iters=2, ng_iters=1, verbose=False,
        ckpt_dir=str(tmp_path / "ck"), ckpt_every=1,
        curvature_sample_schedule="0:1.0,1:0.5", profile_dir=str(trace))
    assert all("cg_evals" in row for row in log)
    paths = glob.glob(str(trace / "**" / "*.xplane.pb"), recursive=True)
    assert len(paths) == 1
    data = jax.profiler.ProfileData.from_file(paths[0])
    names = [e.name for plane in data.planes if plane.name.startswith("/host")
             for line in plane.lines for e in line.events
             if e.name.startswith("train.")]
    # the first update (which compiles) is not traced, the second is
    assert sorted(set(names)) == sorted(SPANS)
    assert names.count("train.update") == 1
