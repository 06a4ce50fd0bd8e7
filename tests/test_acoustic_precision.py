"""The TDNN's stated matmul precision and its named scopes, in the lowered
NGHF sequence step, and the TDNN against the benchmark's plain reference.

``models/acoustic.py`` states a precision for the TDNN's dots, by kind of
layer (``TDNN_PRECISION``: the five spliced "hidden" layers and the
"output" layer).  JAX carries a dot's precision into its JVP and its
transpose, so the curvature products and the backward pass run the
passes the forward runs.  The LSTM's dots state none: its compiled update
is the one it was."""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.acoustic import LSTM, TDNN_SIGMOID
from repro.core.optim import SecondOrderConfig
from repro.data.synthetic import asr_batch
from repro.launch.steps import build_sequence_step
from repro.models import acoustic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TDNN = TDNN_SIGMOID.smoke().replace(hidden_dim=16)
LSTM_CFG = LSTM.smoke().replace(hidden_dim=16, num_outputs=12)
# HLO's spelling of a dot's precision; DEFAULT prints no attribute
HLO_NAME = {None: None, jax.lax.Precision.DEFAULT: None,
            jax.lax.Precision.HIGH: "high",
            jax.lax.Precision.HIGHEST: "highest"}


def _lowered_hlo(cfg):
    socfg = SecondOrderConfig(method="nghf", cg_iters=2, ng_iters=1)
    params = acoustic.init_params(cfg, jax.random.PRNGKey(0))
    fn, opt = build_sequence_step(cfg, socfg, loss="mpe", kappa=0.5,
                                  share_counts=acoustic.share_counts(
                                      cfg, params))
    kw = dict(num_frames=16, num_states=cfg.num_outputs,
              input_dim=cfg.input_dim)
    return jax.jit(fn).lower(
        params, opt.init(params), asr_batch(0, batch=4, **kw),
        asr_batch(1, batch=2, **kw)).as_text(dialect="hlo", debug_info=True)


def _dots(text):
    """[(precision or None, op_name)] of every dot of the HLO text."""
    out = []
    for line in text.splitlines():
        if " dot(" not in line:
            continue
        prec = re.search(r"operand_precision=\{(\w+),", line)
        name = re.search(r'op_name="([^"]*)"', line)
        out.append((prec and prec.group(1), name.group(1) if name else ""))
    return out


def _holds(op_name, scope):
    # JAX wraps a scope traced under a transform: jvp(output_layer)
    return scope in re.split(r"[/()]", op_name)


@pytest.fixture(scope="module")
def tdnn_hlo():
    return _lowered_hlo(TDNN)


def test_tdnn_states_a_precision():
    assert set(acoustic.TDNN_PRECISION) == {"hidden", "output"}
    assert any(HLO_NAME[p] is not None
               for p in acoustic.TDNN_PRECISION.values())


def _kinds(names, scope):
    """Which of forward, JVP and transpose the op names under ``scope``
    hold: a candidate's evaluation runs the layer forward, the products
    and the gradient's linearisation as a JVP, the backward transposed."""
    names = [n for n in names if _holds(n, scope)]
    return {"forward": any(f"/{scope}/" in n for n in names),
            "jvp": any(f"jvp({scope})" in n and "transpose" not in n
                       for n in names),
            "transpose": any(f"transpose(jvp({scope}))" in n
                             for n in names)}


def test_tdnn_dots_carry_the_stated_precision(tdnn_hlo):
    dots = _dots(tdnn_hlo)
    for kind, scope in (("output", "output_layer"), ("hidden", "tdnn_hidden")):
        want = HLO_NAME[acoustic.TDNN_PRECISION[kind]]
        names = [n for _, n in dots if _holds(n, scope)]
        for prec, name in dots:
            if _holds(name, scope):
                assert prec == want, (name, prec, want)
        # forward (a candidate's evaluation), JVP (the products, the
        # gradient's linearisation) and transpose (the backward)
        assert _kinds(names, scope) == dict.fromkeys(
            ("forward", "jvp", "transpose"), True), scope
    # every dot of the step is one of the model's, under one of the two
    assert all(_holds(n, "output_layer") != _holds(n, "tdnn_hidden")
               for _, n in dots)


def test_a_kind_with_no_stated_precision_runs_the_ambient_one(monkeypatch):
    monkeypatch.setattr(acoustic, "TDNN_PRECISION",
                        dict.fromkeys(acoustic.TDNN_PRECISION))
    text = _lowered_hlo(TDNN)
    dots = _dots(text)
    assert dots and all(prec is None for prec, _ in dots)
    assert "operand_precision" not in text


def test_tdnn_scopes_name_the_splice_and_the_output_layer(tdnn_hlo):
    names = re.findall(r'op_name="([^"]*)"', tdnn_hlo)
    assert any(_holds(n, "tdnn_splice") for n in names)
    assert any("transpose(jvp(tdnn_splice))" in n for n in names)
    assert any(_holds(n, "tdnn_hidden") for n in names)
    assert any(_holds(n, "output_layer") for n in names)
    # the splice is not inside a layer's scope: the three partition the
    # model's work
    assert not any(_holds(n, "tdnn_splice") and _holds(n, "tdnn_hidden")
                   for n in names)


def test_lstm_step_states_no_precision():
    text = _lowered_hlo(LSTM_CFG)
    dots = _dots(text)
    assert dots and all(prec is None for prec, _ in dots)
    assert "operand_precision" not in text
    for scope in ("output_layer", "tdnn_splice", "tdnn_hidden"):
        assert scope not in text


def test_tdnn_agrees_with_the_reference():
    """Forward and gradient against ``bench/reference/model.py`` on
    seeded weights: f32 on the CPU on both sides, where a dot's precision
    changes nothing; the two differ only in how XLA orders the sums, so
    the tolerances are a few float32 roundings (logits of size ~1, a
    gradient summed over 2 x 24 frames)."""
    from bench.reference import model as ref

    cfg = {"kind": "tdnn", "input_dim": TDNN.input_dim,
           "hidden_dim": TDNN.hidden_dim,
           "tdnn_contexts": [list(c) for c in TDNN.tdnn_contexts],
           "num_outputs": TDNN.num_outputs, "activation": TDNN.activation}
    params = ref.make_weights(cfg, 2**31 + 15)
    feats = jax.random.normal(jax.random.PRNGKey(3),
                              (2, 24, TDNN.input_dim), jnp.float32)
    cot = jax.random.normal(jax.random.PRNGKey(4),
                            (2, 24, TDNN.num_outputs), jnp.float32)
    got = acoustic.forward(TDNN, params, feats)
    want = ref.forward(cfg, params, feats)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    g_got = jax.grad(lambda p: jnp.sum(
        acoustic.forward(TDNN, p, feats) * cot))(params)
    g_want = jax.grad(lambda p: jnp.sum(ref.forward(cfg, p, feats) * cot))(
        params)
    assert jax.tree.structure(g_got) == jax.tree.structure(g_want)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
