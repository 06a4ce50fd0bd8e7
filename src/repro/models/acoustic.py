"""Paper-faithful acoustic models (Sec. 4.3 / 7 of the NGHF paper).

Hybrid NN-HMM output-probability models mapping acoustic features
(B, T, input_dim) to per-frame logits over ~6000 tied triphone states:

  * RNN  — two 1000-dim Elman recurrent layers + one 1000-dim FF layer.
  * LSTM — same structure with LSTM cells (paper Sec. 4.3 equations).
  * TDNN — five 1000-dim FC layers performing 1-d convolutions across time
           with context splices {-2..2},{-1,2},{-3,3},{-7,2},{0}.

These carry nontrivial ``share_counts`` (Sec. 4.3): recurrent cell weights
are applied ``unfold`` times per output frame under truncated BPTT, and a
TDNN layer viewed as a duplicated tree is applied prod(|ctx_j|, j>l) times —
exactly what the paper's shared-parameter preconditioner normalises by.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import _act, dense_init


def _fc(key, d_in, d_out):
    k1, _ = jax.random.split(key)
    return {"w": dense_init(k1, d_in, d_out, jnp.float32),
            "b": jnp.zeros((d_out,), jnp.float32)}


def _fc_apply(p, x):
    return x @ p["w"] + p["b"]


# Matmul precision of the TDNN's dots, by kind of layer: "hidden" for the
# five spliced layers, "output" for the 1000 -> 6000 layer; None is the
# ambient precision.  On a TPU v5e (PERF.md, Findings; 12 seeds) the
# default one-pass bf16 makes the TDNN's NGHF MPE update on DAG lattices
# depart from the float32 reference: loss gap up to 4.3e-3, another CG
# candidate on 6 seeds.  HIGH (three bf16 passes) on the hidden layers
# alone departs as far (up to 1.6e-3, 7 seeds), as does the output layer
# alone; HIGH on all six agrees to 9e-6 and keeps the reference's
# candidates but on one near tie.  JAX carries a dot's precision into its
# JVP and transpose, so the curvature products and the backward pass run
# at it too.
TDNN_PRECISION = {"hidden": jax.lax.Precision.HIGH,
                  "output": jax.lax.Precision.HIGH}


def _tdnn_fc_apply(p, x, precision):
    """``_fc_apply`` at ``precision``.  A stated precision runs over the
    (B*T) rows of x: at (B, T, 6000) and HIGH the TPU compiler overruns its
    64 MiB scoped-VMEM limit on the output layer's product cotangent
    (98.9 MiB at 32 x 512 frames); over rows it does not.  T a multiple of
    8 makes both reshapes free on the (8, 128) tiles."""
    if precision is None:
        return _fc_apply(p, x)
    B, T, D = x.shape
    y = jnp.matmul(x.reshape(B * T, D), p["w"], precision=precision)
    return y.reshape(B, T, -1) + p["b"]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg, key):
    ks = jax.random.split(key, 16)
    h = cfg.hidden_dim
    params = {}
    if cfg.kind in ("rnn", "lstm"):
        mult = 4 if cfg.kind == "lstm" else 1
        d_in = cfg.input_dim
        for i in range(cfg.num_recurrent_layers):
            params[f"rec{i}"] = _fc(ks[i], d_in + h, mult * h)
            d_in = h
        for i in range(cfg.num_ff_layers):
            params[f"ff{i}"] = _fc(ks[4 + i], d_in, h)
            d_in = h
        params["out"] = _fc(ks[8], d_in, cfg.num_outputs)
    elif cfg.kind == "tdnn":
        d_in = cfg.input_dim
        for i, ctx in enumerate(cfg.tdnn_contexts):
            params[f"tdnn{i}"] = _fc(ks[i], d_in * len(ctx), h)
            d_in = h
        params["out"] = _fc(ks[8], d_in, cfg.num_outputs)
    else:
        raise ValueError(cfg.kind)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rnn_layer(cfg, p, x):
    """Elman layer: h_t = act(U concat(x_t, h_{t-1}) + b).  x: (B,T,D)."""
    B, T, _ = x.shape
    h0 = jnp.zeros((B, cfg.hidden_dim), x.dtype)

    def step(h, x_t):
        h_new = _act(cfg.activation, _fc_apply(p, jnp.concatenate([x_t, h], -1)))
        return h_new, h_new

    _, hs = jax.lax.scan(step, h0, x.transpose(1, 0, 2))
    return hs.transpose(1, 0, 2)


def _lstm_layer(cfg, p, x):
    """Paper Sec. 4.3 LSTM equations (four FC gates + Hadamard products)."""
    B, T, _ = x.shape
    H = cfg.hidden_dim
    c0 = jnp.zeros((B, H), x.dtype)
    h0 = jnp.zeros((B, H), x.dtype)

    def step(carry, x_t):
        c, h = carry
        z = _fc_apply(p, jnp.concatenate([x_t, h], -1))
        i, f, g, o = jnp.split(z, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f + 1.0), jax.nn.sigmoid(o)
        c_new = f * c + i * jnp.tanh(g)
        h_new = o * jnp.tanh(c_new)
        return (c_new, h_new), h_new

    _, hs = jax.lax.scan(step, (c0, h0), x.transpose(1, 0, 2))
    return hs.transpose(1, 0, 2)


def _splice(x, ctx):
    """Concatenate x shifted by each offset in ctx (edge-padded)."""
    T = x.shape[1]
    cols = []
    for c in ctx:
        idx = jnp.clip(jnp.arange(T) + c, 0, T - 1)
        cols.append(x[:, idx])
    return jnp.concatenate(cols, axis=-1)


def forward(cfg, params, feats):
    """feats: (B, T, input_dim) -> logits (B, T, num_outputs)."""
    x = feats.astype(jnp.float32)
    if cfg.kind in ("rnn", "lstm"):
        layer = _lstm_layer if cfg.kind == "lstm" else _rnn_layer
        for i in range(cfg.num_recurrent_layers):
            x = layer(cfg, params[f"rec{i}"], x)
        for i in range(cfg.num_ff_layers):
            x = _act(cfg.activation, _fc_apply(params[f"ff{i}"], x))
        return _fc_apply(params["out"], x)
    for i, ctx in enumerate(cfg.tdnn_contexts):
        with jax.named_scope("tdnn_splice"):
            x_ctx = _splice(x, ctx)
        with jax.named_scope("tdnn_hidden"):
            x = _act(cfg.activation, _tdnn_fc_apply(
                params[f"tdnn{i}"], x_ctx, TDNN_PRECISION["hidden"]))
    with jax.named_scope("output_layer"):
        return _tdnn_fc_apply(params["out"], x, TDNN_PRECISION["output"])


# ---------------------------------------------------------------------------
# shared-parameter counts (paper Sec. 4.3)
# ---------------------------------------------------------------------------

def share_counts(cfg, params):
    """Per-leaf application counts c(i) for the CG preconditioner.

    Recurrent cells: ``unfold`` applications per output frame (truncated
    BPTT depth).  TDNN layer l (tree view): prod of |ctx_j| for j > l.
    FF / output layers: 1.
    """
    counts = {}
    if cfg.kind in ("rnn", "lstm"):
        for i in range(cfg.num_recurrent_layers):
            counts[f"rec{i}"] = float(cfg.unfold)
        for i in range(cfg.num_ff_layers):
            counts[f"ff{i}"] = 1.0
    else:
        n = len(cfg.tdnn_contexts)
        for i in range(n):
            c = 1.0
            for j in range(i + 1, n):
                c *= len(cfg.tdnn_contexts[j])
            counts[f"tdnn{i}"] = c
    counts["out"] = 1.0
    return jax.tree.map(
        lambda leaf, path=None: leaf,
        {k: jax.tree.map(lambda _: jnp.asarray(counts[k], jnp.float32), v)
         for k, v in params.items() if k in counts} |
        {k: jax.tree.map(lambda _: jnp.asarray(1.0, jnp.float32), v)
         for k, v in params.items() if k not in counts})
