"""Acoustic models of the paper's Sec. 7, plain ``jax.numpy``.

The parameter tree (names and shapes) is the one the program's
``repro.models.acoustic`` uses, so one tree of weights feeds both.  Two
conventions the paper does not state are the program's: the LSTM's four
gates come from one matrix over ``[x_t, h_{t-1}]`` in the order
(input, forget, cell, output), with +1 on the forget gate's
pre-activation; a TDNN splice repeats the edge frame past either end.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def layer_shapes(cfg):
    """{layer name: (fan_in, fan_out)} of every affine layer."""
    h, shapes = cfg["hidden_dim"], {}
    if cfg["kind"] == "lstm":
        d = cfg["input_dim"]
        for i in range(cfg["num_recurrent_layers"]):
            shapes[f"rec{i}"] = (d + h, 4 * h)
            d = h
        for i in range(cfg["num_ff_layers"]):
            shapes[f"ff{i}"] = (d, h)
            d = h
    elif cfg["kind"] == "tdnn":
        d = cfg["input_dim"]
        for i, ctx in enumerate(cfg["tdnn_contexts"]):
            shapes[f"tdnn{i}"] = (d * len(ctx), h)
            d = h
    else:
        raise ValueError(f"unknown model kind {cfg['kind']!r}")
    shapes["out"] = (d, cfg["num_outputs"])
    return shapes


def init_params(cfg, key):
    """Random weights from ``key``: w ~ N(0, 1/fan_in), b = 0 (f32)."""
    shapes = layer_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    return {name: {"w": jax.random.normal(k, shp, jnp.float32)
                   * (1.0 / shp[0]) ** 0.5,
                   "b": jnp.zeros((shp[1],), jnp.float32)}
            for k, (name, shp) in zip(keys, shapes.items())}


def make_weights(cfg, seed):
    """The benchmark's weights for ``seed`` (any non-negative integer),
    made on the device in one jitted call."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    key = jax.random.wrap_key_data(words)
    return jax.jit(lambda k: init_params(cfg, k))(key)


def share_counts(cfg):
    """{layer name: c}: how often a layer's weights are applied per output
    frame (Sec. 4.3) — ``unfold`` for a recurrent layer, the product of
    the later splices' widths for a TDNN layer, 1 otherwise."""
    counts = {}
    for name in layer_shapes(cfg):
        c = 1.0
        if name.startswith("rec"):
            c = float(cfg["unfold"])
        elif name.startswith("tdnn"):
            for ctx in cfg["tdnn_contexts"][int(name[4:]) + 1:]:
                c *= len(ctx)
        counts[name] = c
    return counts


def _act(name, x):
    if name == "sigmoid":
        return jax.nn.sigmoid(x)
    if name == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown activation {name!r}")


def _int8(a):
    """``a`` rounded to int8 with one symmetric scale per tensor, and back;
    the gradient passes straight through the rounding."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 127.0
    q = jnp.round(a / scale) * scale
    return a + jax.lax.stop_gradient(q - a)


def _affine(p, x):
    if p.get("int8"):
        return jnp.dot(_int8(x), _int8(p["w"])) + p["b"]
    return jnp.dot(x, p["w"]) + p["b"]


def _lstm(p, x):
    B, _, _ = x.shape
    H = p["w"].shape[1] // 4
    zeros = jnp.zeros((B, H), x.dtype)

    def step(carry, x_t):
        c, h = carry
        z = _affine(p, jnp.concatenate([x_t, h], axis=-1))
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (c, h), h

    _, hs = jax.lax.scan(step, (zeros, zeros), jnp.swapaxes(x, 0, 1))
    return jnp.swapaxes(hs, 0, 1)


def _splice(x, ctx):
    T = x.shape[1]
    return jnp.concatenate(
        [x[:, jnp.clip(jnp.arange(T) + c, 0, T - 1)] for c in ctx], axis=-1)


def forward(cfg, params, feats, dtype=jnp.float32):
    """feats (B, T, input_dim) -> logits (B, T, num_outputs).

    ``dtype`` is the compute type of weights, activations and logits, or
    "int8": float32 with every matmul's operands rounded to int8 (the
    lower-precision controls of the comparison)."""
    if isinstance(dtype, str) and dtype == "int8":
        p = {k: dict(v, int8=True) for k, v in params.items()}
        x = feats.astype(jnp.float32)
    else:
        p = jax.tree.map(lambda a: a.astype(dtype), params)
        x = feats.astype(dtype)
    if cfg["kind"] == "lstm":
        for i in range(cfg["num_recurrent_layers"]):
            x = _lstm(p[f"rec{i}"], x)
        for i in range(cfg["num_ff_layers"]):
            x = _act(cfg["activation"], _affine(p[f"ff{i}"], x))
    else:
        for i, ctx in enumerate(cfg["tdnn_contexts"]):
            x = _act(cfg["activation"], _affine(p[f"tdnn{i}"],
                                                _splice(x, ctx)))
    out = _affine(p["out"], x)
    return out.astype(jnp.float32) if isinstance(dtype, str) else out
