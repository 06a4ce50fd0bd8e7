"""Lattice statistics and the MPE and MMI losses, plain ``jax.numpy``.

The forward recursion walks the arcs one at a time in id order (arcs are
sorted topologically), so it shares no code or layout with the program's
level-parallel engine.  Gradients come from autodiff through it.

  alpha(a)   = own(a) + log sum_{p in pred(a)} exp alpha(p)   (start: own)
  c_alpha(a) = corr(a) + sum_p softmax_p(alpha(p)) c_alpha(p)
  logZ       = log sum_{final a} exp alpha(a)
  c_avg      = sum_{final a} softmax_a(alpha(a)) c_alpha(a)

with own(a) = kappa * sum_{t in [start, end)} log p(label(a) | o_t) + lm(a).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e30


def _lse(x, valid):
    """log sum exp over the valid entries; NEG (with zero gradient) where
    none is valid.  Invalid entries are replaced before ``exp``, so that
    no overflow there turns a zero cotangent into NaN."""
    anyv = jnp.any(valid)
    m = jnp.where(anyv, jnp.max(jnp.where(valid, x, NEG)), 0.0)
    e = jnp.where(valid, jnp.exp(jnp.where(valid, x, m) - m), 0.0)
    s = jnp.where(anyv, jnp.sum(e), 1.0)
    return jnp.where(anyv, jnp.log(s) + m, NEG)


def _weights(x, valid):
    """softmax over the valid entries; all zero where none is valid."""
    m = jnp.where(jnp.any(valid), jnp.max(jnp.where(valid, x, NEG)), 0.0)
    e = jnp.where(valid, jnp.exp(jnp.where(valid, x, m) - m), 0.0)
    return e / jnp.maximum(jnp.sum(e), 1e-30)


def arc_scores(lat, log_probs, kappa):
    """(B, A): kappa * the sum of the arc label's frame log-probs over the
    arc's span, plus its LM score."""
    T = log_probs.shape[1]
    lab = jnp.take_along_axis(log_probs, lat["label"][:, None, :], axis=2)
    t = jnp.arange(T)[None, None, :]
    span = (t >= lat["start_t"][:, :, None]) & (t < lat["end_t"][:, :, None])
    ac = jnp.sum(jnp.where(span, jnp.swapaxes(lab, 1, 2), 0.0), axis=-1)
    return kappa * ac + lat["lm"].astype(ac.dtype)


def _one_utterance(own, corr, preds, is_start, is_final, mask):
    A = own.shape[0]

    def body(carry, a):
        alpha, c_alpha = carry
        p = preds[a]
        valid = (p >= 0) & mask[jnp.maximum(p, 0)]
        pa = alpha[jnp.maximum(p, 0)]
        pc = c_alpha[jnp.maximum(p, 0)]
        a_val = jnp.where(is_start[a], own[a], own[a] + _lse(pa, valid))
        c_val = corr[a] + jnp.where(is_start[a], 0.0,
                                    jnp.sum(_weights(pa, valid) * pc))
        alpha = alpha.at[a].set(jnp.where(mask[a], a_val, NEG))
        c_alpha = c_alpha.at[a].set(jnp.where(mask[a], c_val, 0.0))
        return (alpha, c_alpha), None

    (alpha, c_alpha), _ = jax.lax.scan(
        body, (jnp.full((A,), NEG, own.dtype), jnp.zeros((A,), own.dtype)),
        jnp.arange(A))
    fin = is_final & mask
    return _lse(alpha, fin), jnp.sum(_weights(alpha, fin) * c_alpha)


def stats(lat, log_probs, kappa):
    """(logZ (B,), c_avg (B,)) of a batch of lattices, computed in the
    dtype of ``log_probs``."""
    own = arc_scores(lat, log_probs, kappa)
    corr = lat["corr"].astype(own.dtype)
    return jax.vmap(_one_utterance)(own, corr, lat["preds"],
                                    lat["is_start"], lat["is_final"],
                                    lat["arc_mask"])


def frames(lat):
    """(B,) real frames per utterance: the last valid arc's end."""
    return jnp.max(jnp.where(lat["arc_mask"], lat["end_t"], 0),
                   axis=-1).astype(jnp.float32)


def mpe_loss(logits, lat, kappa, batch_size):
    """-(1/batch_size) * sum_b c_avg_b / max(n_ref_b, 1) (Eqn. 3, phone
    accuracy as the gain).  ``batch_size`` is the whole batch's, so that
    blocks of rows sum to the batch loss."""
    lp = jax.nn.log_softmax(logits, axis=-1)
    _, c_avg = stats(lat, lp, kappa)
    return -jnp.sum(c_avg / jnp.maximum(lat["num_ref_units"], 1.0)) \
        / batch_size


def mmi_loss(logits, lat, kappa, total_frames):
    """-(1/total_frames) * sum_b (kappa log p(ref_b) - logZ_b) (Eqn. 2),
    the numerator over each utterance's real frames."""
    lp = jax.nn.log_softmax(logits, axis=-1)
    logz, _ = stats(lat, lp, kappa)
    ref = jnp.take_along_axis(lp, lat["ref_states"][..., None], -1)[..., 0]
    t = jnp.arange(lp.shape[1])[None, :]
    real = (t < frames(lat)[:, None]).astype(jnp.float32)
    num = kappa * jnp.sum(ref * real, axis=-1)
    return -jnp.sum(num - logz) / total_frames
