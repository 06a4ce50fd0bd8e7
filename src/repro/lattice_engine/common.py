"""Shared pieces of the lattice engine: the FBStats contract, arc scoring,
log-semiring helpers, and the final reduction from (alpha, beta) to
(logZ, gamma, c_avg).

Every backend (per-arc scan, levelized scan, Pallas kernels — sausage
AND general-DAG, topology-dispatched in ``pallas_backend``) produces the
same ``FBStats`` in arc layout (B, A), so losses and tests are
backend-agnostic.  ``lattice_is_sausage`` below is the static topology
check that picks between the two Pallas kernel families.
"""
from __future__ import annotations

import weakref
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.losses.lattice import Lattice

NEG = -1e30


class FBStats(NamedTuple):
    alpha: jnp.ndarray       # (B, A) forward log score incl. the arc
    beta: jnp.ndarray        # (B, A) backward log score excl. the arc
    logZ: jnp.ndarray        # (B,) total lattice log score
    gamma: jnp.ndarray       # (B, A) arc posterior
    c_alpha: jnp.ndarray     # (B, A) expected partial correctness (incl.)
    c_beta: jnp.ndarray      # (B, A) expected remaining correctness (excl.)
    c_avg: jnp.ndarray       # (B,) expected total correctness
    c_arc: jnp.ndarray       # (B, A) c_q = c_alpha + c_beta


class LossStats(NamedTuple):
    """The ``accumulators="loss_only"`` contract: exactly what the MMI/MPE
    loss *values* need — no per-arc statistics, no backward recursion.
    Field names/meanings match the ``FBStats`` members of the same name so
    loss code is agnostic to which mode produced the statistics."""

    logZ: jnp.ndarray        # (B,) total lattice log score
    c_avg: jnp.ndarray       # (B,) expected total correctness


ACCUMULATORS = ("full", "loss_only")


def check_accumulators(accumulators: str) -> str:
    if accumulators not in ACCUMULATORS:
        raise ValueError(
            f"unknown accumulators mode {accumulators!r}; expected one of "
            f"{ACCUMULATORS}")
    return accumulators


def arc_scores(lat: Lattice, log_probs: jnp.ndarray, kappa: float):
    """Per-arc acoustic score: kappa * sum_{t in span} log p(label | o_t).

    log_probs: (B, T, K) frame log-probabilities (log_softmax of logits).
    Returns (B, A) f32.  Cumulative-sums the (T, K) grid once, then
    gathers only the 2A span endpoints, reading each (b, t, label) of the
    cumsum in place — O(T*K) streaming work + O(A) gathered elements,
    instead of materialising a (T, A) per-arc gather.  On the TPU's tiled
    layout a flatten of the grid to one axis was a relayout copy.

    The cumsum is mean-centred per (b, k) stream: raw partial sums grow
    like t·E[log p] (≈ -t·log K), so at large T the f32 endpoint
    difference of a short span cancels catastrophically against the
    cumulative magnitude.  Centred partial sums stay O(√T·σ); the removed
    linear ramp is restored exactly from the span length.

    The identity itself lives in ``kernels.ref.sausage_arc_scores_ref``
    (one copy, shared with the fused loss-only kernel's oracle and its
    ``custom_jvp`` tangent rule).
    """
    from repro.kernels.ref import sausage_arc_scores_ref
    return sausage_arc_scores_ref(log_probs, lat.start_t, lat.end_t,
                                  lat.label, kappa)


def gather_log(arr, idx):
    """arr: (A,), idx: (...,) with -1 padding -> values with NEG at pads."""
    safe = jnp.maximum(idx, 0)
    return jnp.where(idx >= 0, arr[safe], NEG)


def gather_lin(arr, idx, fill=0.0):
    safe = jnp.maximum(idx, 0)
    return jnp.where(idx >= 0, arr[safe], fill)


def masked_logsumexp(x, axis=-1):
    """logsumexp treating entries at/near ``NEG`` as masked.

    An all-masked row returns exactly ``NEG`` with ZERO gradient: naively,
    ``exp(x - max) = 1`` for every entry of such a row, so softmax-style
    cotangents of 1/W would leak into padded arc scores (e.g. the summed
    ``beta + own`` terms of arcs whose successor slots are all padding).
    Masked entries are zeroed *before* the sum so no gradient flows.
    """
    valid = x > NEG * 0.5
    any_valid = jnp.any(valid, axis=axis)
    m = jnp.max(x, axis=axis, keepdims=True)
    m = jnp.where(m > NEG * 0.5, m, 0.0)       # safe pivot for masked rows
    e = jnp.where(valid, jnp.exp(x - m), 0.0)
    s = jnp.sum(e, axis=axis)
    out = jnp.log(jnp.where(any_valid, s, 1.0)) + jnp.squeeze(m, axis)
    return jnp.where(any_valid, jnp.maximum(out, NEG), NEG)


def masked_softmax(x, axis=-1):
    """Softmax companion of ``masked_logsumexp``: all-masked rows get
    all-zero weights (not uniform 1/W), and masked entries carry no
    gradient.  Used for the expected-correctness weighted means."""
    valid = x > NEG * 0.5
    m = jnp.max(x, axis=axis, keepdims=True)
    m = jnp.where(m > NEG * 0.5, m, 0.0)
    e = jnp.where(valid, jnp.exp(x - m), 0.0)
    s = jnp.sum(e, axis=axis, keepdims=True)
    # any valid row has s >= 1 (the max contributes exp(0)); masked rows
    # divide 0 by 1.
    return e / jnp.maximum(s, 1.0)


def data_constrainer(mesh):
    """``with_sharding_constraint`` factory for batch-leading tensors.

    Returns ``f(x)`` constraining dim 0 of ``x`` over the mesh's data axes
    (``pod``/``data``) and replicating the rest — the GSPMD annotation that
    keeps the vmapped level scans data-parallel instead of silently
    replicated.  Identity when ``mesh`` is None, when the mesh has no data
    axes, or when the batch dim does not divide the data extent (matching
    ``launch.sharding.batch_pspec`` divisibility semantics).
    """
    if mesh is None:
        return lambda x: x
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch.sharding import data_extent   # shared axis policy
    axes, size = data_extent(mesh)
    if not axes:
        return lambda x: x

    def constrain(x):
        if not hasattr(x, "ndim") or x.ndim == 0 or x.shape[0] % size:
            return x
        spec = PartitionSpec(axes, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return constrain


def finalize_loss_only(lat: Lattice, alpha, c_alpha,
                       constrain=None) -> LossStats:
    """Reduce forward-only scores to (logZ, c_avg) — the final-arc
    reduction shared by both accumulator modes."""
    c = constrain if constrain is not None else (lambda x: x)
    alpha, c_alpha = c(alpha), c(c_alpha)
    final_alpha = jnp.where(lat.is_final & lat.arc_mask, alpha, NEG)
    logZ = masked_logsumexp(final_alpha, axis=-1)               # (B,)
    wf = masked_softmax(final_alpha, axis=-1)
    c_avg = jnp.sum(wf * c_alpha, axis=-1)
    return LossStats(logZ=logZ, c_avg=c_avg)


def finalize(lat: Lattice, alpha, beta, c_alpha, c_beta,
             constrain=None) -> FBStats:
    """Reduce per-arc forward/backward scores to the full statistics set."""
    c = constrain if constrain is not None else (lambda x: x)
    alpha, beta = c(alpha), c(beta)
    c_alpha, c_beta = c(c_alpha), c(c_beta)
    logZ, c_avg = finalize_loss_only(lat, alpha, c_alpha)
    gamma = c(jnp.where(lat.arc_mask,
                        jnp.exp(alpha + beta - logZ[:, None]), 0.0))
    return FBStats(alpha=alpha, beta=beta, logZ=logZ, gamma=gamma,
                   c_alpha=c_alpha, c_beta=c_beta, c_avg=c_avg,
                   c_arc=c_alpha + c_beta)


def _concrete(x):  # reprolint: host
    """numpy view of a lattice field, or None if traced/abstract."""
    if x is None or isinstance(x, jax.core.Tracer):
        return None
    try:
        return np.asarray(x)
    except Exception:
        return None


def _is_sausage_uncached(lat: Lattice) -> bool:
    la = _concrete(lat.level_arcs)
    preds = _concrete(lat.preds)
    mask = _concrete(lat.arc_mask)
    is_start = _concrete(lat.is_start)
    is_final = _concrete(lat.is_final)
    if any(x is None for x in (la, preds, mask, is_start, is_final)):
        return False
    B = la.shape[0]
    for b in range(B):
        levels = [set(row[row >= 0].tolist()) for row in la[b]]
        levels = [lv for lv in levels if lv]
        if not levels:
            return False
        for li, lv in enumerate(levels):
            prev = levels[li - 1] if li > 0 else set()
            last = li == len(levels) - 1
            for a in lv:
                p = preds[b, a]
                p = {int(x) for x in p[p >= 0] if mask[b, x]}
                if li == 0:
                    if not is_start[b, a] and p:
                        return False
                elif p != prev:
                    return False
                if bool(is_final[b, a]) != last:
                    return False
    return True


_SAUSAGE_CACHE: dict = {}


def lattice_is_sausage(lat: Lattice) -> bool:
    """Static topology check: True iff every level is fully connected to
    the previous one and exactly the last level's arcs are final — the
    contract of the Pallas sausage kernels.  Returns False whenever the
    lattice is traced (inside jit) or the check cannot be decided.

    The O(B * arcs * preds) walk is memoized per ``level_arcs`` array
    (lattices are immutable), so eager training loops pay it once.
    """
    key_obj = lat.level_arcs
    if key_obj is None or isinstance(key_obj, jax.core.Tracer):
        return False
    k = id(key_obj)
    hit = _SAUSAGE_CACHE.get(k)
    if hit is not None and hit[0]() is key_obj:
        return hit[1]
    val = _is_sausage_uncached(lat)
    try:
        if len(_SAUSAGE_CACHE) > 256:
            _SAUSAGE_CACHE.clear()
        _SAUSAGE_CACHE[k] = (weakref.ref(key_obj), val)
    except TypeError:                      # not weakref-able; skip caching
        pass
    return val


def frame_state_occupancy(lat: Lattice, weights: jnp.ndarray,
                          num_states: int) -> jnp.ndarray:
    """Scatter per-arc weights onto (B, T, K) frame/state occupancies.

    occ[b, t, k] = sum over arcs a with label k and t in [start, end).
    Used by tests to cross-check VJP-derived occupancies and by the
    benchmark reproducing the paper's statistics-collection stage.
    """
    B, A = weights.shape
    T = lat.num_frames

    def per_utt(start, end, label, w):
        t = jnp.arange(T)
        span = (t[None, :] >= start[:, None]) & (t[None, :] < end[:, None])
        contrib = span * w[:, None]                          # (A, T)
        out = jnp.zeros((T, num_states))
        t_ix = jnp.broadcast_to(t[None, :], (A, T))
        l_ix = jnp.broadcast_to(label[:, None], (A, T))
        return out.at[t_ix, l_ix].add(contrib)

    return jax.vmap(per_utt)(lat.start_t, lat.end_t, lat.label, weights)
