"""The one lattice-statistics entry point: ``lattice_stats``.

    stats = lattice_stats(lat, log_probs, kappa, backend="auto")

``accumulators`` selects how much of the statistics set is computed:

  * ``"full"``      — the complete arc-layout ``FBStats`` (alpha, beta,
                      gamma, correctness accumulators, logZ, c_avg).
  * ``"loss_only"`` — just ``LossStats(logZ, c_avg)``: the scan/levelized
                      backends skip the backward recursion entirely, and
                      the Pallas backend runs the FUSED forward-only
                      kernel (arc scores built in-kernel from the frame
                      log-probs — no per-arc statistics in the graph).
                      This is the CG candidate-evaluation fast path
                      (paper Alg. 1; ~73 % of CG wall time in Table 1).
                      Values and grads agree with the full path (tested).

Backends (all produce the same arc-layout ``FBStats``):

  * ``"scan"``      — per-arc ``lax.scan`` reference (O(A) sequential steps)
  * ``"levelized"`` — level-parallel scan over ``Lattice.level_arcs``
                      frontiers (O(levels) sequential steps)
  * ``"pallas"``    — TPU kernels behind a ``custom_jvp``, for ANY
                      topology: statically-known sausage (confusion-
                      network) lattices run the specialised fully-
                      connected segment kernels; every other DAG — and
                      any traced lattice — runs the general-DAG frontier
                      kernels (level-major scores + predecessor/successor
                      positions).  Never falls back to a scan backend.
  * ``"auto"``      — Pallas when the default JAX backend is TPU and the
                      lattice is levelized (``level_arcs`` present) and
                      concrete; the levelized scan otherwise.  Inside
                      ``jit`` the arrays are tracers and auto resolves to
                      the levelized scan — pass ``backend="pallas"``
                      explicitly (or resolve outside the jit boundary) to
                      commit to the kernel path (the pallas backend
                      handles traced lattices via the DAG kernels).
                      ``REPRO_LATTICE_BACKEND`` overrides auto everywhere.
"""
from __future__ import annotations

import os

import jax

from repro.lattice_engine.common import (ACCUMULATORS, FBStats, LossStats,
                                         check_accumulators,
                                         lattice_is_sausage)
from repro.lattice_engine.levelized import forward_backward_levelized
from repro.lattice_engine.pallas_backend import forward_backward_pallas
from repro.lattice_engine.scan_backend import forward_backward_scan
from repro.losses.lattice import Lattice

BACKENDS = ("scan", "levelized", "pallas")

_DISPATCH = {
    "scan": forward_backward_scan,
    "levelized": forward_backward_levelized,
    "pallas": forward_backward_pallas,
}


def resolve_backend(backend: str, lat: Lattice) -> str:
    """Turn 'auto' into a concrete backend name (see module docstring)."""
    if backend != "auto":
        if backend not in BACKENDS:
            raise ValueError(
                f"unknown lattice backend {backend!r}; expected one of "
                f"{BACKENDS + ('auto',)}")
        return backend
    forced = os.environ.get("REPRO_LATTICE_BACKEND")
    if forced:
        if forced not in BACKENDS:
            raise ValueError(
                f"REPRO_LATTICE_BACKEND={forced!r} not in {BACKENDS}")
        return forced
    if jax.default_backend() == "tpu" and lat.level_arcs is not None \
            and not isinstance(lat.level_arcs, jax.core.Tracer):
        # any topology: the pallas backend dispatches sausage vs DAG
        # kernels internally (lattice_is_sausage)
        return "pallas"
    return "levelized"


def lattice_stats(lat: Lattice, log_probs, kappa: float,
                  backend: str = "auto", mesh=None,
                  accumulators: str = "full") -> FBStats | LossStats:
    """Differentiable lattice forward-backward statistics (one API over
    the scan / levelized / Pallas backends).

    Args:
      lat: batched ``losses.lattice.Lattice`` (any DAG topology; every
        backend honours ``arc_mask`` ragged-batch padding).  The
        levelized and Pallas backends need ``lat.level_arcs``
        (``batch_lattices`` builds it).
      log_probs: (B, T, K) frame log-probabilities (``log_softmax`` of
        the acoustic logits) — the only differentiable input;
        ``jax.grad``/``jax.jvp`` through the returned ``logZ``/``c_avg``
        are exact on every backend (the Pallas kernels sit behind
        ``custom_jvp`` occupancy identities).
      kappa: acoustic scale (may be traced; it is linear in the score
        construction on every backend).
      backend: ``"scan" | "levelized" | "pallas" | "auto"`` — see module
        docstring.  ``"pallas"`` supports ANY topology (sausage kernels
        for statically-known confusion networks, general-DAG frontier
        kernels otherwise; never a scan fallback).
      mesh: optional ``jax.sharding.Mesh`` — the (B, A) arc tensors
        (scores, alpha/beta/gamma, correctness accumulators) are then
        ``with_sharding_constraint``-ed to its data axes so the
        statistics stage stays GSPMD data-parallel under pjit (see
        ``launch.sharding.lattice_shardings`` for the input side).
      accumulators: ``"full"`` -> ``FBStats`` (alpha, beta, gamma,
        correctness accumulators, logZ, c_avg — arc layout (B, A));
        ``"loss_only"`` -> ``LossStats(logZ, c_avg)`` with the backward
        recursion (and, on the Pallas backend, all per-arc statistics)
        elided — the CG candidate-evaluation fast path.

    Returns:
      ``FBStats`` or ``LossStats`` (see ``lattice_engine.common``); on
      the Pallas backend only ``logZ``/``c_avg`` carry gradients — the
      per-arc statistics are constants (losses only differentiate the
      former; tested equal to the scan backend's autodiff).
    """
    check_accumulators(accumulators)
    with jax.named_scope("lattice_stats"):
        return _DISPATCH[resolve_backend(backend, lat)](
            lat, log_probs, kappa, mesh=mesh, accumulators=accumulators)
