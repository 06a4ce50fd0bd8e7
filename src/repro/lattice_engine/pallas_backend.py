"""Pallas backend: lattice statistics on the TPU kernels, for BOTH
sausage and general-DAG topologies.

Topology dispatch happens here, inside the backend: when the lattice is
statically known to be a sausage (confusion network —
``lattice_is_sausage``), the specialised fully-connected segment kernels
run; for every other topology — and whenever the lattice is traced, so
topology cannot be inspected — the GENERAL-DAG kernel pair runs over the
levelized frontier tensors (``losses.lattice.lattice_frontiers``:
level-major scores, predecessor/successor positions, ragged-level
masks).  The DAG kernels are correct for sausages too (a sausage is just
a DAG whose levels are fully connected), so ``backend="pallas"`` NEVER
silently falls back to a scan backend.

``Lattice.level_arcs`` doubles as the gather map from arc layout (B, A)
into the kernels' (B, S, W) segment/alternative layout (levels are
segments for a sausage).  The forward + backward kernels
(``kernels/lattice_fb.py``) are not differentiable by ``jax.grad``
directly — Pallas calls have no autodiff rules — so ``logZ`` and
``c_avg`` are exposed through a ``jax.custom_jvp`` whose tangent rule uses
the closed-form occupancy identities,

    d logZ / d score_a   = gamma_a
    d c_avg / d score_a  = gamma_a * (c_arc_a - c_avg)
    d c_avg / d corr_a   = gamma_a

with gamma/c_arc computed by one extra forward+backward kernel pass.  The
rule is linear in the tangents, so JAX can both push JVPs through it (the
R-operator in ``core/curvature.py``) and transpose it for ``jax.grad`` /
VJPs — occupancy-based EBP, exactly the paper's Sec. 5.2 gradient.

The auxiliary arc statistics (alpha, beta, gamma, ...) are returned as
*constants* (no gradient flows through them); the losses only ever
differentiate ``logZ``/``c_avg``, and under jit the unused direct kernel
calls are dead-code-eliminated.

``accumulators="loss_only"`` routes through the candidate-evaluation
kernels instead (``kernels.lattice_fb.sausage_loss_only`` /
``dag_loss_only``): XLA turns the (B,T,K) log-probs into per-arc scores
(centred cumsum endpoint gather) and gathers them into the (S,W) level
layout, and one forward-only kernel runs the recursion.  No per-arc
statistics and no backward kernel appear in the graph; only
``(logZ, c_avg)`` come back.  Its ``custom_jvp`` uses the same occupancy
identities — the tangent rule runs the kernel pair (gradient and
R-operator passes need gamma anyway); the loss-only path is the pure
*value* evaluation that CG candidate selection executes per iteration.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.lattice_fb import (dag_backward, dag_forward,
                                      dag_loss_only, sausage_backward,
                                      sausage_forward, sausage_loss_only)
from repro.kernels.ref import gather_sausage_ref, sausage_arc_scores_ref
from repro.lattice_engine.common import (NEG, FBStats, LossStats, arc_scores,
                                         check_accumulators, data_constrainer,
                                         lattice_is_sausage)
from repro.losses.lattice import Lattice, lattice_frontiers


def _to_sausage(lat: Lattice, values, fill):
    """Gather (B, A) arc values into (B, S, W) via level_arcs."""
    return gather_sausage_ref(values, lat.level_arcs, fill)


def _from_sausage(lat: Lattice, values_sg, fill):
    """Scatter (B, S, W) values back to arc layout (B, A)."""
    A = lat.num_arcs
    flat_idx = lat.level_arcs.reshape(lat.level_arcs.shape[0], -1)
    flat_val = values_sg.reshape(values_sg.shape[0], -1)

    def per_utt(vals, idx):
        out = jnp.full((A + 1,), fill)
        safe = jnp.where(idx >= 0, idx, A)
        return out.at[safe].set(jnp.where(idx >= 0, vals, fill))[:A]

    return jax.vmap(per_utt)(flat_val, flat_idx)


def _sausage_mask(lat: Lattice):
    return gather_sausage_ref(lat.arc_mask.astype(jnp.float32),
                              lat.level_arcs, 0.0)


@jax.custom_jvp
def sausage_logz_cavg(scores_sg, corr_sg, mask_sg):
    """Differentiable (logZ, c_avg) on sausage-layout tensors (B, S, W)."""
    _, _, logz, cavg = sausage_forward(scores_sg, corr_sg, mask_sg)
    return logz, cavg


@sausage_logz_cavg.defjvp
def _sausage_logz_cavg_jvp(primals, tangents):
    scores_sg, corr_sg, mask_sg = primals
    ds, dc, _ = tangents                      # mask tangent is symbolically 0
    alpha, c_alpha, logz, cavg = sausage_forward(scores_sg, corr_sg, mask_sg)
    beta, c_beta = sausage_backward(scores_sg, corr_sg, mask_sg)
    gamma = jnp.where(mask_sg > 0.5,
                      jnp.exp(alpha + beta - logz[:, None, None]), 0.0)
    c_arc = c_alpha + c_beta
    ds = ds.astype(jnp.float32) if hasattr(ds, "astype") else 0.0
    dc = (dc.astype(jnp.float32)
          if hasattr(dc, "astype") and dc.dtype != jax.dtypes.float0 else None)
    dlogz = jnp.sum(gamma * ds, axis=(1, 2))
    dcavg = jnp.sum(gamma * (c_arc - cavg[:, None, None]) * ds, axis=(1, 2))
    if dc is not None:
        dcavg = dcavg + jnp.sum(gamma * dc, axis=(1, 2))
    return (logz, cavg), (dlogz, dcavg)


def _zero_if_symbolic(t):
    """None for float0 / missing tangents (int primals), else f32 view."""
    if t is None or not hasattr(t, "dtype") or t.dtype == jax.dtypes.float0:
        return None
    return t.astype(jnp.float32)


@jax.custom_jvp
def fused_sausage_loss_only(kappa, log_probs, start, end, label, lm, corr,
                            arc_mask, level_arcs):
    """Differentiable fused (logZ, c_avg) straight from (B, T, K)
    log-probs + ARC-LAYOUT lattice fields (B, A) and the (B, S, W)
    level_arcs gather map.  ``kappa`` is a regular primal (it is folded
    into the cumsum grid, so traced values work) with its own tangent.

    The primal is ONE forward-only Pallas kernel (nothing but the two
    (B,) outputs leaves it).  The tangent rule runs the kernel pair for
    gamma/c_arc — candidate evaluation never triggers it; gradient passes
    do, and they need the full statistics regardless.
    """
    return sausage_loss_only(log_probs, start, end, label, lm, corr,
                             arc_mask, level_arcs, kappa=kappa)


@fused_sausage_loss_only.defjvp
def _fused_sausage_loss_only_jvp(primals, tangents):
    kappa, log_probs, start, end, label, lm, corr, arc_mask, \
        level_arcs = primals
    dkappa, dlp, _, _, _, dlm, dcorr, _, _ = tangents  # int/bool tg are zero
    score_arc = sausage_arc_scores_ref(log_probs, start, end, label, kappa) \
        + lm.astype(jnp.float32)                                # (B, A)
    scores_sg = gather_sausage_ref(score_arc, level_arcs, NEG)
    corr_sg = gather_sausage_ref(corr.astype(jnp.float32), level_arcs, 0.0)
    mask_sg = gather_sausage_ref(arc_mask.astype(jnp.float32),
                                 level_arcs, 0.0)
    # score construction + the sausage gather are LINEAR in (log_probs,
    # lm, corr) and in kappa: the (log_probs, lm) tangents go through the
    # same map, and d score / d kappa is the acoustic part at kappa = 1
    dkappa = _zero_if_symbolic(dkappa)
    dlp = _zero_if_symbolic(dlp)
    dlm = _zero_if_symbolic(dlm)
    dcorr = _zero_if_symbolic(dcorr)
    ds_arc = None
    if dlp is not None:
        ds_arc = sausage_arc_scores_ref(dlp, start, end, label, kappa)
    if dkappa is not None:
        ac = dkappa * sausage_arc_scores_ref(log_probs, start, end,
                                             label, 1.0)
        ds_arc = ac if ds_arc is None else ds_arc + ac
    if dlm is not None:
        ds_arc = dlm if ds_arc is None else ds_arc + dlm
    ds_sg = jnp.zeros_like(scores_sg) if ds_arc is None else \
        gather_sausage_ref(ds_arc, level_arcs, 0.0)
    dc_sg = jnp.zeros_like(corr_sg) if dcorr is None else \
        gather_sausage_ref(dcorr, level_arcs, 0.0)
    # delegate to the full path's occupancy-identity rule — ONE place owns
    # the gamma/c_arc tangent math for both statistics modes
    return jax.jvp(sausage_logz_cavg, (scores_sg, corr_sg, mask_sg),
                   (ds_sg, dc_sg, jnp.zeros_like(mask_sg)))


# ---------------------------------------------------------------------------
# General-DAG path: the kernel pair over the levelized frontier tensors.
# Same custom_jvp structure as the sausage path — the occupancy identities
# are topology-independent; only the kernels (and the extra integer
# frontier inputs, which carry no tangents) differ.
# ---------------------------------------------------------------------------


def _dag_level_tensors(lat: Lattice, am):
    """Gather arc-layout values + frontier flags into the kernels'
    level-major layout.  ``am``: (B, A) acoustic+lm arc scores."""
    fr = lattice_frontiers(lat)
    own = gather_sausage_ref(am, lat.level_arcs, NEG)
    corr = gather_sausage_ref(lat.corr.astype(jnp.float32),
                              lat.level_arcs, 0.0)
    return (own, corr, fr.start.astype(jnp.float32),
            fr.ok.astype(jnp.float32), fr.final.astype(jnp.float32),
            fr.pidx, fr.sidx)


def _dag_occupancy_jvp(own, corr, start, ok, final, pidx, sidx, ds, dc):
    """(primal, tangent) of (logZ, c_avg) w.r.t. level-major (scores,
    corr) tangents (ds, dc) — the closed-form occupancy identities, with
    gamma/c_arc from one extra DAG kernel pair pass.  Shared by the full
    and fused loss-only custom_jvp rules so ONE place owns the math."""
    alpha, c_alpha, logz, cavg = dag_forward(own, corr, start, ok, final,
                                             pidx)
    beta, c_beta = dag_backward(own, corr, final, ok, sidx)
    gamma = jnp.where(ok > 0.5,
                      jnp.exp(alpha + beta - logz[:, None, None]), 0.0)
    c_arc = c_alpha + c_beta
    dlogz = jnp.zeros_like(logz)
    dcavg = jnp.zeros_like(cavg)
    if ds is not None:
        dlogz = jnp.sum(gamma * ds, axis=(1, 2))
        dcavg = jnp.sum(gamma * (c_arc - cavg[:, None, None]) * ds,
                        axis=(1, 2))
    if dc is not None:
        dcavg = dcavg + jnp.sum(gamma * dc, axis=(1, 2))
    return (logz, cavg), (dlogz, dcavg)


@jax.custom_jvp
def dag_logz_cavg(own, corr, start, ok, final, pidx, sidx):
    """Differentiable (logZ, c_avg) on level-major frontier tensors.
    ``sidx`` is unused by the primal (forward kernel only) but is a primal
    argument so the tangent rule can run the backward kernel."""
    _, _, logz, cavg = dag_forward(own, corr, start, ok, final, pidx)
    return logz, cavg


@dag_logz_cavg.defjvp
def _dag_logz_cavg_jvp(primals, tangents):
    own, corr, start, ok, final, pidx, sidx = primals
    ds, dc = tangents[0], tangents[1]   # flag/index tangents symbolically 0
    return _dag_occupancy_jvp(own, corr, start, ok, final, pidx, sidx,
                              _zero_if_symbolic(ds), _zero_if_symbolic(dc))


@jax.custom_jvp
def fused_dag_loss_only(kappa, log_probs, start, end, label, lm, corr,
                        arc_mask, is_start, is_final, level_arcs, pidx,
                        sidx):
    """Differentiable fused (logZ, c_avg) for general DAGs straight from
    (B, T, K) log-probs + arc-layout lattice fields + the frontier
    tensors — the DAG twin of :func:`fused_sausage_loss_only`.  ``sidx``
    rides along (unused by the primal) for the tangent rule's backward
    kernel."""
    return dag_loss_only(log_probs, start, end, label, lm, corr, arc_mask,
                         is_start, is_final, level_arcs, pidx, kappa=kappa)


@fused_dag_loss_only.defjvp
def _fused_dag_loss_only_jvp(primals, tangents):
    (kappa, log_probs, start, end, label, lm, corr, arc_mask, is_start,
     is_final, level_arcs, pidx, sidx) = primals
    dkappa, dlp, _, _, _, dlm, dcorr = tangents[:7]  # int/bool tg are zero
    score_arc = sausage_arc_scores_ref(log_probs, start, end, label, kappa) \
        + lm.astype(jnp.float32)                                # (B, A)
    own = gather_sausage_ref(score_arc, level_arcs, NEG)
    corr_lv = gather_sausage_ref(corr.astype(jnp.float32), level_arcs, 0.0)
    ok = gather_sausage_ref(arc_mask.astype(jnp.float32), level_arcs, 0.0)
    st = gather_sausage_ref(is_start.astype(jnp.float32), level_arcs,
                            0.0) * ok
    fin = gather_sausage_ref(is_final.astype(jnp.float32), level_arcs,
                             0.0) * ok
    # score construction + the level-major gather are LINEAR in
    # (log_probs, lm, corr) and in kappa — same tangent map as the fused
    # sausage rule
    dkappa = _zero_if_symbolic(dkappa)
    dlp = _zero_if_symbolic(dlp)
    dlm = _zero_if_symbolic(dlm)
    dcorr = _zero_if_symbolic(dcorr)
    ds_arc = None
    if dlp is not None:
        ds_arc = sausage_arc_scores_ref(dlp, start, end, label, kappa)
    if dkappa is not None:
        ac = dkappa * sausage_arc_scores_ref(log_probs, start, end,
                                             label, 1.0)
        ds_arc = ac if ds_arc is None else ds_arc + ac
    if dlm is not None:
        ds_arc = dlm if ds_arc is None else ds_arc + dlm
    ds = None if ds_arc is None else \
        gather_sausage_ref(ds_arc, level_arcs, 0.0)
    dc = None if dcorr is None else \
        gather_sausage_ref(dcorr, level_arcs, 0.0)
    return _dag_occupancy_jvp(own, corr_lv, st, ok, fin, pidx, sidx, ds, dc)


def _loss_only_dag_pallas(lat: Lattice, log_probs: jnp.ndarray,
                          kappa: float, constrain) -> LossStats:
    """Fused DAG candidate-evaluation path: raw arc-layout lattice fields
    + frontier tensors in, (logZ, c_avg) out."""
    fr = lattice_frontiers(lat)
    c = constrain
    logZ, c_avg = fused_dag_loss_only(
        kappa, c(log_probs.astype(jnp.float32)),
        lat.start_t, lat.end_t, lat.label, lat.lm, lat.corr,
        lat.arc_mask, lat.is_start, lat.is_final, lat.level_arcs,
        fr.pidx, fr.sidx)
    return LossStats(logZ=logZ, c_avg=c_avg)


def _forward_backward_dag_pallas(lat: Lattice, log_probs: jnp.ndarray,
                                 kappa: float, constrain,
                                 accumulators: str) -> FBStats | LossStats:
    """General-DAG statistics via the frontier kernels (see module
    docstring): the full path mirrors the sausage one — differentiable
    (logZ, c_avg) through ``dag_logz_cavg``, per-arc statistics as
    constants scattered back to arc layout."""
    c = constrain
    if accumulators == "loss_only":
        return _loss_only_dag_pallas(lat, log_probs, kappa, c)
    am = c(arc_scores(lat, log_probs, kappa) + lat.lm)         # (B, A)
    own, corr_lv, start_lv, ok_lv, final_lv, pidx, sidx = \
        _dag_level_tensors(lat, am)
    own = c(own)

    logZ, c_avg = dag_logz_cavg(own, corr_lv, start_lv, ok_lv, final_lv,
                                pidx, sidx)

    # constant (non-differentiable) per-arc statistics; DCE'd when unused
    sg_own, sg_corr = jax.lax.stop_gradient((own, corr_lv))
    alpha_lv, c_alpha_lv, logz_c, cavg_c = dag_forward(
        sg_own, sg_corr, start_lv, ok_lv, final_lv, pidx)
    beta_lv, c_beta_lv = dag_backward(sg_own, sg_corr, final_lv, ok_lv,
                                      sidx)
    gamma_lv = jnp.where(ok_lv > 0.5,
                         jnp.exp(alpha_lv + beta_lv
                                 - logz_c[:, None, None]), 0.0)
    alpha = c(_from_sausage(lat, alpha_lv, NEG))
    beta = c(_from_sausage(lat, beta_lv, NEG))
    c_alpha = c(_from_sausage(lat, c_alpha_lv, 0.0))
    c_beta = c(_from_sausage(lat, c_beta_lv, 0.0))
    gamma = c(_from_sausage(lat, gamma_lv, 0.0))
    return FBStats(alpha=alpha, beta=beta, logZ=logZ, gamma=gamma,
                   c_alpha=c_alpha, c_beta=c_beta, c_avg=c_avg,
                   c_arc=c_alpha + c_beta)


def _loss_only_pallas(lat: Lattice, log_probs: jnp.ndarray, kappa: float,
                      constrain) -> LossStats:
    """The candidate-evaluation path: raw arc-layout lattice fields in,
    (logZ, c_avg) out — no per-arc statistics, no backward kernel
    anywhere in the graph."""
    c = constrain
    logZ, c_avg = fused_sausage_loss_only(
        kappa, c(log_probs.astype(jnp.float32)),
        lat.start_t, lat.end_t, lat.label, lat.lm, lat.corr,
        lat.arc_mask, lat.level_arcs)
    return LossStats(logZ=logZ, c_avg=c_avg)


def forward_backward_pallas(lat: Lattice, log_probs: jnp.ndarray,
                            kappa: float, mesh=None,
                            accumulators: str = "full"
                            ) -> FBStats | LossStats:
    """Lattice statistics via the Pallas kernels — ANY topology.

    Statically-known sausage lattices run the specialised fully-connected
    segment kernels; everything else (general DAGs, and ANY lattice whose
    arrays are traced so topology cannot be inspected) runs the
    general-DAG frontier kernels — both pure Pallas, never a scan
    fallback.  ``accumulators="full"`` runs the forward/backward kernel
    pair and returns the complete ``FBStats``; only ``logZ`` and
    ``c_avg`` carry gradients (see module docstring) — the per-arc fields
    are statistics-as-constants.  ``accumulators="loss_only"`` runs the
    fused forward-only kernel and returns ``LossStats``.
    """
    check_accumulators(accumulators)
    if lat.level_arcs is None:
        raise ValueError(
            "pallas backend needs Lattice.level_arcs; build batches with "
            "repro.losses.lattice.batch_lattices (levelizes automatically)")
    c = data_constrainer(mesh)
    # topology dispatch: the sausage kernels assume full inter-level
    # connectivity + last-level finals; the DAG kernels handle everything
    # (sausages included) via the frontier tensors
    if isinstance(lat.level_arcs, jax.core.Tracer) \
            or not lattice_is_sausage(lat):
        return _forward_backward_dag_pallas(lat, log_probs, kappa, c,
                                            accumulators)
    if accumulators == "loss_only":
        return _loss_only_pallas(lat, log_probs, kappa, c)
    am = c(arc_scores(lat, log_probs, kappa) + lat.lm)         # (B, A)
    scores_sg = c(_to_sausage(lat, am, NEG))
    corr_sg = _to_sausage(lat, lat.corr, 0.0)
    mask_sg = _sausage_mask(lat)

    logZ, c_avg = sausage_logz_cavg(scores_sg, corr_sg, mask_sg)

    # constant (non-differentiable) per-arc statistics; DCE'd when unused
    sg = jax.lax.stop_gradient((scores_sg, corr_sg))
    alpha_sg, c_alpha_sg, logz_c, cavg_c = sausage_forward(*sg, mask_sg)
    beta_sg, c_beta_sg = sausage_backward(*sg, mask_sg)
    gamma_sg = jnp.where(mask_sg > 0.5,
                         jnp.exp(alpha_sg + beta_sg - logz_c[:, None, None]),
                         0.0)
    alpha = c(_from_sausage(lat, alpha_sg, NEG))
    beta = c(_from_sausage(lat, beta_sg, NEG))
    c_alpha = c(_from_sausage(lat, c_alpha_sg, 0.0))
    c_beta = c(_from_sausage(lat, c_beta_sg, 0.0))
    gamma = c(_from_sausage(lat, gamma_sg, 0.0))
    return FBStats(alpha=alpha, beta=beta, logZ=logZ, gamma=gamma,
                   c_alpha=c_alpha, c_beta=c_beta, c_avg=c_avg,
                   c_arc=c_alpha + c_beta)
