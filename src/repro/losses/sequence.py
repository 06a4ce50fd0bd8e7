"""Loss specifications with matched curvature factors.

A ``LossSpec`` packages everything NGHF needs from a training criterion
(paper Secs. 3.2, 3.4, 5.2):

    value(logits, batch, accumulators="full") -> (scalar loss, metrics)
    logit_grad(logits, batch)     -> G = dL/dlogits            (B,T,K)
    gn_vp(logits, batch, u)       -> per-frame GN factor product  H^ u
    fisher_vp(logits, batch, u)   -> per-frame empirical-Fisher product F^ u

The lattice losses also give the prepared form

    curvature_factors(logits, batch) -> (u -> H^ u, u -> F^ u)

which runs the lattice gradients G once at ``logits`` and closes over
them, so that applying a factor is elementwise work over (B,T,K).
``core.curvature.make_curvature_ops`` builds it once per CG stage (the
parameters and the CG batch are fixed there, only u changes); their
``gn_vp``/``fisher_vp`` are thin wrappers over it.  A spec without it
(CE, the chunked LM loss: no lattice recursion to save) is called
per product.

``value``'s ``accumulators`` selects the lattice-engine statistics mode:
``"loss_only"`` computes only what the loss value needs (no backward
recursion; on the Pallas backend one fused forward kernel) — this is what
CG candidate evaluation runs per iteration (``CurvatureOps.eval_loss``,
``SecondOrderConfig.eval_accumulators``).  Non-lattice losses accept and
ignore it.

Normalisation convention: ``value`` is a batch *mean*; both curvature
factors are normalised the same way (mean over loss atoms), so
``B Δθ = -∇L`` is scale-consistent and the CG λ/damping hyper-parameters
have a stable meaning across batch sizes.

Matrix-free identities used (never materialising K x K blocks):
  CE / matching loss :  H^u = w (p ⊙ u - p (pᵀu)),   ĝ = w (p - y)
  MPE (Eqn. 11)      :  H^u = κ² w (γ ⊙ u) + κ G (γᵀu),  γ = ML occupancy
  MMI Fisher (Eq.19) :  F^u = S · G_mmi (G_mmiᵀ u)  per frame, S = #atoms

The MPE form follows the paper's Hadamard-product formulation in Sec. 3.4
(the diag term uses the ML occupancy γ_t; the rank-1 term uses γ_t^MBR via
G = -κ w γ^MBR).  For lattice training the Fisher always comes from the MMI
loss regardless of the training loss (Sec. 5.2) — that is what makes NGHF
an MPE/MMI interpolation.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.lattice_engine import lattice_stats
from repro.losses.lattice import (Lattice, lattice_frame_counts,
                                  lattice_frame_mask)


# ---------------------------------------------------------------------------
# Cross entropy (LM next-token or frame-level hybrid pretraining)
# ---------------------------------------------------------------------------

class CELoss:
    """Mean token/frame CE.  batch["labels"]: (B,T) int32; optional
    batch["label_mask"]: (B,T).  For LM training the caller passes labels
    already shifted (labels[t] = tokens[t+1])."""

    name = "ce"

    def _mask(self, logits, batch):
        m = batch.get("label_mask")
        if m is None:
            m = jnp.ones(logits.shape[:2], jnp.float32)
        return m.astype(jnp.float32)

    def value(self, logits, batch,
              accumulators: str = "full") -> Tuple[jnp.ndarray, Dict]:
        # ``accumulators`` is part of the LossSpec interface (lattice
        # losses have a cheap loss-only statistics mode); CE has nothing
        # to elide.
        labels = batch["labels"]
        m = self._mask(logits, batch)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(lp, labels[..., None], -1)[..., 0]
        denom = jnp.maximum(m.sum(), 1.0)
        loss = jnp.sum(nll * m) / denom
        acc = jnp.sum((jnp.argmax(logits, -1) == labels) * m) / denom
        return loss, {"ce": loss, "acc": acc}

    def logit_grad(self, logits, batch):
        labels = batch["labels"]
        m = self._mask(logits, batch)
        p = jax.nn.softmax(logits.astype(jnp.float32), -1)
        y = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
        w = m / jnp.maximum(m.sum(), 1.0)
        return (p - y) * w[..., None]

    def gn_vp(self, logits, batch, u):
        m = self._mask(logits, batch)
        p = jax.nn.softmax(logits.astype(jnp.float32), -1)
        w = m / jnp.maximum(m.sum(), 1.0)
        pu = jnp.sum(p * u, -1, keepdims=True)
        return w[..., None] * (p * u - p * pu)

    def fisher_vp(self, logits, batch, u):
        g = self.logit_grad(logits, batch)
        S = jnp.maximum(self._mask(logits, batch).sum(), 1.0)
        gu = jnp.sum(g * u, -1, keepdims=True)
        return S * g * gu


# ---------------------------------------------------------------------------
# Prepared curvature factors of the lattice losses
# ---------------------------------------------------------------------------

def _matching_apply(lat: Lattice, g, diag_w, rank1_w):
    """u -> diag_w (y ⊙ u) + rank1_w G (yᵀu), y the one-hot reference
    states.  Edge-padded frames are masked out of y: the loss has zero
    dependence on them, so the curvature must not add PSD mass there
    (padding-dependent GN shifts the CG direction)."""
    mask = lattice_frame_mask(lat)[..., None]

    def apply(u):
        y = jax.nn.one_hot(lat.ref_states, u.shape[-1],
                           dtype=jnp.float32) * mask
        yu = jnp.sum(y * u, -1, keepdims=True)
        return diag_w * (y * u) + rank1_w * g * yu

    return apply


def _fisher_apply(g, S):
    """u -> S G (Gᵀu) per frame (Eq. 19), S the number of loss atoms."""
    def apply(u):
        gu = jnp.sum(g * u, -1, keepdims=True)
        return S * g * gu

    return apply


# ---------------------------------------------------------------------------
# Lattice MMI (Eqn. 2)
# ---------------------------------------------------------------------------

class MMILoss:
    """L = -(1/Σ_b T_b) Σ_b (num_score_b - logZ_den_b), with T_b the REAL
    per-utterance frame count (``lattice_frame_counts``).

    batch["lattice"]: Lattice.  The numerator is the reference state
    alignment (its LM score is a constant w.r.t. θ and is dropped);
    edge-padded ``ref_states`` frames past the last arc are masked out of
    the numerator and excluded from the normaliser, so neither the loss
    value nor its scale (and hence the meaning of λ/damping) depends on
    how far the batch was padded.

    ``backend`` selects the lattice-engine statistics backend ("auto"
    dispatches: Pallas sausage kernels on TPU, levelized scan elsewhere).
    ``mesh`` (optional jax.sharding.Mesh) keeps the engine's (B, A) arc
    tensors constrained to the data axes under pjit."""

    name = "mmi"

    def __init__(self, kappa: float = 1.0, backend: str = "auto", mesh=None):
        self.kappa = kappa
        self.backend = backend
        self.mesh = mesh

    def _frames(self, lat: Lattice):
        """Total real frame count (the loss-atom count S of Eq. 19)."""
        return jnp.maximum(jnp.sum(lattice_frame_counts(lat)), 1.0)

    def _parts(self, logits, lat: Lattice, accumulators: str = "full"):
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        ref_lp = jnp.take_along_axis(
            lp, lat.ref_states[..., None], -1)[..., 0]              # (B, T)
        num = self.kappa * jnp.sum(ref_lp * lattice_frame_mask(lat), -1)
        stats = lattice_stats(lat, lp, self.kappa, backend=self.backend,
                              mesh=self.mesh, accumulators=accumulators)
        return num, stats

    def value(self, logits, batch, accumulators: str = "full"):
        lat: Lattice = batch["lattice"]
        num, stats = self._parts(logits, lat, accumulators)
        loss = -jnp.sum(num - stats.logZ) / self._frames(lat)
        return loss, {"mmi": loss, "logZ": stats.logZ.mean()}

    def logit_grad(self, logits, batch):
        return jax.grad(lambda lg: self.value(lg, batch)[0])(
            logits.astype(jnp.float32))

    def curvature_factors(self, logits, batch):
        """(u -> H^u, u -> F^u) at ``logits``, both from one G_mmi.

        GN: the MMI matching-loss factor, matrix-free via the denominator
        occupancy: H^u ≈ κ²w(γ_den ⊙ u - γ_den(γ_denᵀu)) computed with two
        VJP-free softmax-style contractions on the ML occupancy is not
        available in closed form here, so we use the exact Gauss-Newton of
        the *numerator* matching part plus the rank-1 denominator term
        derived from logit_grad (same structure as the MPE factor).
        Fisher: Eq. 19."""
        lat: Lattice = batch["lattice"]
        g = self.logit_grad(logits, batch)
        S = self._frames(lat)
        return (_matching_apply(lat, g, self.kappa ** 2 / S, self.kappa),
                _fisher_apply(g, S))

    def gn_vp(self, logits, batch, u):
        return self.curvature_factors(logits, batch)[0](u)

    def fisher_vp(self, logits, batch, u):
        return self.curvature_factors(logits, batch)[1](u)


# ---------------------------------------------------------------------------
# Lattice MPE / MBR (Eqn. 3, risk = phone correctness)
# ---------------------------------------------------------------------------

class MPELoss:
    """L = -(1/B) Σ_b c_avg_b / n_ref_units_b  (negative expected phone
    accuracy).  ``metrics["mpe_acc"]`` is the paper's "MPE Acc"."""

    name = "mpe"

    def __init__(self, kappa: float = 1.0, backend: str = "auto", mesh=None):
        self.kappa = kappa
        self.backend = backend
        self.mesh = mesh
        self._mmi = MMILoss(kappa, backend=backend, mesh=mesh)

    def value(self, logits, batch, accumulators: str = "full"):
        lat: Lattice = batch["lattice"]
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        stats = lattice_stats(lat, lp, self.kappa, backend=self.backend,
                              mesh=self.mesh, accumulators=accumulators)
        acc = stats.c_avg / jnp.maximum(lat.num_ref_units, 1.0)
        loss = -jnp.mean(acc)
        return loss, {"mpe_acc": jnp.mean(acc), "logZ": stats.logZ.mean()}

    def logit_grad(self, logits, batch):
        return jax.grad(lambda lg: self.value(lg, batch)[0])(
            logits.astype(jnp.float32))

    def _gn_factor(self, logits, batch):
        """u -> H^u, Eqn. 11 via the Sec. 3.4 Hadamard form:
        H^u = κ² w (γ_ml ⊙ u) + κ G (γ_mlᵀ u), G = -κ w γ^MBR."""
        lat: Lattice = batch["lattice"]
        B = logits.shape[0]
        w = (1.0 / (B * jnp.maximum(lat.num_ref_units, 1.0)))[:, None, None]
        return _matching_apply(lat, self.logit_grad(logits, batch),
                               (self.kappa ** 2) * w, self.kappa)

    def curvature_factors(self, logits, batch):
        """(u -> H^u, u -> F^u) at ``logits``: G_mpe and G_mmi once each.
        The Fisher comes from the *MMI* loss (Sec. 5.2), regardless of
        training criterion — NGHF's MPE/MMI interpolation."""
        return (self._gn_factor(logits, batch),
                self._mmi.curvature_factors(logits, batch)[1])

    def gn_vp(self, logits, batch, u):
        return self._gn_factor(logits, batch)(u)

    def fisher_vp(self, logits, batch, u):
        return self._mmi.fisher_vp(logits, batch, u)


def get_loss(name: str, kappa: float = 1.0, backend: str = "auto",
             mesh=None):
    if name == "ce":
        return CELoss()
    if name == "mmi":
        return MMILoss(kappa, backend=backend, mesh=mesh)
    if name == "mpe":
        return MPELoss(kappa, backend=backend, mesh=mesh)
    raise ValueError(name)
