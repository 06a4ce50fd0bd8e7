"""The two-stage distributed second-order optimisers (paper Secs. 4-6) on
the unified stateful protocol.

One **update** = gradient-accumulation stage (large gradient batch) + CG
stage (small CG batch), exactly Fig. 1:

  NG   (Sec. 5):  solve   λ F Δθ = -∇L          with CG on Fisher products
  HF   (Sec. 3):  solve     G Δθ = -∇L          with CG on GN products
  NGHF (Sec. 6):  solve     G Δθ = -F⁻¹∇L       — the outer CG is
                  *initialised with the NG direction* as its RHS, so the
                  returned update is a weighted combination of the NG
                  direction and GN-conjugate directions (Eqn. 22).

Everything happens inside ONE jitted ``step``: under pjit the gradient
batch / CG batch means become GSPMD all-reduces across the (pod, data)
mesh axes — the master/worker accumulation of the paper at pod scale.

What statefulness adds over the historical stateless update (and what the
state slots mean — they are documented API):

  "step"    int32 — completed updates.
  "lam"     f32   — live λ when ``adapt_lam``: Levenberg–Marquardt-style
            adaptation from the quadratic-model reduction ratio
            ρ = (L(θ) - L(θ+Δθ)) / (-q(Δθ)) on the CG batch (Martens
            2010): ρ > 3/4 relaxes λ by ``lam_dec``, ρ < 1/4 tightens by
            ``lam_inc``, clipped to [lam_min, lam_max].  λ multiplies the
            Fisher for ng/nghf and acts as Tikhonov damping for hf.
  "delta"   θ-like (iff ``warm_start``) — the previous best Δθ; the outer
            CG starts from it instead of 0 (Martens-style HF warm start;
            costs one extra curvature product to form the true residual).
  "precond" preconditioner state — running empirical-Fisher diagonal for
            ``preconditioner="fisher_diag"``, {} for the stateless
            ``share_counts`` (Sec. 4.3, default) and ``identity``.

With ``warm_start=False``, ``adapt_lam=False`` and the (default)
``share_counts`` preconditioner, ``step`` reproduces the pre-protocol
``second_order_update`` bit-for-bit — the historical entry points in
``repro.core.nghf`` are thin shims over this class and the regression
tests run through them unchanged.
"""
from __future__ import annotations

import dataclasses
import logging
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import tree_math as tm
from repro.core.cg import cg_solve
from repro.core.curvature import grad_and_loss, make_curvature_ops
from repro.core.optim.base import Optimizer, register_optimizer
from repro.core.optim.preconditioners import get_preconditioner

logger = logging.getLogger(__name__)


def _mesh_data_extent(state_sharding) -> int:
    """Data-parallel extent of the storage mesh (1 when unsharded).

    Read off the first NamedSharding leaf; the ("pod", "data") axis
    convention is the same single definition ``launch.sharding.
    data_extent`` uses (kept inline here so core/ stays launch-free)."""
    if state_sharding is None:
        return 1
    for s in jax.tree.leaves(
            state_sharding,
            is_leaf=lambda x: isinstance(x, jax.sharding.Sharding)):
        mesh = getattr(s, "mesh", None)
        if mesh is not None:
            size = 1
            for a in ("pod", "data"):
                if a in mesh.axis_names:
                    size *= int(mesh.shape[a])
            return size
    return 1


@dataclass(frozen=True)
class SecondOrderConfig:
    method: str = "nghf"          # ng | hf | nghf
    cg_iters: int = 8             # outer CG iterations (paper: 5-8); with
                                  # cg_tol > 0 this is the CEILING of the
                                  # adaptive budget
    ng_iters: int = 4             # inner Fisher-CG iterations for NGHF
    cg_tol: float = 0.0           # adaptive CG budget: stop once the
                                  # quadratic model's relative per-
                                  # iteration gain drops below this
                                  # (Martens 2010); 0 keeps the fixed
                                  # budget bit-for-bit.  Applies to the
                                  # outer solve AND the inner NG solve.
    cg_min_iters: int = 1         # floor before cg_tol may fire
    cg_fused: bool = False        # fused CG vector work (one pass for
                                  # x+=αv, r-=αBv, <r,r>): the flat-
                                  # buffer kernel (kernels/cg_fused.py)
                                  # on a single chip, the sharded per-
                                  # leaf variant (cg_fused_update_tree)
                                  # under a mesh (state_sharding), where
                                  # carries keep their per-leaf 2d
                                  # sharding and rr reduces cross-shard
    curvature_sample: float = 1.0  # fraction of the CG batch used for the
                                  # GN/Fisher products (Sainath-style
                                  # sampling); candidate evaluation always
                                  # keeps the FULL CG batch.  1.0 is
                                  # bit-identical to the unsampled path.
    lam: float = 1.0              # λ, KL trust multiplier on F (Eqn. 17)
    damping: float = 0.0          # Tikhonov η (baseline; paper avoids it)
    ng_damping: float = 1.0       # inner-Fisher-solve damping for NGHF: the
                                  # empirical Fisher is rank-deficient, so an
                                  # undamped 3-4 iteration CG inversion blows
                                  # up along near-null directions (|d| 130x
                                  # |g| measured) and every outer candidate
                                  # loses to Δθ=0.  Same role as TRPO's CG
                                  # damping; the mean-normalised F makes 1.0
                                  # a stable default.
    stabilize: bool = True        # Sec. 4.2 ‖θ‖/‖v‖ rescaling
    precondition: bool = True     # master switch; False forces "identity"
    preconditioner: str = "share_counts"
                                  # identity | share_counts (Sec. 4.3,
                                  # default) | fisher_diag (running
                                  # empirical-Fisher diagonal, Sainath-
                                  # style implicit preconditioning)
    fisher_decay: float = 0.95    # fisher_diag EMA decay
    fisher_eps: float = 1e-4      # fisher_diag damping ε
    fisher_power: float = 0.75    # fisher_diag exponent α
    eval_candidates: bool = True  # Alg. 1 candidate selection
    reject_worse: bool = True     # keep θ when no candidate beats Δθ=0
    eval_every: int = 1           # candidate-eval stride (the final CG
                                  # iterate is always evaluated)
    eval_accumulators: str = "loss_only"
                                  # statistics mode for the per-CG-iteration
                                  # candidate evaluation (Alg. 1 — ~73 % of
                                  # CG wall time in paper Table 1):
                                  # "loss_only" computes just (logZ, c_avg)
                                  # — no backward recursion; one fused
                                  # forward kernel on the Pallas backend —
                                  # while the gradient/curvature stages
                                  # keep full statistics.  "full" restores
                                  # the complete FBStats evaluation.
    warm_start: bool = False      # start the outer CG from the previous Δθ
    adapt_lam: bool = False       # LM-style λ adaptation (needs
                                  # eval_candidates for the CG-batch loss)
    lam_inc: float = 1.5          # ρ < 1/4  =>  λ *= lam_inc
    lam_dec: float = 2.0 / 3.0    # ρ > 3/4  =>  λ *= lam_dec
    lam_min: float = 1e-3
    lam_max: float = 1e3
    step_scale: float = 1.0       # trust-region style final scaling
    curvature_mode: str = "rematvp"   # rematvp | linearize (see curvature.py)
    grad_microbatches: int = 1        # sequential grad accumulation (memory)
    state_dtype: str = "float32"      # CG vector storage; "bfloat16" halves
                                      # θ-state memory (the Sec. 4.2 rescaling
                                      # is what keeps bf16 products usable)

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


class SecondOrderOptimizer(Optimizer):
    """NG / HF / NGHF as a thin stateful orchestration over
    ``grad_and_loss`` + ``make_curvature_ops`` + ``cg_solve``."""

    uses_cg_batch = True

    def __init__(self, cfg: SecondOrderConfig, forward_fn, loss_spec, *,
                 share_counts=None, state_sharding=None):
        if cfg.method not in ("ng", "hf", "nghf"):
            raise ValueError(cfg.method)
        if cfg.adapt_lam and not cfg.eval_candidates:
            # the reduction ratio needs the CG-batch candidate losses;
            # without them λ would silently stay frozen at cfg.lam
            raise ValueError("adapt_lam requires eval_candidates=True "
                             "(ρ is measured on the CG-batch losses)")
        self.cfg = cfg
        self.name = cfg.method
        self.forward_fn = forward_fn
        self.loss_spec = loss_spec
        self.state_sharding = state_sharding
        # He-style worker split of the curvature batch: GN/Fisher products
        # keep the CG batch evenly divisible over the data axes so every
        # product is per-shard work + ONE all-reduce
        self.data_extent = _mesh_data_extent(state_sharding)
        if cfg.cg_fused and state_sharding is not None:
            logger.info(
                "%s: cg_fused under a mesh — using the sharded per-leaf "
                "fused path (cg_fused_update_tree); the flat-buffer Pallas "
                "kernel needs an unsharded ravel", cfg.method)
        pname = cfg.preconditioner if cfg.precondition else "identity"
        self.precond = get_preconditioner(
            pname, share_counts=share_counts, fisher_decay=cfg.fisher_decay,
            fisher_eps=cfg.fisher_eps, fisher_power=cfg.fisher_power)

    # -- state ---------------------------------------------------------------
    def _delta_dtype(self, leaf):
        return (self.cfg.state_dtype if self.cfg.state_dtype != "float32"
                else leaf.dtype)

    def state_template(self, theta, scalar):
        # ``init``/``state_shardings`` both derive from this (base class),
        # so structure, dtypes and sharding cannot drift
        st = {"step": scalar(jnp.int32, 0),
              "lam": scalar(jnp.float32, self.cfg.lam),
              "precond": self.precond.state_template(theta, scalar)}
        if self.cfg.warm_start:
            # Δθ is stored in the CG state dtype (bf16 state halves θ-state
            # memory; it re-enters the solve as x0)
            st["delta"] = theta(cast=self._delta_dtype)
        return st

    # -- the update ----------------------------------------------------------
    def step(self, params, state, grad_batch, cg_batch=None):
        cfg = self.cfg
        if cg_batch is None:
            raise ValueError(f"{self.name} needs an explicit CG batch "
                             "(paper Sec. 4.1)")
        ss = self.state_sharding

        def _c(t):
            """Constrain θ-sized vectors to the storage sharding: second-
            order state inherits the 2d STORAGE sharding rather than the 1d
            compute sharding the vjp cotangents carry (6 GiB/dev difference
            on qwen2.5-3b)."""
            if ss is None:
                return t
            return jax.tree.map(jax.lax.with_sharding_constraint, t, ss)

        # --- stage 1: gradient accumulation (Fig. 1, left) ------------------
        loss, metrics, grads = grad_and_loss(
            self.forward_fn, self.loss_spec, params, grad_batch,
            microbatches=cfg.grad_microbatches, constrain=_c)
        grads = _c(grads)
        # θ-sized preconditioner state (fisher_diag's EMA) must mirror
        # state_shardings: the constrainer pins it to the 2d storage
        # sharding instead of letting it replicate at the jit boundary
        pstate = self.precond.update(state["precond"], grads, constrain=_c)
        b = tm.scale(grads, -1.0)
        if cfg.state_dtype != "float32":
            b = jax.tree.map(lambda x: x.astype(cfg.state_dtype), b)

        # --- stage 2: CG (Fig. 1, right) -------------------------------------
        theta_norm = tm.norm(params)
        ops = make_curvature_ops(self.forward_fn, self.loss_spec, params,
                                 cg_batch, stabilize=cfg.stabilize,
                                 theta_norm=theta_norm,
                                 mode=cfg.curvature_mode,
                                 eval_accumulators=cfg.eval_accumulators,
                                 curvature_sample=cfg.curvature_sample,
                                 data_extent=self.data_extent)
        precond = self.precond.apply_fn(pstate)
        lam = state["lam"] if cfg.adapt_lam else cfg.lam
        # fused vector work survives the mesh: with ``constrain`` set,
        # cg_solve dispatches the sharded per-leaf fused path (carries
        # stay pytrees, rr is an exact cross-shard reduction) instead of
        # the single-chip flat-buffer kernel
        solve_kw = dict(tol=cfg.cg_tol, min_iters=cfg.cg_min_iters,
                        fused=cfg.cg_fused)

        def _st(t):
            """Match the CG state storage dtype (bf16 state keeps scan
            carries homogeneous; reductions inside tm.* stay f32)."""
            if cfg.state_dtype == "float32":
                return t
            return jax.tree.map(lambda x: x.astype(cfg.state_dtype), t)

        fvp = lambda v: _st(_c(tm.scale(ops.fvp(v), lam)))     # noqa: E731
        if cfg.method == "hf" and cfg.adapt_lam:
            # for plain HF the adaptive λ acts as LM Tikhonov damping
            # (G + λI); added here because cg_solve's ``damping`` must stay
            # a static float
            gnvp = lambda v: _st(_c(tm.axpy(lam, v, ops.gnvp(v))))  # noqa
        else:
            gnvp = lambda v: _st(_c(ops.gnvp(v)))                   # noqa
        constrain = _c if ss is not None else None
        x0 = state["delta"] if cfg.warm_start else None

        diag = {}
        if cfg.method == "ng":
            res = cg_solve(fvp, b,
                           iters=cfg.cg_iters, precond=precond,
                           eval_fn=ops.eval_loss if cfg.eval_candidates
                           else None,
                           damping=cfg.damping, eval_every=cfg.eval_every,
                           constrain=constrain, x0=x0, **solve_kw)
        elif cfg.method == "hf":
            res = cg_solve(gnvp, b,
                           iters=cfg.cg_iters, precond=precond,
                           eval_fn=ops.eval_loss if cfg.eval_candidates
                           else None,
                           damping=cfg.damping, eval_every=cfg.eval_every,
                           constrain=constrain, x0=x0, **solve_kw)
        else:
            # inner solve: (λF + ηI) d = -∇L  (NG direction, no candidate
            # eval — it only forms the RHS of the regulated problem,
            # Eqn. 20/21)
            inner = cg_solve(fvp, b,
                             iters=cfg.ng_iters, precond=precond,
                             eval_fn=None,
                             damping=max(cfg.damping, cfg.ng_damping),
                             constrain=constrain, **solve_kw)
            ng_dir = inner.x
            diag["ng_quad"] = inner.quad
            diag["ng_iters_used"] = inner.iters_used
            # outer solve: G Δθ = NG direction  (Sec. 6.2)
            res = cg_solve(gnvp, ng_dir,
                           iters=cfg.cg_iters, precond=precond,
                           eval_fn=ops.eval_loss if cfg.eval_candidates
                           else None,
                           damping=cfg.damping, eval_every=cfg.eval_every,
                           constrain=constrain, x0=x0, **solve_kw)

        delta = tm.scale(res.x, cfg.step_scale)
        accepted = jnp.asarray(True)
        base = None
        cg_evals = res.evals
        if cfg.eval_candidates and (cfg.reject_worse or cfg.adapt_lam):
            base = ops.eval_loss(tm.zeros_like(res.x))
            cg_evals = cg_evals + 1          # the zero update's evaluation
        if cfg.eval_candidates and cfg.reject_worse:
            # Alg. 1 returns the best candidate by CG-batch loss;
            # additionally reject it if it does not beat the zero update
            # (guards the first few updates where the quadratic model is
            # untrustworthy).
            accepted = res.best_loss < base
            delta = tm.where(accepted, delta, tm.zeros_like(delta))
        new_params = tm.add(params, tm.cast_like(delta, params))

        new_state = dict(state, step=state["step"] + 1, precond=pstate)
        if cfg.adapt_lam:
            # LM reduction ratio on the CG batch against the LOSS quadratic
            # model q(Δ) = -bᵀΔ + ½ΔᵀBΔ, b = -∇L.  For ng/hf the CG solve's
            # own quadratic IS that model (its RHS is b), so the selected
            # iterate's history entry is free; for nghf the outer solve's
            # RHS is the NG direction — its quadratic is measured against
            # the wrong linear term — so form the model explicitly with one
            # extra curvature product at the selected candidate.
            if cfg.method == "nghf":
                pred = (tm.vdot(res.x, b)
                        - 0.5 * tm.vdot(res.x, gnvp(res.x)))
            else:
                pred = -jnp.take(res.quad, jnp.maximum(res.best_iter, 0))
            actual = base - res.best_loss
            rho = actual / jnp.maximum(pred, 1e-30)
            valid = (jnp.isfinite(rho) & (pred > 1e-30)
                     & (res.best_iter >= 0))
            adj = (jnp.where(rho > 0.75, cfg.lam_dec, 1.0)
                   * jnp.where(rho < 0.25, cfg.lam_inc, 1.0))
            new_state["lam"] = jnp.clip(
                jnp.where(valid, state["lam"] * adj, state["lam"]),
                cfg.lam_min, cfg.lam_max)
            diag["cg_rho"] = rho
            diag["lam"] = lam
        if cfg.warm_start:
            # the NEXT solve starts from this update's best candidate —
            # stored even when rejected (the same system roughly recurs)
            new_state["delta"] = _c(_st(res.x))

        metrics = dict(metrics)
        metrics.update(
            loss=loss, grad_norm=tm.norm(grads), update_norm=tm.norm(delta),
            cg_best_iter=res.best_iter, cg_best_loss=res.best_loss,
            cg_quad=res.quad, cg_resid=res.resid, cg_curv=res.curv,
            cg_losses=res.losses, cg_accepted=accepted,
            cg_iters_used=res.iters_used, cg_evals=cg_evals,
            opt_step=new_state["step"], **diag)
        return new_params, new_state, metrics


for _m in ("ng", "hf", "nghf"):
    register_optimizer(_m, SecondOrderConfig, SecondOrderOptimizer,
                       method=_m)
