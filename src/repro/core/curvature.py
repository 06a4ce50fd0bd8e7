"""Curvature-matrix-vector products (paper Secs. 3.4 and 5.2).

The Gauss-Newton product  G v = Jᵀ (H^ (J v))  and the empirical-Fisher
product  F v = Jᵀ (F^ (J v))  are computed matrix-free:

  * ``J v`` — the directional derivative / Pearlmutter R-operator — is a
    single ``jax.linearize`` JVP through the model (the modified forward
    propagation of Eqn. 12; the LSTM gating rule Eqn. 13 is what JVP does
    for Hadamard products automatically).
  * ``H^ ·`` / ``F^ ·`` are the per-frame logit-space factors supplied by
    the LossSpec (Eqns. 11 and 19) — never materialised as K x K blocks.
  * ``Jᵀ u`` — EBP with a substituted output cotangent — is the transpose
    of the linearized JVP (``jax.linear_transpose``), reusing the stored
    forward residuals.

``linearize`` is called ONCE per CG stage (the parameters and CG batch are
fixed across CG iterations), so each CG iteration costs one JVP + one
transposed JVP + (optionally) one candidate-evaluation forward — matching
the cost profile in paper Table 1.

Numerical stability (paper Sec. 4.2): when ‖θ‖₂ ≫ ‖v‖₂ the directional
derivative loses float precision and the quadratic form can evaluate
negative even for PSD G.  ``stabilize=True`` computes J v' with
v' = (‖θ‖₂/‖v‖₂) v and rescales the final product by the inverse factor —
algebraically a no-op (G is linear), numerically the paper's fix that cuts
the CG iterations needed from ~200 to 5-8.
"""
from __future__ import annotations

import inspect
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import tree_math as tm


class CurvatureOps(NamedTuple):
    """Matrix-free operators bound to (params, cg_batch)."""

    gnvp: Callable        # v -> G v      (Gauss-Newton)
    fvp: Callable         # v -> F v      (empirical Fisher, from MMI/CE)
    eval_loss: Callable   # delta -> loss(params + delta) on the FULL
    #                       CG batch (never subsampled)
    logits: jnp.ndarray   # primal logits on the curvature batch


def subsample_batch(batch, fraction: float, multiple: int = 1):
    """Deterministic leading-dim prefix of a batch pytree.

    Keeps ``max(1, round(B * fraction))`` utterances of every
    batch-leading field (same leading-dim heuristic as
    ``launch.steps.cg_sub_batch``), everything else untouched.  The CG
    batch is itself drawn randomly from the whole training set
    (Sec. 4.1), so a static prefix is an unbiased sample — and being a
    static slice it stays jit-friendly (no gather, no recompile per
    step).

    ``multiple`` (the data-parallel mesh extent under GSPMD) rounds the
    kept size UP to a whole multiple so the sample splits evenly across
    the data axes — He et al.'s distributed-HF worker split: each worker
    keeps the same per-shard prefix of its local shard and the products'
    batch mean stays one all-reduce.  A non-divisible prefix would
    instead fall off the sharded layout and replicate the curvature
    batch on every device."""
    arrs = [x for x in jax.tree.leaves(batch)
            if hasattr(x, "ndim") and x.ndim >= 1]
    B = arrs[0].shape[0]
    n = max(1, int(round(B * float(fraction))))
    if multiple > 1 and B % multiple == 0:
        n = min(B, ((n + multiple - 1) // multiple) * multiple)
    if n >= B:
        return batch

    def slc(x):
        if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] == B:
            return x[:n]
        return x

    return jax.tree.map(slc, batch)


def make_curvature_ops(forward_fn, loss_spec, params, batch, *,
                       stabilize: bool = True,
                       theta_norm=None,
                       mode: str = "rematvp",
                       eval_accumulators: str = "full",
                       curvature_sample: float = 1.0,
                       data_extent: int = 1) -> CurvatureOps:
    """forward_fn(params, batch) -> (logits, aux).

    eval_accumulators: statistics mode for ``eval_loss`` (the per-CG-
    iteration candidate evaluation).  "loss_only" asks the LossSpec for
    its value-only fast path (lattice losses skip the backward recursion
    / run the fused Pallas kernel); "full" keeps the default statistics
    set.  The gradient/curvature products are unaffected either way.

    curvature_sample: fraction of the CG batch the GN/Fisher products
    run on (Sainath et al. 2013, "implicit preconditioning and
    sampling": curvature estimates tolerate far smaller batches than
    candidate ranking does).  The sample is a deterministic prefix
    (``subsample_batch``); ``eval_loss`` ALWAYS sees the full CG batch —
    Alg. 1's candidate selection keeps its cheap fused loss-only
    evaluation at full fidelity while every JVP/VJP pair shrinks by the
    sample factor.  1.0 (default) is bit-identical to the unsampled
    path (the batch object is passed through untouched).  Schedulable
    across outer iterations by rebuilding the step (shapes are static
    under jit) — ``launch.train --curvature-sample-schedule``.

    data_extent: size of the data-parallel mesh axes the CG batch is
    sharded over (1 = unsharded, bit-identical to before).  The
    curvature sample is rounded up to a multiple of it
    (``subsample_batch(..., multiple=data_extent)``) so the GN/Fisher
    products run as He-style worker splits — every worker computes its
    shard's partial JVP/VJP and the batch-mean inside the LossSpec
    factor is reduced ONCE per product by the GSPMD all-reduce; the
    model's own FSDP gathers (``launch.fsdp.gather_for_compute``, traced
    inside ``forward_fn``) apply to the jvp/vjp passes exactly as to the
    primal forward.

    mode="linearize": linearize ONCE and reuse residuals across CG
    iterations — fastest, but holds every forward intermediate of the CG
    batch in memory for the whole CG stage (fine for the paper-scale
    acoustic models, catastrophic for 30-layer LLMs: ~17 GiB/dev measured
    on qwen2.5-3b train_4k; see EXPERIMENTS.md §Perf iter 1).

    mode="rematvp": per-product jax.jvp + jax.vjp — forward-mode stores
    only live tensors, reverse-mode under remat stores only layer carries.
    ~1.7x compute per CG iteration, O(30x) less resident memory.
    """
    curv_batch = (batch if curvature_sample >= 1.0
                  else subsample_batch(batch, curvature_sample,
                                       multiple=data_extent))

    def f(p):
        return forward_fn(p, curv_batch)[0]

    if mode == "linearize":
        with jax.named_scope("curvature_product"):
            logits, jvp_fn = jax.linearize(f, params)
        vjp_fn = jax.linear_transpose(jvp_fn, params)
    else:
        logits = None

        def jvp_fn(v):                           # noqa: ANN001
            _, jv = jax.jvp(f, (params,), (v,))
            return jv

        def vjp_fn(cot):
            _, pullback = jax.vjp(f, params)
            return pullback(cot)

    if theta_norm is None:
        theta_norm = tm.norm(params)

    def _product(factor_vp, v):
        with jax.named_scope("curvature_product"):
            if stabilize:
                v_norm = jnp.maximum(tm.norm(v), 1e-30)
                s = theta_norm / v_norm
                v_in = tm.scale(v, s)
            else:
                s = 1.0
                v_in = v
            # JVP requires tangent dtype == primal dtype (bf16 CG state vs
            # f32 master params)
            v_in = tm.cast_like(v_in, params)
            if mode == "linearize":
                out_primal = logits
                jv = jvp_fn(v_in)
                hu = factor_vp(out_primal, curv_batch, jv)
                (out,) = vjp_fn(hu)
            else:
                out_primal, jv = jax.jvp(f, (params,), (v_in,))
                hu = factor_vp(out_primal, curv_batch, jv)
                _, pullback = jax.vjp(f, params)
                (out,) = pullback(hu)
            return tm.scale(out, 1.0 / s) if stabilize else out

    def gnvp(v):
        return _product(loss_spec.gn_vp, v)

    def fvp(v):
        return _product(loss_spec.fisher_vp, v)

    # pass the kwarg only to LossSpecs that declare it, so specs with the
    # pre-accumulators signature keep working under the default
    # "loss_only" mode (they have no statistics to elide anyway)
    eval_kw = {}
    if eval_accumulators != "full":
        try:
            sig = inspect.signature(loss_spec.value).parameters
            accepts = "accumulators" in sig or any(
                p.kind is inspect.Parameter.VAR_KEYWORD
                for p in sig.values())
        except (TypeError, ValueError):
            accepts = False
        if accepts:
            eval_kw = {"accumulators": eval_accumulators}

    def eval_loss(delta):
        with jax.named_scope("candidate_eval"):
            lg, aux = forward_fn(
                tm.add(params, tm.cast_like(delta, params)), batch)
            # include the scaled auxiliary loss: grad_and_loss minimises
            # ``loss + aux``, so Alg. 1 candidate selection / reject_worse
            # must rank candidates by the SAME objective (dropping aux
            # made selection compare a different function than the one
            # optimised)
            return loss_spec.value(lg, batch, **eval_kw)[0] + aux

    return CurvatureOps(gnvp=gnvp, fvp=fvp, eval_loss=eval_loss, logits=logits)


def grad_and_loss(forward_fn, loss_spec, params, batch, *,
                  microbatches: int = 1, constrain=None):
    """Gradient-accumulation stage: mean loss + gradient over the gradient
    batch (data-parallel under pjit; the accumulation all-reduce is emitted
    by GSPMD — the Fig. 1 master/worker sum).

    microbatches > 1 splits the batch's leading dim and accumulates the
    gradient over a (rematted) sequential scan — the standard activation-
    memory lever for very large models (§Perf hillclimb 2: qwen2-72b's
    grad-stage residuals scale 1/microbatches).  ``constrain`` keeps the
    accumulated-gradient scan carry on its storage sharding.
    """

    with jax.named_scope("grad_stage"):
        def obj(p, b):
            logits, aux = forward_fn(p, b)
            loss, metrics = loss_spec.value(logits, b)
            # ``aux`` is the already-scaled auxiliary loss (e.g. MoE router
            # load-balance, scaled by cfg.router_aux_coef where the step is
            # built).
            return loss + aux, metrics

        if microbatches <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                obj, has_aux=True)(params, batch)
            return loss, metrics, grads

        B = jax.tree.leaves(batch)[0].shape[0]
        k = microbatches
        assert B % k == 0, (B, k)
        split = jax.tree.map(
            lambda x: x.reshape((k, B // k) + x.shape[1:])
            if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] == B else x,
            batch)
        ident = constrain if constrain is not None else (lambda t: t)

        def body(carry, mb):
            acc, loss_acc = carry
            (loss, metrics), grads = jax.value_and_grad(
                obj, has_aux=True)(params, mb)
            acc = ident(jax.tree.map(lambda a, g: a + g / k, acc, grads))
            return (acc, loss_acc + loss / k), metrics

        zeros = ident(jax.tree.map(jnp.zeros_like, params))
        (grads, loss), metrics = jax.lax.scan(body, (zeros, jnp.float32(0.0)),
                                              split)
        metrics = jax.tree.map(lambda m: m.mean(0) if hasattr(m, "ndim") and
                               m.ndim >= 1 else m, metrics)
        return loss, metrics, grads
