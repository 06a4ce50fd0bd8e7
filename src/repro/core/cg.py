"""The linear conjugate-gradient engine (paper Alg. 1 + Secs. 4.2/4.3).

Solves ``B x = b`` for θ-sized pytrees with a matrix-free ``Bv`` operator,
inside one jitted computation (``lax.scan`` over CG iterations — the
"sequential CG driven by the master" of Fig. 1, with each product
data-parallel over the CG batch underneath).

Three paper-specific features on top of textbook CG:

  1. **Candidate-update selection** — every iterate Δθ_m is (optionally)
     evaluated on the CG batch and the argmin candidate is returned
     (Alg. 1's "best performance on the validation set").  Candidate
     evaluation dominates the CG stage (~73 % of CG wall time in paper
     Table 1); ``eval_fn`` should therefore be the loss-only fast path —
     ``SecondOrderConfig.eval_accumulators="loss_only"`` wires
     ``CurvatureOps.eval_loss`` through the lattice engine's fused
     forward-only statistics (no backward recursion, no per-arc tensors),
     cutting the per-iteration evaluation cost.  With ``eval_every > 1``
     intermediate iterates are skipped, but the FINAL iterate is always
     evaluated — the deepest candidate must never be silently excluded
     from selection.
  2. **Pluggable preconditioning** — diagonal PCG behind the
     ``core.optim.preconditioners`` protocol.  ``precond`` is an
     M⁻¹-apply callable (r -> M⁻¹ r); a per-leaf count tree is still
     accepted and means the paper's Sec. 4.3 shared-parameter scaling
     M⁻¹ = diag(1/c): equivalently plain CG in the √c-rescaled variable
     space, i.e. residuals/directional derivatives are normalised by the
     number of times a parameter is applied, so heavily-shared weights
     stop dominating ‖r‖ and ‖Bv‖.
  3. **Negative-curvature guard** — if vᵀBv ≤ 0 (possible for the MBR GN
     matrix, Sec. 3.2, or from fp error without the Sec. 4.2 rescaling)
     the iteration freezes and the best candidate so far is kept.

And two cost levers on the vector/iteration side:

  * **Fused vector work** (``fused=True``) — the iterate/residual/search
    vectors are flattened into ONE contiguous buffer (``ravel_pytree``)
    and each iteration's ``x += αv; r -= αBv; rr = <r, r>`` chain runs
    through ``kernels.ops.cg_fused_update``: a single Pallas launch on
    TPU (3 HBM reads + 2 writes instead of 5 + 2, the dot rides along
    with an exact per-block f32 reduction), the pure-jnp fused reference
    elsewhere.  With the identity preconditioner the kernel's ``rr`` IS
    ``<r, z>``, so the separate reduction pass disappears too.  Under a
    mesh (``constrain`` given) the solve switches to the SHARDED fused
    variant: the loop carries keep the pytree layout (a ravel of a
    2d-sharded leaf is inexpressible for GSPMD), each leaf is the
    per-shard flat buffer for one fused elementwise pass, and ``rr`` is
    the exact cross-shard reduction — per-leaf f32 partials + one
    all-reduce (``kernels.ops.cg_fused_update_tree``) — composing with
    the per-leaf sharding constraints instead of refusing them.
  * **Adaptive iteration budget** (``tol > 0``) — instead of always
    spending ``iters`` curvature products, stop once CG's per-iteration
    relative improvement of the quadratic model q(x) = ½xᵀBx − xᵀb
    drops below ``tol`` (Martens 2010's relative-improvement criterion:
    q decreases monotonically, so a vanishing gain means further
    products cannot buy a better candidate).  ``iters`` becomes the
    CEILING; the solve runs a ``lax.while_loop`` and genuinely skips
    the remaining products.  A warm start that lands near the solution
    now shows up as FEWER iterations instead of equal cost at equal
    quality.  History rows beyond ``iters_used`` read NaN (losses: inf).

Tikhonov damping (B + ηI) is available for the baseline comparison the
paper makes against (Sainath et al., 2013a).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from repro.core import tree_math as tm
from repro.kernels import ops as kernel_ops


class CGResult(NamedTuple):
    x: dict                    # best candidate Δθ
    best_loss: jnp.ndarray     # its CG-batch loss (inf if eval_fn is None)
    best_iter: jnp.ndarray     # which iteration produced it
    quad: jnp.ndarray          # (M,) quadratic-model value per iteration
    resid: jnp.ndarray         # (M,) preconditioned residual norm
    curv: jnp.ndarray          # (M,) vᵀBv per iteration
    losses: jnp.ndarray        # (M,) candidate losses (inf where not eval'd)
    iters_used: jnp.ndarray    # iterations actually executed: always
    #                            iters on the fixed-budget scan (a guarded
    #                            iteration still runs its product); < iters
    #                            when the tol criterion or the curvature
    #                            guard stopped the adaptive loop.  ``evals``
    #                            says how many iterates were evaluated
    evals: jnp.ndarray         # candidate evaluations actually run


def cg_solve(bv_fn: Callable, b, *, iters: int,
             precond=None,
             eval_fn: Optional[Callable] = None,
             damping: float = 0.0,
             eval_every: int = 1,
             constrain: Optional[Callable] = None,
             x0=None,
             tol: float = 0.0,
             min_iters: int = 1,
             fused: bool = False) -> CGResult:
    """Run up to ``iters`` CG iterations on B x = b.

    bv_fn:    v -> B v (θ-sized pytree in/out).
    b:        right-hand side (e.g. -∇L, or the NG direction for NGHF).
    precond:  the M⁻¹ apply — None => identity; a callable r -> M⁻¹ r
              (``core.optim.preconditioners``); or a legacy per-leaf
              share-count tree c meaning M = diag(c) (Sec. 4.3).
    eval_fn:  Δθ -> scalar CG-batch loss for candidate selection.
    damping:  Tikhonov η (B + ηI) — the baseline the paper improves on.
    constrain: optional θ-tree -> θ-tree sharding constraint applied to
              every loop-carried vector each iteration.  Without it GSPMD's
              while-loop fixpoint can settle the carries on REPLICATED
              (measured: 7 full-size f32 vectors/dev on qwen2.5-3b).
    x0:       optional warm-start iterate (e.g. the previous update's Δθ,
              ``SecondOrderConfig.warm_start``).  Costs ONE extra B
              product to form the true residual b - B x0; None keeps the
              historical cold start from 0 exactly (no extra product).
    tol:      adaptive budget — stop once the quadratic model's relative
              per-iteration gain (q_{m-1} - q_m) / |q_m| falls below it
              (or the curvature guard fires).  0.0 (default) keeps the
              historical fixed-``iters`` scan bit-for-bit: there a fired
              guard freezes the iterate but every iteration still runs its
              product, so ``iters_used`` stays ``iters``; the evaluations
              the guard skipped show in ``CGResult.evals``.
    min_iters: floor before ``tol`` may fire (the first gain is measured
              against q(x0)).
    fused:    run the per-iteration vector work fused.  Single-chip
              (``constrain=None``): ONE flat buffer via
              ``kernels.ops.cg_fused_update`` (Pallas on TPU, fused-jnp
              ref elsewhere).  Under a mesh (``constrain`` given): the
              sharded variant ``cg_fused_update_tree`` — per-leaf fused
              passes + exact cross-shard ``rr`` reduction, leaving every
              carry in its (constrained) pytree layout.
    """
    with jax.named_scope("cg_solve"):
        sharded_fused = fused and constrain is not None
        if constrain is None:
            constrain = lambda t: t          # noqa: E731

        unravel = None
        if fused and not sharded_fused:
            # flatten ONCE; every loop-carried vector lives in one contiguous
            # buffer so the AXPY+dot chain is a single kernel launch.  The
            # matrix-free product still needs the pytree view — unravel is a
            # reshape/split, negligible against the JVP+VJP it feeds.
            b, unravel = ravel_pytree(b)
            _tree_bv = bv_fn
            bv_fn = lambda vf: ravel_pytree(_tree_bv(unravel(vf)))[0]  # noqa
            if eval_fn is not None:
                _tree_eval = eval_fn
                eval_fn = lambda xf: _tree_eval(unravel(xf))           # noqa
            if x0 is not None:
                x0 = ravel_pytree(x0)[0]
            if precond is not None:
                if callable(precond):                # protocol M⁻¹ apply
                    _tree_minv = precond
                    precond = lambda rf: ravel_pytree(          # noqa: E731
                        _tree_minv(unravel(rf)))[0]
                else:
                    precond = ravel_pytree(precond)[0]  # legacy counts -> flat

        identity_precond = precond is None
        if precond is None:
            Minv = lambda t: t               # noqa: E731
        elif callable(precond):
            Minv = precond
        else:                                # legacy per-leaf count tree
            counts = precond
            Minv = lambda t: jax.tree.map(                      # noqa: E731
                lambda x, c: x / jnp.asarray(c, x.dtype), t, counts)

        def B(v):
            out = bv_fn(v)
            if damping:
                out = tm.axpy(damping, v, out)
            return out

        warm = x0 is not None
        if not warm:
            x0 = tm.zeros_like(b)
            r0 = b                   # residual of x=0
        else:
            x0 = constrain(x0)
            r0 = constrain(tm.sub(b, B(x0)))
        z0 = Minv(r0)
        v0 = z0
        rz0 = tm.vdot(r0, z0)

        def iterate(x, r, z, v, rz, dead):
            """One CG iteration's linear algebra — shared verbatim by the
            fixed-budget scan and the adaptive while_loop so the two paths
            cannot drift."""
            bv = B(v)
            vbv = tm.vdot(v, bv)
            bad = (vbv <= 0.0) | dead
            alpha = jnp.where(bad, 0.0, rz / jnp.maximum(vbv, 1e-30))
            if fused:
                if sharded_fused:
                    x_new, r_new, rr = kernel_ops.cg_fused_update_tree(
                        alpha, x, v, r, bv)
                else:
                    x_new, r_new, rr = kernel_ops.cg_fused_update(
                        alpha, x, v, r, bv)
                if identity_precond:
                    # with M = I the kernel's exact blockwise <r, r> IS <r, z>
                    z_new, rz_new = r_new, rr
                else:
                    z_new = Minv(r_new)
                    rz_new = tm.vdot(r_new, z_new)
            else:
                x_new = tm.axpy(alpha, v, x)
                r_new = tm.axpy(-alpha, bv, r)
                z_new = Minv(r_new)
                rz_new = tm.vdot(r_new, z_new)
            beta = jnp.where(bad, 0.0, rz_new / jnp.maximum(rz, 1e-30))
            v_new = tm.axpy(beta, v, z_new)
            x_new, r_new, z_new, v_new = (constrain(t) for t in
                                          (x_new, r_new, z_new, v_new))
            # quadratic model g(x) = 0.5 xᵀBx - xᵀb, via the residual
            # identity Bx = b - r  =>  g(x) = -0.5 (xᵀb + xᵀr): no extra B
            # product.
            quad = -0.5 * (tm.vdot(x_new, r_new) + tm.vdot(x_new, b))
            return x_new, r_new, z_new, v_new, rz_new, bad, vbv, quad

        def select(x_new, loss, best_x, best_loss, best_iter, m):
            better = loss < best_loss
            best_x = constrain(tm.where(better, x_new, best_x))
            best_loss = jnp.where(better, loss, best_loss)
            best_iter = jnp.where(better, m, best_iter)
            return best_x, best_loss, best_iter

        inf = jnp.asarray(jnp.inf, jnp.float32)

        if tol <= 0.0:
            # ---- historical fixed-budget path: lax.scan over exactly
            # `iters` iterations (bit-for-bit the pre-adaptive behaviour) --
            def body(carry, m):
                (x, r, z, v, rz, best_x, best_loss, best_iter, dead,
                 evals) = carry
                x_new, r_new, z_new, v_new, rz_new, bad, vbv, quad = \
                    iterate(x, r, z, v, rz, dead)
                if eval_fn is not None:
                    # always evaluate the final iterate: with eval_every > 1
                    # the deepest candidate would otherwise be skipped whenever
                    # (iters - 1) % eval_every != 0
                    do_eval = (((m % eval_every) == 0) | (m == iters - 1)) \
                        & ~bad
                    loss = jax.lax.cond(do_eval, lambda: eval_fn(x_new),
                                        lambda: inf)
                    evals = evals + do_eval.astype(jnp.int32)
                else:
                    loss = inf
                best_x, best_loss, best_iter = select(
                    x_new, loss, best_x, best_loss, best_iter, m)
                new_carry = (x_new, r_new, z_new, v_new, rz_new,
                             best_x, best_loss, best_iter, bad, evals)
                return new_carry, (quad, jnp.sqrt(jnp.maximum(rz_new, 0.0)),
                                   vbv, loss)

            init = (x0, r0, z0, v0, rz0, x0, inf,
                    jnp.asarray(-1, jnp.int32), jnp.asarray(False),
                    jnp.asarray(0, jnp.int32))
            (x, r, z, v, rz, best_x, best_loss, best_iter, dead,
             evals), hist = jax.lax.scan(body, init, jnp.arange(iters))
            quad, resid, curv, losses = hist
            iters_used = jnp.asarray(iters, jnp.int32)
            last_iter = jnp.asarray(iters - 1, jnp.int32)
        else:
            # ---- adaptive budget: while_loop, so the skipped iterations'
            # curvature products genuinely never run -----------------------
            M = iters
            nanv = jnp.full((M,), jnp.nan, jnp.float32)
            hist0 = (nanv, nanv, nanv, jnp.full((M,), jnp.inf, jnp.float32))
            # gain at m=0 is measured against q(x0) (0 for a cold start)
            q0 = -0.5 * (tm.vdot(x0, r0) + tm.vdot(x0, b))

            def cond(carry):
                m = carry[0]
                stop = carry[11]
                return (m < iters) & ~stop

            def wbody(carry):
                (m, x, r, z, v, rz, best_x, best_loss, best_iter, dead,
                 q_prev, stop, evaled, evals, hist) = carry
                x_new, r_new, z_new, v_new, rz_new, bad, vbv, quad = \
                    iterate(x, r, z, v, rz, dead)
                if eval_fn is not None:
                    # the final iterate cannot be known in advance here — it
                    # is evaluated AFTER the loop if its turn never came
                    do_eval = ((m % eval_every) == 0) & ~bad
                    loss = jax.lax.cond(do_eval, lambda: eval_fn(x_new),
                                        lambda: inf)
                else:
                    do_eval = jnp.asarray(False)
                    loss = inf
                evals = evals + do_eval.astype(jnp.int32)
                best_x, best_loss, best_iter = select(
                    x_new, loss, best_x, best_loss, best_iter, m)
                # relative-improvement criterion: q decreases monotonically on
                # the non-degenerate path, so a gain below tol·|q| means the
                # remaining products cannot buy a meaningfully better candidate
                gain = q_prev - quad
                converged = ((m + 1 >= min_iters)
                             & (gain <= tol * jnp.maximum(jnp.abs(quad),
                                                          1e-12)))
                qh, rh, ch, lh = hist
                hist = (qh.at[m].set(quad),
                        rh.at[m].set(jnp.sqrt(jnp.maximum(rz_new, 0.0))),
                        ch.at[m].set(vbv), lh.at[m].set(loss))
                return (m + 1, x_new, r_new, z_new, v_new, rz_new,
                        best_x, best_loss, best_iter, bad,
                        quad, bad | converged, do_eval, evals, hist)

            init = (jnp.asarray(0, jnp.int32), x0, r0, z0, v0, rz0,
                    x0, inf, jnp.asarray(-1, jnp.int32), jnp.asarray(False),
                    q0, jnp.asarray(False), jnp.asarray(False),
                    jnp.asarray(0, jnp.int32), hist0)
            # re-pack carry positions: (m, x, r, z, v, rz, bx, bl, bi, dead,
            #                           q_prev, stop, evaled, evals, hist)
            (m_end, x, r, z, v, rz, best_x, best_loss, best_iter, dead,
             q_prev, stop_flag, evaled, evals, hist) = jax.lax.while_loop(
                cond, wbody, init)
            quad, resid, curv, losses = hist
            iters_used = m_end
            last_iter = jnp.maximum(m_end - 1, 0)
            if eval_fn is not None:
                # the deepest candidate must never be silently excluded: if
                # the last executed iterate missed the eval stride (and the
                # solve did not die on negative curvature — a dead iterate
                # never moved), evaluate it now and let it compete
                need = ~evaled & ~dead
                loss_last = jax.lax.cond(need, lambda: eval_fn(x),
                                         lambda: inf)
                evals = evals + need.astype(jnp.int32)
                best_x, best_loss, best_iter = select(
                    x, loss_last, best_x, best_loss, best_iter, last_iter)
                losses = losses.at[last_iter].set(
                    jnp.where(need, loss_last, losses[last_iter]))

        # a warm-started solve frozen by the negative-curvature guard at
        # iteration 0 never left x0 — the PREVIOUS system's solution, not a
        # candidate for this one.  The unevaluated fallbacks below must return
        # Δθ=0 (the historical cold-start behaviour), never re-apply it.
        stale = (curv[0] <= 0.0) if warm else jnp.asarray(False)
        last = tm.where(stale, tm.zeros_like(x), x) if warm else x
        if eval_fn is None:
            best_x, best_iter = last, last_iter
        else:
            # if nothing evaluated better than inf (e.g. all bad), fall back
            none_found = ~jnp.isfinite(best_loss)
            best_x = tm.where(none_found, last, best_x)
            best_iter = jnp.where(none_found, last_iter, best_iter)
        if unravel is not None:
            best_x = unravel(best_x)
        return CGResult(x=best_x, best_loss=best_loss, best_iter=best_iter,
                        quad=quad, resid=resid, curv=curv, losses=losses,
                        iters_used=iters_used, evals=evals)
