"""Jitted public wrappers for the Pallas kernels.

Every kernel auto-detects its mode through the ONE dispatch predicate in
``kernels.dispatch``: compiled on TPU backends, interpret elsewhere.
Every wrapper has a pure-jnp fallback (ref.py) that is also what the
distributed (GSPMD) model paths use — the kernels are the single-chip
hot-spot implementations.
"""
from __future__ import annotations

from repro.kernels import dispatch, ref
from repro.kernels.cg_fused import cg_fused_update as _cg_pallas
from repro.kernels.lattice_fb import dag_backward as _dag_bwd_pallas
from repro.kernels.lattice_fb import dag_forward as _dag_fwd_pallas
from repro.kernels.lattice_fb import dag_loss_only as _dag_loss_only_pallas
from repro.kernels.lattice_fb import sausage_backward as _fb_bwd_pallas
from repro.kernels.lattice_fb import sausage_forward as _fb_pallas
from repro.kernels.lattice_fb import sausage_loss_only as _fb_loss_only_pallas
from repro.kernels.swa_attention import swa_attention as _swa_pallas


def swa_attention(q, k, v, window: int, *, use_pallas: bool = True):
    if not use_pallas:
        return ref.swa_attention_ref(q, k, v, window)
    # interpret=None auto-detects via kernels.dispatch (one source of
    # truth for every kernel): compiled on TPU, interpreter elsewhere
    return _swa_pallas(q, k, v, window, interpret=None)


def sausage_forward(scores, corr, mask=None, *, use_pallas: bool = True):
    if not use_pallas:
        return ref.sausage_forward_ref(scores, corr, mask)
    # interpret=None auto-detects: compiled on TPU, interpreter elsewhere
    return _fb_pallas(scores, corr, mask, interpret=None)


def sausage_backward(scores, corr, mask=None, *, use_pallas: bool = True):
    if not use_pallas:
        return ref.sausage_backward_ref(scores, corr, mask)
    return _fb_bwd_pallas(scores, corr, mask, interpret=None)


def sausage_loss_only(log_probs, start, end, label, lm, corr, arc_mask,
                      level_arcs, *, kappa: float = 1.0,
                      use_pallas: bool = True):
    """Candidate-evaluation forward: (logZ, c_avg) straight from the
    (B, T, K) frame log-probs + arc-layout lattice fields (scores and the
    arc->sausage gather built in XLA, the forward-only recursion in the
    kernel; no per-arc statistics materialised)."""
    if not use_pallas:
        return ref.sausage_loss_only_ref(log_probs, start, end, label, lm,
                                         corr, arc_mask, level_arcs,
                                         kappa=kappa)
    return _fb_loss_only_pallas(log_probs, start, end, label, lm, corr,
                                arc_mask, level_arcs, kappa=kappa,
                                interpret=None)


def dag_forward(own, corr, start, ok, final, pidx, *,
                use_pallas: bool = True):
    """General-DAG forward recursion over level-major frontier tensors
    (alpha, c_alpha, logZ, c_avg) — final arcs may sit on any level."""
    if not use_pallas:
        return ref.dag_forward_ref(own, corr, start, ok, final, pidx)
    return _dag_fwd_pallas(own, corr, start, ok, final, pidx,
                           interpret=None)


def dag_backward(own, corr, final, ok, sidx, *, use_pallas: bool = True):
    """General-DAG backward recursion (beta, c_beta) over the successor
    frontier positions."""
    if not use_pallas:
        return ref.dag_backward_ref(own, corr, final, ok, sidx)
    return _dag_bwd_pallas(own, corr, final, ok, sidx, interpret=None)


def dag_loss_only(log_probs, start, end, label, lm, corr, arc_mask,
                  is_start, is_final, level_arcs, pidx, *,
                  kappa: float = 1.0, use_pallas: bool = True):
    """General-DAG candidate-evaluation forward: (logZ, c_avg) straight
    from the (B, T, K) frame log-probs + arc-layout lattice fields + the
    levelized frontier tensors (scores and the arc->level-major gather in
    XLA, the forward-only frontier recursion in the kernel)."""
    if not use_pallas:
        return ref.dag_loss_only_ref(log_probs, start, end, label, lm,
                                     corr, arc_mask, is_start, is_final,
                                     level_arcs, pidx, kappa=kappa)
    return _dag_loss_only_pallas(log_probs, start, end, label, lm, corr,
                                 arc_mask, is_start, is_final, level_arcs,
                                 pidx, kappa=kappa, interpret=None)


def cg_fused_update(alpha, x, v, r, bv, *, use_pallas: bool | None = None):
    """Fused CG vector update: x+αv, r−αBv and the exact blockwise <r,r>
    reduction in one pass over flat (N,) buffers.

    ``use_pallas=None`` (the default, what ``core.cg.cg_solve(fused=True)``
    uses) auto-dispatches on ``kernels.dispatch.compiled_backend()``: the
    Pallas kernel where it compiles (TPU), the fused pure-jnp reference
    elsewhere — interpret-mode Pallas would only add per-block overhead
    on CPU while XLA already fuses the ref's AXPY+dot chain into one
    loop."""
    if use_pallas is None:
        use_pallas = dispatch.compiled_backend()
    if not use_pallas:
        return ref.cg_fused_update_ref(alpha, x, v, r, bv)
    return _cg_pallas(alpha, x, v, r, bv, interpret=None)


def cg_fused_update_tree(alpha, x, v, r, bv):
    """Sharded fused CG vector update over θ-sized PYTREES.

    The mesh-safe counterpart of ``cg_fused_update``: ravelling a
    2d-sharded pytree into one flat buffer is inexpressible for GSPMD
    (full all-gather per leaf), so each leaf stays in its natural layout
    — which IS the per-shard flat buffer under GSPMD — and ``rr`` is an
    exact cross-shard reduction (per-leaf f32 partial sums + one
    all-reduce).  Always the jnp reference: the fused elementwise chain
    is one XLA fusion per leaf, and per-leaf Pallas launches would defeat
    the partitioner.  ``core.cg.cg_solve(fused=True, constrain=...)``
    dispatches here."""
    return ref.cg_fused_update_tree_ref(alpha, x, v, r, bv)
