"""Pallas TPU kernels: lattice forward AND backward passes + expected
correctness, for confusion-network (sausage) and general-DAG lattices.

This is the TPU-native backend of the levelized lattice engine
(``repro.lattice_engine``), the compute hot-spot of the paper's
"collecting statistics over lattices" stage (Table 1).  The engine owns
backend dispatch: the general-DAG per-arc scan and the level-parallel scan
live in ``repro/lattice_engine/{scan_backend,levelized}.py``; these kernels
run the same recursions with the per-level state in VMEM.  The engine
gathers arc tensors into the (levels, width) layout via
``Lattice.level_arcs`` and wraps the kernels in a ``jax.custom_jvp`` so
that ``jax.grad`` / ``jax.jvp`` flow through them via the closed-form
occupancy identities (see ``lattice_engine/pallas_backend.py``).

Sausage forward recursion (per utterance, sequential over segments s;
every arc of segment s connects to every arc of segment s-1):

    in_log(s)   = logsumexp(alpha[s-1])
    alpha[s,a]  = score[s,a] + in_log(s)
    c_in(s)     = sum softmax(alpha[s-1]) * c_alpha[s-1]
    c_alpha[s,a]= corr[s,a] + c_in(s)

Backward recursion (sequential over segments in reverse):

    beta[s,a]   = logsumexp_a'(score[s+1,a'] + beta[s+1,a'])   (0 at final)
    c_beta[s,a] = sum softmax(score[s+1]+beta[s+1]) * (corr[s+1]+c_beta[s+1])

Both sausage kernels honour an arc ``mask`` (B,S,A): masked arcs score
-inf and contribute nothing; a fully-masked segment (arc-count padding
from ``make_sausage_lattice(max_arcs=...)`` or batch-level levelization
padding) passes the carry through unchanged, so ``logZ``/``c_avg`` are
exact for ragged batches.

The *loss-only* kernels (``sausage_loss_only`` / ``dag_loss_only``) serve
the CG stage's candidate evaluation (paper Alg. 1 — ~73 % of CG wall time
in Table 1): the wrapper builds the per-arc scores from the frame
log-probs with the mean-centred cumsum endpoint gather
(``ref.sausage_arc_scores_ref``, the same identity as
``lattice_engine.common.arc_scores``) and gathers them into the
(levels, width) layout; the kernel runs only the forward recursion and
emits just ``(logZ, c_avg)`` — no alpha/c_alpha tiles leave VMEM and no
backward pass runs.

TPU mapping: every kernel grids over the batch, one utterance per grid
step, with its (L, W) level tiles in VMEM (a few KB per utterance at the
paper-scale shapes: L = T/4 levels of W = 3 alternatives).  The
sequential level recursion runs inside the kernel and reads/writes one
level row at a time through the refs (``ref[pl.ds(l, 1), :]``).  The
scalar carries are (1, 1) tiles and ``logZ``/``c_avg`` leave as (B, 1, 1)
arrays (a block's last two dims must be (8, 128)-aligned or whole).  The
frame-level endpoint gather stays in XLA: Mosaic has no arbitrary-index
lane gather, and a per-utterance grid would otherwise DMA the whole
(T+1, K) cumsum grid (3 MB at T=128, K=6000) to read 3 values per arc.

The general-DAG kernels keep a level-major position buffer (one column of
L*W+1 rows, the last the dump slot) in VMEM scratch.  A level's
predecessor (successor) gather is a one-hot select-and-sum over that
column — exact in f32, since exactly one position matches — and its
write-back is the transposed select.  This is O(L*W) work per gathered
slot: cheap at the frontier sizes the trainer and the service produce,
and what a TPU core can run without a lane gather.

``interpret`` defaults to auto-detection (``kernels.dispatch``): compiled
on TPU backends, interpreter everywhere else (CPU CI containers).
Validated against the oracles in ``kernels/ref.py``; ``tests/
test_tpu_compile.py`` compiles every kernel for a described TPU v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import instrument
from repro.kernels.dispatch import resolve_interpret
from repro.kernels.ref import gather_sausage_ref, sausage_arc_scores_ref

NEG = -1e30
_EPS = 1e-30
_F32 = jnp.float32


def _row(ref, i):
    """Row ``i`` (traced) of a (rows, lanes) VMEM ref, as (1, lanes)."""
    return ref[pl.ds(i, 1), :]


def _utt_spec(*dims):
    """Per-utterance block over a leading batch axis: (B, *dims) arrays,
    one whole utterance per grid step."""
    return pl.BlockSpec((None,) + dims, lambda b: (b,) + (0,) * len(dims))


def _scalar_out(B):
    """(B, 1, 1) per-utterance scalar output (logZ / c_avg)."""
    return _utt_spec(1, 1), jax.ShapeDtypeStruct((B, 1, 1), _F32)


# ---------------------------------------------------------------------------
# Sausage kernels: fully-connected segment recursion
# ---------------------------------------------------------------------------


def _sausage_recursion(score_ref, corr_ref, mask_ref, alpha_ref, calpha_ref,
                       num_segments: int):
    """Forward segment recursion of one utterance; writes alpha/c_alpha
    rows when the refs are given and returns the (1, 1) (logZ, c_avg)."""

    def seg_step(s, carry):
        in_log, c_in = carry                                  # (1, 1)
        m = _row(mask_ref, s)                                 # (1, A)
        valid = m > 0.5
        seg_valid = jnp.max(m, axis=1, keepdims=True) > 0.5
        row = jnp.where(valid, _row(score_ref, s) + in_log, NEG)
        c_row = jnp.where(valid, _row(corr_ref, s) + c_in, 0.0)
        if alpha_ref is not None:
            alpha_ref[pl.ds(s, 1), :] = row
            calpha_ref[pl.ds(s, 1), :] = c_row
        mx = jnp.max(row, axis=1, keepdims=True)
        e = jnp.exp(row - mx) * m
        z = jnp.sum(e, axis=1, keepdims=True)
        new_in_log = jnp.where(seg_valid, jnp.log(jnp.maximum(z, _EPS)) + mx,
                               in_log)
        w = e / jnp.maximum(z, _EPS)
        new_c_in = jnp.where(seg_valid,
                             jnp.sum(w * c_row, axis=1, keepdims=True), c_in)
        return new_in_log, new_c_in

    zero = jnp.zeros((1, 1), _F32)
    return jax.lax.fori_loop(0, num_segments, seg_step, (zero, zero))


def _fwd_kernel(score_ref, corr_ref, mask_ref, alpha_ref, calpha_ref,
                logz_ref, cavg_ref, *, num_segments: int):
    logz_ref[...], cavg_ref[...] = _sausage_recursion(
        score_ref, corr_ref, mask_ref, alpha_ref, calpha_ref, num_segments)


def _loss_only_kernel(score_ref, corr_ref, mask_ref, logz_ref, cavg_ref, *,
                      num_segments: int):
    """Forward-only segment recursion: only (logZ, c_avg) leave."""
    logz_ref[...], cavg_ref[...] = _sausage_recursion(
        score_ref, corr_ref, mask_ref, None, None, num_segments)


def _bwd_kernel(score_ref, corr_ref, mask_ref, beta_ref, cbeta_ref,
                *, num_segments: int):

    def seg_step(i, carry):
        out_log, c_out = carry                                # (1, 1)
        s = num_segments - 1 - i
        m = _row(mask_ref, s)
        valid = m > 0.5
        seg_valid = jnp.max(m, axis=1, keepdims=True) > 0.5
        b_row = jnp.where(valid, out_log, NEG)
        cb_row = jnp.where(valid, c_out, 0.0)
        beta_ref[pl.ds(s, 1), :] = b_row
        cbeta_ref[pl.ds(s, 1), :] = cb_row
        row = jnp.where(valid, _row(score_ref, s) + b_row, NEG)
        mx = jnp.max(row, axis=1, keepdims=True)
        e = jnp.exp(row - mx) * m
        z = jnp.sum(e, axis=1, keepdims=True)
        new_out_log = jnp.where(seg_valid,
                                jnp.log(jnp.maximum(z, _EPS)) + mx, out_log)
        w = e / jnp.maximum(z, _EPS)
        new_c_out = jnp.where(
            seg_valid,
            jnp.sum(w * (_row(corr_ref, s) + cb_row), axis=1, keepdims=True),
            c_out)
        return new_out_log, new_c_out

    zero = jnp.zeros((1, 1), _F32)
    jax.lax.fori_loop(0, num_segments, seg_step, (zero, zero))


def _sausage_call(kernel, outs, scores, corr, mask, interpret):
    """Launch one sausage kernel over (B, S, A) f32 tiles; ``outs`` lists
    the output kinds, "tile" (B, S, A) or "scalar" (B, 1, 1)."""
    B, S, A = scores.shape
    if mask is None:
        mask = jnp.ones(scores.shape, _F32)
    tile = (_utt_spec(S, A), jax.ShapeDtypeStruct((B, S, A), _F32))
    specs = [tile if o == "tile" else _scalar_out(B) for o in outs]
    return instrument.pallas_call(
        functools.partial(kernel, num_segments=S),
        grid=(B,),
        in_specs=[_utt_spec(S, A)] * 3,
        out_specs=[s for s, _ in specs],
        out_shape=[o for _, o in specs],
        interpret=resolve_interpret(interpret),
    )(scores.astype(_F32), corr.astype(_F32), mask.astype(_F32))


def sausage_forward(scores, corr, mask=None, *, interpret: bool | None = None):
    """scores/corr: (B, S, A) per-arc acoustic+lm scores and correctness;
    mask: optional (B, S, A), nonzero = valid arc.

    Returns (alpha (B,S,A), c_alpha (B,S,A), logZ (B,), c_avg (B,)).
    """
    alpha, c_alpha, logz, cavg = _sausage_call(
        _fwd_kernel, ("tile", "tile", "scalar", "scalar"), scores, corr,
        mask, interpret)
    return alpha, c_alpha, logz[:, 0, 0], cavg[:, 0, 0]


def sausage_backward(scores, corr, mask=None, *,
                     interpret: bool | None = None):
    """Backward (beta / c_beta) companion of :func:`sausage_forward`.

    Returns (beta (B,S,A), c_beta (B,S,A)); beta excludes the arc's own
    score (FBStats convention), so gamma = exp(alpha + beta - logZ).
    """
    beta, c_beta = _sausage_call(_bwd_kernel, ("tile", "tile"), scores,
                                 corr, mask, interpret)
    return beta, c_beta


def _level_arc_scores(log_probs, start, end, label, lm, kappa):
    """(B, A) acoustic+lm arc scores from the (B, T, K) frame log-probs
    (the mean-centred cumsum endpoint gather, in XLA)."""
    return sausage_arc_scores_ref(log_probs, start, end, label, kappa) \
        + lm.astype(_F32)


def sausage_loss_only(log_probs, start, end, label, lm, corr, arc_mask,
                      level_arcs, *, kappa: float = 1.0,
                      interpret: bool | None = None):
    """Loss-only forward: (logZ (B,), c_avg (B,)) straight from the frame
    log-probs and ARC-LAYOUT lattice fields.

    log_probs: (B, T, K) frame log-probabilities; start/end/label:
    (B, A) int32 arc span endpoints and output units (pad arcs may hold
    any in-range index — ``arc_mask`` must zero them); lm/corr/arc_mask:
    (B, A); level_arcs: (B, S, W) int32 frontier map (-1 padded).
    ``kappa`` is the acoustic scale and may be traced.

    Not differentiable directly (Pallas calls have no autodiff rules) —
    ``lattice_engine.pallas_backend`` wraps it in a ``custom_jvp``.
    """
    score_arc = _level_arc_scores(log_probs, start, end, label, lm, kappa)
    logz, cavg = _sausage_call(
        _loss_only_kernel, ("scalar", "scalar"),
        gather_sausage_ref(score_arc, level_arcs, 0.0),
        gather_sausage_ref(corr.astype(_F32), level_arcs, 0.0),
        gather_sausage_ref(arc_mask.astype(_F32), level_arcs, 0.0),
        interpret)
    return logz[:, 0, 0], cavg[:, 0, 0]


# ---------------------------------------------------------------------------
# General-DAG kernels: level-frontier recursion over the levelized tensors
# (losses.lattice.lattice_frontiers).  Same recursions as the levelized
# scan backend, but the per-level gathers, the masked logsumexp/softmax
# reductions and the level-major position buffers all live in VMEM
# inside one kernel invocation per utterance.  Unlike the sausage pair,
# final arcs may sit on ANY level, so logZ/c_avg are reduced over the
# final-flag mask at the end instead of from the last segment's carry.
# ---------------------------------------------------------------------------


def _buffer_rows(L: int, W: int) -> int:
    """Rows of the level-major position buffer: L*W slots + the dump slot
    at L*W, rounded up to whole (8, 128) tiles."""
    return -(-(L * W + 1) // 8) * 8


def _masked_lse(xs):
    """Masked logsumexp over a list of equally shaped tiles (entries
    at/near NEG are masked; all-masked positions -> exactly NEG and
    all-zero weights) — the kernel-side twin of ``ref._masked_lse_row``
    with the reduced axis unrolled.  Returns (lse, [weights])."""
    m = functools.reduce(jnp.maximum, xs)
    has = m > NEG * 0.5
    m0 = jnp.where(has, m, 0.0)
    es = [jnp.where(x > NEG * 0.5, jnp.exp(x - m0), 0.0) for x in xs]
    z = functools.reduce(jnp.add, es)
    lse = jnp.where(has, jnp.maximum(jnp.log(jnp.maximum(z, _EPS)) + m0, NEG),
                    NEG)
    zs = jnp.maximum(z, _EPS)
    return lse, [e / zs for e in es]


def _masked_lse_tile(x):
    """Masked logsumexp over a whole (L, W) tile -> (1, 1), with the
    (L, W) masked-softmax weights."""
    def full(op, t):
        return op(op(t, axis=1, keepdims=True), axis=0, keepdims=True)
    m = full(jnp.max, x)
    has = m > NEG * 0.5
    m0 = jnp.where(has, m, 0.0)
    e = jnp.where(x > NEG * 0.5, jnp.exp(x - m0), 0.0)
    z = full(jnp.sum, e)
    lse = jnp.where(has, jnp.maximum(jnp.log(jnp.maximum(z, _EPS)) + m0, NEG),
                    NEG)
    return lse, e / jnp.maximum(z, _EPS)


def _gather_rows(buf, idx):
    """buf: (N, 1) position column; idx: (1, W) positions -> (1, W)
    values buf[idx] (one-hot select-and-sum; exact, one match)."""
    pos = jax.lax.broadcasted_iota(jnp.int32, (buf.shape[0], idx.shape[1]), 0)
    return jnp.sum(jnp.where(pos == idx, buf, 0.0), axis=0, keepdims=True)


def _scatter_level(buf, vals, l, W):
    """Write the (1, W) row ``vals`` into positions l*W .. l*W+W-1 of the
    (N, 1) position column ``buf``."""
    N = buf.shape[0]
    pos = jax.lax.broadcasted_iota(jnp.int32, (N, W), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (N, W), 1)
    hit = pos == l * W + lane
    placed = jnp.sum(jnp.where(hit, vals, 0.0), axis=1, keepdims=True)
    col = jax.lax.broadcasted_iota(jnp.int32, (N, 1), 0)
    in_level = (col >= l * W) & (col < l * W + W)
    return jnp.where(in_level, placed, buf)


def _dag_fwd_kernel(own_ref, corr_ref, start_ref, ok_ref, final_ref,
                    pidx_ref, alpha_ref, calpha_ref, logz_ref, cavg_ref,
                    abuf_ref, cbuf_ref):
    """Forward frontier recursion of one utterance: alpha/c_alpha (L, W)
    level by level through the level-major position buffers, then
    logZ / c_avg over the final arcs, which may sit on any level."""
    P, L, W = pidx_ref.shape
    abuf_ref[...] = jnp.full(abuf_ref.shape, NEG, _F32)
    cbuf_ref[...] = jnp.zeros(cbuf_ref.shape, _F32)

    def level_step(l, carry):
        a_col, c_col = abuf_ref[...], cbuf_ref[...]           # (N, 1)
        idx = [pidx_ref[p, pl.ds(l, 1), :] for p in range(P)]  # (1, W)
        in_log, ws = _masked_lse([_gather_rows(a_col, i) for i in idx])
        c_in = functools.reduce(jnp.add, [
            w * _gather_rows(c_col, i) for w, i in zip(ws, idx)])
        own_l = _row(own_ref, l)
        start_l = _row(start_ref, l) > 0.5
        ok_l = _row(ok_ref, l) > 0.5
        a_val = jnp.where(start_l, own_l, own_l + in_log)
        c_val = _row(corr_ref, l) + jnp.where(start_l, 0.0, c_in)
        a_val = jnp.where(ok_l, a_val, NEG)
        c_val = jnp.where(ok_l, c_val, 0.0)
        alpha_ref[pl.ds(l, 1), :] = a_val
        calpha_ref[pl.ds(l, 1), :] = c_val
        abuf_ref[...] = _scatter_level(a_col, a_val, l, W)
        cbuf_ref[...] = _scatter_level(c_col, c_val, l, W)
        return carry

    jax.lax.fori_loop(0, L, level_step, 0)
    af = jnp.where(final_ref[...] > 0.5, alpha_ref[...], NEG)
    logz, w = _masked_lse_tile(af)
    logz_ref[...] = logz
    cavg_ref[...] = jnp.sum(jnp.sum(w * calpha_ref[...], axis=1,
                                    keepdims=True), axis=0, keepdims=True)


def _dag_loss_only_kernel(own_ref, corr_ref, start_ref, ok_ref, final_ref,
                          pidx_ref, logz_ref, cavg_ref, alpha_ref,
                          calpha_ref, abuf_ref, cbuf_ref):
    """Forward-only frontier recursion: alpha/c_alpha stay in VMEM
    scratch; only (logZ, c_avg) leave."""
    _dag_fwd_kernel(own_ref, corr_ref, start_ref, ok_ref, final_ref,
                    pidx_ref, alpha_ref, calpha_ref, logz_ref, cavg_ref,
                    abuf_ref, cbuf_ref)


def _dag_bwd_kernel(own_ref, corr_ref, final_ref, ok_ref, sidx_ref,
                    beta_ref, cbeta_ref, qbuf_ref, cqbuf_ref):
    """Backward frontier recursion.  The position buffers hold what a
    successor gather needs, q = beta + own and cq = c_beta + corr (NEG and
    0 at empty slots and at the dump slot)."""
    S, L, W = sidx_ref.shape
    qbuf_ref[...] = jnp.full(qbuf_ref.shape, NEG, _F32)
    cqbuf_ref[...] = jnp.zeros(cqbuf_ref.shape, _F32)

    def level_step(i, carry):
        l = L - 1 - i
        q_col, cq_col = qbuf_ref[...], cqbuf_ref[...]         # (N, 1)
        idx = [sidx_ref[s, pl.ds(l, 1), :] for s in range(S)]  # (1, W)
        out_log, ws = _masked_lse([_gather_rows(q_col, j) for j in idx])
        c_out = functools.reduce(jnp.add, [
            w * _gather_rows(cq_col, j) for w, j in zip(ws, idx)])
        final_l = _row(final_ref, l) > 0.5
        ok_l = _row(ok_ref, l) > 0.5
        b_val = jnp.where(ok_l, jnp.where(final_l, 0.0, out_log), NEG)
        c_val = jnp.where(ok_l, jnp.where(final_l, 0.0, c_out), 0.0)
        beta_ref[pl.ds(l, 1), :] = b_val
        cbeta_ref[pl.ds(l, 1), :] = c_val
        q = jnp.where(ok_l, b_val + _row(own_ref, l), NEG)
        cq = jnp.where(ok_l, c_val + _row(corr_ref, l), 0.0)
        qbuf_ref[...] = _scatter_level(q_col, q, l, W)
        cqbuf_ref[...] = _scatter_level(cq_col, cq, l, W)
        return carry

    jax.lax.fori_loop(0, L, level_step, 0)


def _dag_call(kernel, outs, tiles, frontier, interpret, scratch_tiles=0):
    """Launch one DAG kernel: ``tiles`` are (B, L, W) level-major operands,
    ``frontier`` the (B, L, W, F) predecessor/successor positions (laid
    out (B, F, L, W) for the kernel).  ``outs`` as in ``_sausage_call``;
    ``scratch_tiles`` extra (L, W) VMEM tiles precede the two position
    buffers."""
    B, L, W = tiles[0].shape
    F = frontier.shape[-1]
    tile = (_utt_spec(L, W), jax.ShapeDtypeStruct((B, L, W), _F32))
    specs = [tile if o == "tile" else _scalar_out(B) for o in outs]
    N = _buffer_rows(L, W)
    return instrument.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[_utt_spec(L, W)] * len(tiles) + [_utt_spec(F, L, W)],
        out_specs=[s for s, _ in specs],
        out_shape=[o for _, o in specs],
        scratch_shapes=([pltpu.VMEM((L, W), _F32)] * scratch_tiles
                        + [pltpu.VMEM((N, 1), _F32)] * 2),
        interpret=resolve_interpret(interpret),
    )(*[t.astype(_F32) for t in tiles],
      jnp.moveaxis(frontier.astype(jnp.int32), -1, 1))


def dag_forward(own, corr, start, ok, final, pidx, *,
                interpret: bool | None = None):
    """General-DAG forward kernel over level-major frontier tensors.

    own/corr: (B, L, W) f32 per-slot scores (acoustic+lm; NEG at empty
    slots) and correctness counts; start/ok/final: (B, L, W) f32 flags
    (nonzero = set); pidx: (B, L, W, P) int32 predecessor positions into
    the flat (L*W+1,) level-major buffer, dump slot L*W
    (``losses.lattice.lattice_frontiers``).

    Returns (alpha (B,L,W), c_alpha (B,L,W), logZ (B,), c_avg (B,)).
    Validated against ``ref.dag_forward_ref``.
    """
    alpha, c_alpha, logz, cavg = _dag_call(
        _dag_fwd_kernel, ("tile", "tile", "scalar", "scalar"),
        (own, corr, start, ok, final), pidx, interpret)
    return alpha, c_alpha, logz[:, 0, 0], cavg[:, 0, 0]


def dag_backward(own, corr, final, ok, sidx, *,
                 interpret: bool | None = None):
    """Backward (beta / c_beta) companion of :func:`dag_forward` over the
    successor frontier positions ``sidx`` (B, L, W, S).  beta excludes the
    arc's own score (FBStats convention).  Validated against
    ``ref.dag_backward_ref``."""
    beta, c_beta = _dag_call(_dag_bwd_kernel, ("tile", "tile"),
                             (own, corr, final, ok), sidx, interpret)
    return beta, c_beta


def dag_loss_only(log_probs, start, end, label, lm, corr, arc_mask,
                  is_start, is_final, level_arcs, pidx, *,
                  kappa: float = 1.0, interpret: bool | None = None):
    """Loss-only forward for GENERAL DAG lattices: (logZ (B,), c_avg (B,))
    straight from the frame log-probs and arc-layout lattice fields, like
    :func:`sausage_loss_only`, but running the frontier-recursion forward
    pass (predecessor-position gathers) instead of the fully-connected
    segment recursion.

    Extra inputs over the sausage variant: is_start/is_final (B, A) arc
    flags (finals may sit on any level) and pidx (B, L, W, P) predecessor
    positions (``losses.lattice.lattice_frontiers``).

    Not differentiable directly — ``lattice_engine.pallas_backend`` wraps
    it in a ``custom_jvp``.  Validated against ``ref.dag_loss_only_ref``.
    """
    score_arc = _level_arc_scores(log_probs, start, end, label, lm, kappa)
    ok = gather_sausage_ref(arc_mask.astype(_F32), level_arcs, 0.0)
    tiles = (gather_sausage_ref(score_arc, level_arcs, NEG),
             gather_sausage_ref(corr.astype(_F32), level_arcs, 0.0),
             gather_sausage_ref(is_start.astype(_F32), level_arcs, 0.0) * ok,
             ok,
             gather_sausage_ref(is_final.astype(_F32), level_arcs, 0.0) * ok)
    logz, cavg = _dag_call(_dag_loss_only_kernel, ("scalar", "scalar"),
                           tiles, pidx, interpret, scratch_tiles=2)
    return logz[:, 0, 0], cavg[:, 0, 0]
