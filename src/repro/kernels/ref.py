"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def swa_attention_ref(q, k, v, window: int):
    """q/k/v: (B,T,H,hd), kv heads already repeated.  Dense reference."""
    B, T, H, hd = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    pos = jnp.arange(T)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None] - window - 1)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


_NEG = -1e30


def sausage_forward_ref(scores, corr, mask=None):
    """scores/corr: (B,S,A), optional mask (B,S,A; nonzero = valid arc).
    lax.scan reference of the masked sausage forward recursion."""
    if mask is None:
        mask = jnp.ones(scores.shape, jnp.float32)

    def per_utt(sc, co, mk):
        def step(carry, inp):
            in_log, c_in = carry
            row_s, row_c, row_m = inp
            valid = row_m > 0.5
            seg_valid = jnp.max(row_m) > 0.5
            row = jnp.where(valid, row_s + in_log, _NEG)
            c_row = jnp.where(valid, row_c + c_in, 0.0)
            m = row.max()
            e = jnp.exp(row - m) * row_m
            z = e.sum()
            new_log = jnp.where(seg_valid, jnp.log(jnp.maximum(z, 1e-30)) + m,
                                in_log)
            w = e / jnp.maximum(z, 1e-30)
            new_c = jnp.where(seg_valid, jnp.sum(w * c_row), c_in)
            return (new_log, new_c), (row, c_row)

        (logz, cavg), (alpha, c_alpha) = jax.lax.scan(
            step, (jnp.float32(0.0), jnp.float32(0.0)),
            (sc.astype(jnp.float32), co.astype(jnp.float32),
             mk.astype(jnp.float32)))
        return alpha, c_alpha, logz, cavg

    return jax.vmap(per_utt)(scores, corr, mask)


def sausage_backward_ref(scores, corr, mask=None):
    """Reference of the masked sausage backward recursion: returns
    (beta (B,S,A), c_beta (B,S,A)), beta excluding the arc's own score."""
    if mask is None:
        mask = jnp.ones(scores.shape, jnp.float32)

    def per_utt(sc, co, mk):
        def step(carry, inp):
            out_log, c_out = carry
            row_s, row_c, row_m = inp
            valid = row_m > 0.5
            seg_valid = jnp.max(row_m) > 0.5
            b_row = jnp.where(valid, out_log, _NEG)
            cb_row = jnp.where(valid, c_out, 0.0)
            row = jnp.where(valid, row_s + b_row, _NEG)
            m = row.max()
            e = jnp.exp(row - m) * row_m
            z = e.sum()
            new_log = jnp.where(seg_valid, jnp.log(jnp.maximum(z, 1e-30)) + m,
                                out_log)
            w = e / jnp.maximum(z, 1e-30)
            new_c = jnp.where(seg_valid, jnp.sum(w * (row_c + cb_row)), c_out)
            return (new_log, new_c), (b_row, cb_row)

        _, (beta, c_beta) = jax.lax.scan(
            step, (jnp.float32(0.0), jnp.float32(0.0)),
            (sc.astype(jnp.float32), co.astype(jnp.float32),
             mk.astype(jnp.float32)), reverse=True)
        return beta, c_beta

    return jax.vmap(per_utt)(scores, corr, mask)


def sausage_arc_scores_ref(log_probs, start, end, label, kappa: float):
    """Per-arc acoustic scores from (B, T, K) log-probs via the
    mean-centred cumsum endpoint gather (pure jnp; the same identity as
    ``lattice_engine.common.arc_scores``), for any common index shape
    (B, ...) — arc layout (B, A) or sausage layout (B, S, W).

    The gather reads each endpoint (b, t, label) of the (B, T, K) cumsum
    in place, the empty prefix (t = 0) as 0: on the TPU's tiled layout a
    flatten of the cumsum, or a zero row prepended to it, is a relayout
    copy of the whole grid (and of its tangent and cotangent).

    Linear in ``log_probs`` — the fused kernel's ``custom_jvp`` applies
    this very function to the tangents.
    """
    B = log_probs.shape[0]
    shp = start.shape
    lp = log_probs.astype(jnp.float32)
    mu = jnp.mean(lp, axis=1, keepdims=True)                  # (B, 1, K)
    cum = jnp.cumsum(lp - mu, axis=1)                         # (B, T, K)
    bi = jnp.arange(B)[:, None]
    lab = label.reshape(B, -1).astype(jnp.int32)

    def prefix(t):                     # sum over frames [0, t) at (b, lab)
        row = t.reshape(B, -1).astype(jnp.int32) - 1
        return jnp.where(row >= 0, cum[bi, jnp.maximum(row, 0), lab], 0.0)

    hi = prefix(end)
    lo = prefix(start)
    span = (end - start).reshape(B, -1).astype(jnp.float32)
    mu_lab = jnp.take_along_axis(mu[:, 0, :], lab, axis=1)
    return (kappa * (hi - lo + span * mu_lab)).reshape(shp)


def gather_sausage_ref(values, level_arcs, fill):
    """(B, A) arc values -> (B, S, W) sausage layout via the level_arcs
    frontier map (-1 slots get ``fill``)."""
    safe = jnp.maximum(level_arcs, 0)
    g = jax.vmap(lambda v, i: v[i])(values, safe)
    return jnp.where(level_arcs >= 0, g, fill)


def sausage_loss_only_ref(log_probs, start, end, label, lm, corr, arc_mask,
                          level_arcs, *, kappa: float = 1.0):
    """Oracle of the fused loss-only kernel: in-graph score construction,
    arc->sausage gather, and masked forward recursion, returning only
    (logZ (B,), c_avg (B,)).  All lattice fields in arc layout (B, A);
    level_arcs: (B, S, W) int32 (-1 padded)."""
    score_arc = sausage_arc_scores_ref(log_probs, start, end, label, kappa) \
        + lm.astype(jnp.float32)                              # (B, A)
    scores = gather_sausage_ref(score_arc, level_arcs, 0.0)
    co = gather_sausage_ref(corr.astype(jnp.float32), level_arcs, 0.0)
    mk = gather_sausage_ref(arc_mask.astype(jnp.float32), level_arcs, 0.0)
    _, _, logz, cavg = sausage_forward_ref(scores, co, mk)
    return logz, cavg


def _masked_lse_row(x, axis=-1):
    """Row-wise logsumexp treating entries at/near _NEG as masked; an
    all-masked row returns exactly _NEG.  Companion weights (masked
    softmax: all-masked rows get all-zero weights) returned alongside."""
    valid = x > _NEG * 0.5
    m = jnp.max(x, axis=axis)
    m0 = jnp.where(m > _NEG * 0.5, m, 0.0)
    e = jnp.where(valid, jnp.exp(x - jnp.expand_dims(m0, axis)), 0.0)
    z = jnp.sum(e, axis=axis)
    has = jnp.any(valid, axis=axis)
    lse = jnp.where(has,
                    jnp.maximum(jnp.log(jnp.maximum(z, 1e-30)) + m0, _NEG),
                    _NEG)
    w = e / jnp.expand_dims(jnp.maximum(z, 1e-30), axis)
    return lse, w


def dag_forward_ref(own, corr, start, ok, final, pidx):
    """Pure-jnp oracle of the general-DAG forward kernel.

    All level-major (B, L, W): ``own`` arc scores (acoustic+lm, _NEG at
    empty slots), ``corr`` correctness counts, ``start``/``ok``/``final``
    flags (any numeric/bool dtype; nonzero = set); ``pidx``:
    (B, L, W, P) int32 predecessor flat positions into the (L*W+1,)
    level-major buffer (dump slot L*W; see
    ``losses.lattice.lattice_frontiers``).

    Returns (alpha (B,L,W), c_alpha (B,L,W), logZ (B,), c_avg (B,)) —
    logZ/c_avg reduced over FINAL arcs (which may sit on any level, unlike
    the sausage kernels' last-segment contract).
    """

    def per_utt(own_u, corr_u, start_u, ok_u, final_u, pidx_u):
        L, W = own_u.shape
        LW = L * W
        offs = jnp.arange(L, dtype=jnp.int32) * W

        def step(carry, inp):
            a_buf, c_buf = carry
            own_l, corr_l, start_l, ok_l, pidx_l, off = inp
            pa = a_buf[pidx_l]                                 # (W, P)
            pc = c_buf[pidx_l]
            in_log, w = _masked_lse_row(pa)
            c_in = jnp.sum(w * pc, axis=-1)
            a_val = jnp.where(start_l, own_l, own_l + in_log)
            c_val = corr_l + jnp.where(start_l, 0.0, c_in)
            a_val = jnp.where(ok_l, a_val, _NEG)
            c_val = jnp.where(ok_l, c_val, 0.0)
            a_buf = jax.lax.dynamic_update_slice(a_buf, a_val, (off,))
            c_buf = jax.lax.dynamic_update_slice(c_buf, c_val, (off,))
            return (a_buf, c_buf), None

        (a_buf, c_buf), _ = jax.lax.scan(
            step,
            (jnp.full((LW + 1,), _NEG), jnp.zeros((LW + 1,))),
            (own_u.astype(jnp.float32), corr_u.astype(jnp.float32),
             start_u.astype(jnp.float32) > 0.5,
             ok_u.astype(jnp.float32) > 0.5, pidx_u, offs))
        fin = (final_u.astype(jnp.float32).reshape(-1) > 0.5)
        af = jnp.where(fin, a_buf[:LW], _NEG)
        logz, w = _masked_lse_row(af)
        cavg = jnp.sum(w * c_buf[:LW])
        return (a_buf[:LW].reshape(L, W), c_buf[:LW].reshape(L, W),
                logz, cavg)

    return jax.vmap(per_utt)(own, corr, start, ok, final, pidx)


def dag_backward_ref(own, corr, final, ok, sidx):
    """Pure-jnp oracle of the general-DAG backward kernel: level-major
    (beta (B,L,W), c_beta (B,L,W)); beta excludes the arc's own score
    (FBStats convention), so gamma = exp(alpha + beta - logZ)."""

    def per_utt(own_u, corr_u, final_u, ok_u, sidx_u):
        L, W = own_u.shape
        LW = L * W
        okf = ok_u.astype(jnp.float32).reshape(-1) > 0.5
        own_pad = jnp.concatenate(
            [jnp.where(okf, own_u.astype(jnp.float32).reshape(-1), _NEG),
             jnp.full((1,), _NEG)])                            # (LW+1,)
        corr_pad = jnp.concatenate(
            [jnp.where(okf, corr_u.astype(jnp.float32).reshape(-1), 0.0),
             jnp.zeros((1,))])
        offs = jnp.arange(L - 1, -1, -1, dtype=jnp.int32) * W

        def step(carry, inp):
            b_buf, cb_buf = carry
            final_l, ok_l, sidx_l, off = inp
            s_out = jnp.where(sidx_l < LW,
                              b_buf[sidx_l] + own_pad[sidx_l], _NEG)
            sc = cb_buf[sidx_l] + corr_pad[sidx_l]             # (W, S)
            out_log, w = _masked_lse_row(s_out)
            c_out = jnp.sum(w * sc, axis=-1)
            b_val = jnp.where(final_l, 0.0, out_log)
            c_val = jnp.where(final_l, 0.0, c_out)
            b_val = jnp.where(ok_l, b_val, _NEG)
            c_val = jnp.where(ok_l, c_val, 0.0)
            b_buf = jax.lax.dynamic_update_slice(b_buf, b_val, (off,))
            cb_buf = jax.lax.dynamic_update_slice(cb_buf, c_val, (off,))
            return (b_buf, cb_buf), None

        (b_buf, cb_buf), _ = jax.lax.scan(
            step,
            (jnp.full((LW + 1,), _NEG), jnp.zeros((LW + 1,))),
            (final_u.astype(jnp.float32)[::-1] > 0.5,
             ok_u.astype(jnp.float32)[::-1] > 0.5, sidx_u[::-1], offs))
        return b_buf[:LW].reshape(L, W), cb_buf[:LW].reshape(L, W)

    return jax.vmap(per_utt)(own, corr, final, ok, sidx)


def dag_loss_only_ref(log_probs, start, end, label, lm, corr, arc_mask,
                      is_start, is_final, level_arcs, pidx, *,
                      kappa: float = 1.0):
    """Oracle of the fused general-DAG loss-only kernel: in-graph score
    construction, arc->level-major gather, and the forward-only DAG
    recursion with final-arc reduction, returning (logZ (B,), c_avg (B,)).
    Lattice fields in arc layout (B, A); level_arcs (B, L, W) and pidx
    (B, L, W, P) from ``losses.lattice.lattice_frontiers``."""
    score_arc = sausage_arc_scores_ref(log_probs, start, end, label, kappa) \
        + lm.astype(jnp.float32)                              # (B, A)
    own = gather_sausage_ref(score_arc, level_arcs, _NEG)
    co = gather_sausage_ref(corr.astype(jnp.float32), level_arcs, 0.0)
    ok = gather_sausage_ref(arc_mask.astype(jnp.float32), level_arcs, 0.0)
    st = gather_sausage_ref(is_start.astype(jnp.float32), level_arcs,
                            0.0) * ok
    fin = gather_sausage_ref(is_final.astype(jnp.float32), level_arcs,
                             0.0) * ok
    _, _, logz, cavg = dag_forward_ref(own, co, st, ok, fin, pidx)
    return logz, cavg


def cg_fused_update_ref(alpha, x, v, r, bv):
    xf = x.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    rf = r.astype(jnp.float32)
    bvf = bv.astype(jnp.float32)
    x_new = (xf + alpha * vf).astype(x.dtype)
    r_new = (rf - alpha * bvf).astype(r.dtype)
    rr = jnp.sum((rf - alpha * bvf) ** 2)
    return x_new, r_new, rr


def cg_fused_update_tree_ref(alpha, x, v, r, bv):
    """Sharded variant of the fused CG vector update: per-leaf buffers
    instead of one ravelled buffer.

    Flattening a 2d-sharded pytree is inexpressible for GSPMD (a ravel
    forces a full all-gather — the same reason ``tree_math.vdot`` avoids
    ``jnp.vdot``), so under a mesh each leaf keeps its natural shape and
    acts as the per-shard flat buffer: the x+αv / r−αBv / r² chain is one
    fused elementwise pass over every leaf, and ``rr`` is the EXACT
    cross-shard reduction — per-leaf f32 partial sums (per-shard partials
    + one all-reduce under GSPMD) summed over the tree.  Dtype discipline
    matches ``cg_fused_update_ref``: updates compute in f32, land in the
    leaf's storage dtype, ``rr`` stays f32."""

    def leaf(xi, vi, ri, bvi):
        xf = xi.astype(jnp.float32)
        vf = vi.astype(jnp.float32)
        rf = ri.astype(jnp.float32)
        bvf = bvi.astype(jnp.float32)
        rn = rf - alpha * bvf
        return ((xf + alpha * vf).astype(xi.dtype),
                rn.astype(ri.dtype), jnp.sum(rn * rn))

    out = jax.tree.map(leaf, x, v, r, bv,
                       is_leaf=lambda t: hasattr(t, "dtype"))
    x_new = jax.tree.map(lambda o: o[0], out, is_leaf=lambda t: type(t) is tuple)
    r_new = jax.tree.map(lambda o: o[1], out, is_leaf=lambda t: type(t) is tuple)
    rr = jax.tree.reduce(lambda a, o: a + o[2], out, jnp.float32(0.0),
                         is_leaf=lambda t: type(t) is tuple)
    return x_new, r_new, rr
