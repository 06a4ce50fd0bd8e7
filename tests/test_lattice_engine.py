"""Lattice-engine backend equivalence + differentiability guarantees.

Deliberately hypothesis-free (plain parametrize over seeds) so this file
runs even in containers without the property-testing extra: it is the
tier-1 guard for the scan / levelized / Pallas backend contract and for
the Pallas ``custom_jvp`` that MMI/MPE training differentiates through.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.lattice_engine import (BACKENDS, lattice_is_sausage,
                                  lattice_stats, resolve_backend)
from repro.lattice_engine.common import arc_scores
from repro.losses.forward_backward import forward_backward
from repro.losses.lattice import (batch_lattices, make_lattice_batch,
                                  make_random_dag_lattice,
                                  make_sausage_lattice)
from repro.losses.sequence import MMILoss, MPELoss

K = 10
ARC_FIELDS = ("alpha", "beta", "gamma", "c_alpha", "c_beta", "c_arc")
UTT_FIELDS = ("logZ", "c_avg")


def _uniform_batch(seed, T=24, seg_len=4, n_alt=3, B=2):
    lat = make_lattice_batch(seed, batch=B, num_frames=T, num_states=K,
                             seg_len=seg_len, n_alt=n_alt)
    lp = jax.nn.log_softmax(
        jax.random.normal(jax.random.PRNGKey(seed + 100), (B, T, K)), -1)
    return lat, lp


def _padded_batch(seed, T=24, max_arcs=20):
    """Ragged batch: different segmentations + arc-count padding."""
    rng = np.random.default_rng(seed)
    lats = [
        make_sausage_lattice(rng, num_frames=T, num_states=K, seg_len=4,
                             n_alt=3, max_arcs=max_arcs),
        make_sausage_lattice(rng, num_frames=T, num_states=K, seg_len=8,
                             n_alt=2, max_arcs=max_arcs),
    ]
    lat = batch_lattices(lats)
    lp = jax.nn.log_softmax(
        jax.random.normal(jax.random.PRNGKey(seed + 200), (2, T, K)), -1)
    return lat, lp


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("padded", [False, True])
def test_three_backends_agree(seed, padded):
    lat, lp = _padded_batch(seed) if padded else _uniform_batch(seed)
    stats = {b: lattice_stats(lat, lp, kappa=0.8, backend=b)
             for b in BACKENDS}
    for field in ARC_FIELDS + UTT_FIELDS:
        want = np.asarray(getattr(stats["scan"], field))
        for b in ("levelized", "pallas"):
            np.testing.assert_allclose(
                np.asarray(getattr(stats[b], field)), want, atol=1e-4,
                err_msg=f"{b}.{field} (seed={seed}, padded={padded})")


@pytest.mark.parametrize("seed", [0, 5])
def test_padded_arcs_do_not_corrupt_stats(seed):
    """A lattice padded with max_arcs must give the same logZ/c_avg as the
    identical unpadded lattice, on every backend."""
    rng1 = np.random.default_rng(seed)
    rng2 = np.random.default_rng(seed)
    plain = make_sausage_lattice(rng1, num_frames=24, num_states=K,
                                 seg_len=4, n_alt=3)
    padded = make_sausage_lattice(rng2, num_frames=24, num_states=K,
                                  seg_len=4, n_alt=3, max_arcs=30)
    lp = jax.nn.log_softmax(
        jax.random.normal(jax.random.PRNGKey(seed), (1, 24, K)), -1)
    base = lattice_stats(batch_lattices([plain]), lp, 1.0, backend="scan")
    for b in BACKENDS:
        got = lattice_stats(batch_lattices([padded]), lp, 1.0, backend=b)
        np.testing.assert_allclose(np.asarray(got.logZ),
                                   np.asarray(base.logZ), atol=1e-4)
        np.testing.assert_allclose(np.asarray(got.c_avg),
                                   np.asarray(base.c_avg), atol=1e-4)
        # pad arcs carry no posterior mass
        assert np.asarray(got.gamma)[:, plain["lm"].shape[0]:].max() == 0.0


@pytest.mark.parametrize("loss_cls", [MMILoss, MPELoss])
def test_pallas_grad_matches_scan_and_fd(loss_cls):
    """jax.grad through the Pallas custom_jvp == scan-backend autodiff,
    and both match central finite differences (guards the MMILoss.gn_vp /
    occupancy identities in losses/sequence.py)."""
    lat, lp_unused = _uniform_batch(7)
    logits = jax.random.normal(jax.random.PRNGKey(11), (2, 24, K))

    f_scan = lambda lg: loss_cls(kappa=0.8, backend="scan").value(  # noqa: E731
        lg, {"lattice": lat})[0]
    f_pal = lambda lg: loss_cls(kappa=0.8, backend="pallas").value(  # noqa: E731
        lg, {"lattice": lat})[0]
    g_scan = jax.grad(f_scan)(logits)
    g_pal = jax.grad(f_pal)(logits)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_scan),
                               atol=2e-5)
    d = jax.random.normal(jax.random.PRNGKey(13), logits.shape)
    eps = 1e-2                      # f32 round-off dominates below ~3e-3
    fd = (f_pal(logits + eps * d) - f_pal(logits - eps * d)) / (2 * eps)
    assert abs(float(fd) - float(jnp.vdot(g_pal, d))) < 1e-4


@pytest.mark.parametrize("loss_cls", [MMILoss, MPELoss])
def test_pallas_jvp_matches_scan(loss_cls):
    """The R-operator direction (jax.jvp) agrees across backends — the
    custom_jvp tangent rule is the closed-form occupancy identity."""
    lat, _ = _uniform_batch(3)
    logits = jax.random.normal(jax.random.PRNGKey(17), (2, 24, K))
    d = jax.random.normal(jax.random.PRNGKey(19), logits.shape)
    jvps = {}
    for b in BACKENDS:
        f = lambda lg: loss_cls(kappa=0.8, backend=b).value(  # noqa: E731
            lg, {"lattice": lat})[0]
        _, jvps[b] = jax.jvp(f, (logits,), (d,))
    for b in ("levelized", "pallas"):
        assert abs(float(jvps[b]) - float(jvps["scan"])) < 1e-5, b


def test_backends_work_under_jit():
    lat, lp = _uniform_batch(2)
    vals = [jax.jit(lambda lp_, b=b: lattice_stats(lat, lp_, 1.0,
                                                   backend=b).logZ)(lp)
            for b in BACKENDS]
    for v in vals[1:]:
        np.testing.assert_allclose(np.asarray(v), np.asarray(vals[0]),
                                   atol=1e-4)


def test_auto_dispatch_and_sausage_detection(monkeypatch):
    lat, lp = _uniform_batch(0)
    assert lattice_is_sausage(lat)
    # concrete + CPU -> levelized (pallas only auto-selected on TPU)
    assert resolve_backend("auto", lat) in ("levelized", "pallas")
    monkeypatch.setenv("REPRO_LATTICE_BACKEND", "scan")
    assert resolve_backend("auto", lat) == "scan"
    monkeypatch.delenv("REPRO_LATTICE_BACKEND")
    with pytest.raises(ValueError):
        resolve_backend("nope", lat)
    # traced lattices cannot be inspected -> never pallas via auto
    traced = jax.jit(lambda l, lp_: lattice_stats(l, lp_, 1.0,
                                                  backend="auto").logZ)
    np.testing.assert_allclose(np.asarray(traced(lat, lp)),
                               np.asarray(lattice_stats(
                                   lat, lp, 1.0, "scan").logZ), atol=1e-4)


def test_non_sausage_rejected_for_pallas_auto():
    """Breaking full connectivity must fail the static sausage check."""
    rng = np.random.default_rng(0)
    d = make_sausage_lattice(rng, num_frames=16, num_states=K, seg_len=4,
                             n_alt=2)
    d["preds"][2, 1] = -1          # arc 2 no longer sees every level-0 arc
    lat = batch_lattices([d])
    assert not lattice_is_sausage(lat)


def test_arc_scores_long_T_regression():
    """Endpoint-difference arc scoring must stay accurate at T >= 1024:
    the raw f32 cumsum loses ~4e-4 absolute by T=1024 (span sums cancel
    against cumulative magnitudes growing like T·log K); the mean-centred
    cumsum stays within a few f32 ulps of the direct per-arc f64 sum."""
    T, states = 1024, 16
    lat = make_lattice_batch(0, batch=2, num_frames=T, num_states=states,
                             seg_len=4, n_alt=3)
    lp = jax.nn.log_softmax(
        jax.random.normal(jax.random.PRNGKey(0), (2, T, states)), -1)
    got = np.asarray(arc_scores(lat, lp, kappa=1.0))
    lp64 = np.asarray(lp, np.float64)
    start = np.asarray(lat.start_t)
    end = np.asarray(lat.end_t)
    lab = np.asarray(lat.label)
    for b in range(2):
        ref_b = np.array([lp64[b, s:e, l].sum()
                          for s, e, l in zip(start[b], end[b], lab[b])])
        np.testing.assert_allclose(got[b], ref_b, atol=5e-5)


def _flattened_arc_scores(log_probs, start, end, label, kappa):
    """The endpoint gather as it was: a zero row prepended to the
    mean-centred cumsum, flattened to (B, (T+1)K), two 1-D gathers."""
    B, T, K = log_probs.shape
    lp = log_probs.astype(jnp.float32)
    mu = jnp.mean(lp, axis=1, keepdims=True)
    cum = jnp.cumsum(lp - mu, axis=1)
    cum = jnp.concatenate([jnp.zeros_like(cum[:, :1]), cum], axis=1)
    flat = cum.reshape(B, (T + 1) * K)
    lab = label.reshape(B, -1).astype(jnp.int32)
    hi = jnp.take_along_axis(flat, end.reshape(B, -1) * K + lab, axis=1)
    lo = jnp.take_along_axis(flat, start.reshape(B, -1) * K + lab, axis=1)
    span = (end - start).reshape(B, -1).astype(jnp.float32)
    mu_lab = jnp.take_along_axis(mu[:, 0, :], lab, axis=1)
    return (kappa * (hi - lo + span * mu_lab)).reshape(start.shape)


@pytest.mark.parametrize("T", [512, 1024])
@pytest.mark.parametrize("layout", ["arcs", "sausage"])
def test_arc_scores_in_place_gather_matches_flattened(layout, T):
    """The in-place endpoint gather picks the same cumsum elements as the
    flattened formula: forward scores bitwise equal, JVP and VJP within
    1e-6 relative, in arc layout (B, A) and sausage layout (B, S, W)."""
    B, states = 3, 16
    lat = make_lattice_batch(T, batch=B, num_frames=T, num_states=states,
                             seg_len=4, n_alt=3)
    idx = (lat.start_t, lat.end_t, lat.label)
    if layout == "sausage":
        idx = tuple(ref.gather_sausage_ref(x, lat.level_arcs, 0)
                    for x in idx)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(T), 3)
    lp = jax.nn.log_softmax(jax.random.normal(k1, (B, T, states)), -1)
    tangent = jax.random.normal(k2, lp.shape)
    cotangent = jax.random.normal(k3, idx[0].shape)

    def run(f):
        score = lambda x: f(x, *idx, 0.5)                  # noqa: E731
        y, dy = jax.jvp(score, (lp,), (tangent,))
        _, vjp = jax.vjp(score, lp)
        return y, dy, vjp(cotangent)[0]

    y, dy, ct = run(ref.sausage_arc_scores_ref)
    y0, dy0, ct0 = run(_flattened_arc_scores)
    assert y.shape == idx[0].shape
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
    np.testing.assert_allclose(dy, dy0, rtol=1e-6)
    np.testing.assert_allclose(ct, ct0, rtol=1e-6)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("accumulators", ["full", "loss_only"])
def test_padded_arcs_get_zero_cotangent(backend, accumulators):
    """Gradients through logZ/c_avg on a padded ragged batch must put
    EXACTLY zero cotangent on padded arc scores — naive exp(x - max) over
    an all-masked row leaks softmax-style 1/W gradients into padding.
    Holds in both statistics modes (the fused Pallas loss-only path
    differentiates lat.lm through its sausage gather)."""
    lat, lp = _padded_batch(0)
    pad = ~np.asarray(lat.arc_mask)
    assert pad.any()                                 # batch really is ragged

    def f(lm):
        st = lattice_stats(lat._replace(lm=lm), lp, 1.0, backend=backend,
                           accumulators=accumulators)
        return jnp.sum(st.logZ) + jnp.sum(st.c_avg)

    g = np.asarray(jax.grad(f)(lat.lm))
    assert np.isfinite(g).all()
    assert np.abs(g[pad]).max() == 0.0
    assert np.abs(g[~pad]).max() > 0.0               # real arcs still flow


# ---------------------------------------------------------------------------
# accumulators="loss_only" (the fused candidate-evaluation path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("padded", [False, True])
def test_loss_only_matches_full_values(backend, padded):
    """(logZ, c_avg) from the loss-only path == full statistics path, on
    uniform and ragged/padded batches, for every backend."""
    lat, lp = _padded_batch(11) if padded else _uniform_batch(11)
    full = lattice_stats(lat, lp, kappa=0.8, backend=backend)
    lo = lattice_stats(lat, lp, kappa=0.8, backend=backend,
                       accumulators="loss_only")
    assert not hasattr(lo, "gamma")     # really the reduced statistics set
    for field in UTT_FIELDS:
        np.testing.assert_allclose(
            np.asarray(getattr(lo, field)), np.asarray(getattr(full, field)),
            atol=1e-4, err_msg=f"{backend}.{field} (padded={padded})")


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("padded", [False, True])
def test_loss_only_grad_and_jvp_match_full(backend, padded):
    """jax.grad / jax.jvp through the loss-only path == the full path —
    the fused Pallas custom_jvp must reproduce the occupancy tangents."""
    lat, lp = _padded_batch(13) if padded else _uniform_batch(13)

    def f(lp_, acc):
        st = lattice_stats(lat, lp_, 0.8, backend=backend, accumulators=acc)
        return jnp.sum(st.logZ) + jnp.sum(st.c_avg)

    g_full = jax.grad(lambda l: f(l, "full"))(lp)
    g_lo = jax.grad(lambda l: f(l, "loss_only"))(lp)
    np.testing.assert_allclose(np.asarray(g_lo), np.asarray(g_full),
                               atol=2e-5,
                               err_msg=f"{backend} grad (padded={padded})")
    d = jax.random.normal(jax.random.PRNGKey(23), lp.shape)
    _, jv_full = jax.jvp(lambda l: f(l, "full"), (lp,), (d,))
    _, jv_lo = jax.jvp(lambda l: f(l, "loss_only"), (lp,), (d,))
    assert abs(float(jv_lo) - float(jv_full)) < 1e-4, (backend, padded)


def test_loss_only_works_under_jit():
    lat, lp = _uniform_batch(2)
    want = np.asarray(lattice_stats(lat, lp, 1.0, backend="scan").logZ)
    for b in BACKENDS:
        got = jax.jit(lambda lp_, b=b: lattice_stats(
            lat, lp_, 1.0, backend=b, accumulators="loss_only").logZ)(lp)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, err_msg=b)


def test_unknown_accumulators_rejected():
    lat, lp = _uniform_batch(0)
    with pytest.raises(ValueError):
        lattice_stats(lat, lp, 1.0, accumulators="nope")


def test_fused_loss_only_kernel_matches_ref():
    """The fused candidate-eval kernel (in-kernel score construction +
    arc->sausage gather + forward-only recursion) == its pure-jnp oracle,
    on a ragged/padded batch (masked arcs + padded frontier slots), and
    both == the scan backend's logZ/c_avg."""
    lat, lp = _padded_batch(5)
    args = (lp, lat.start_t, lat.end_t, lat.label, lat.lm, lat.corr,
            lat.arc_mask, lat.level_arcs)
    got = ops.sausage_loss_only(*args, kappa=0.8, use_pallas=True)
    want = ref.sausage_loss_only_ref(*args, kappa=0.8)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4)
    full = lattice_stats(lat, lp, 0.8, backend="scan")
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(full.logZ),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(full.c_avg),
                               atol=1e-4)


def _dag_batch(seed, B=3, T=24, max_arcs=80):
    """Random general-DAG batch: skip arcs, variable fan-in/out, ragged
    arc-count padding (max_arcs) — the topology the sausage kernels
    reject."""
    rng = np.random.default_rng(seed)
    lats = [make_random_dag_lattice(rng, num_frames=T, num_states=K,
                                    max_arcs=max_arcs) for _ in range(B)]
    lat = batch_lattices(lats)
    lp = jax.nn.log_softmax(
        jax.random.normal(jax.random.PRNGKey(seed + 300), (B, T, K)), -1)
    return lat, lp


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("backend", ["levelized", "pallas"])
def test_random_dag_backends_agree(seed, backend):
    """The generality claim for the fast backends: agreement with the
    per-arc reference on NON-sausage DAGs (variable fan-in/out, skip
    arcs, ragged/padded batches) — for the Pallas backend this pins the
    general-DAG frontier kernels (never a scan fallback)."""
    lat, lp = _dag_batch(seed)
    assert not lattice_is_sausage(lat)
    want = lattice_stats(lat, lp, kappa=0.8, backend="scan")
    got = lattice_stats(lat, lp, kappa=0.8, backend=backend)
    for field in ARC_FIELDS + UTT_FIELDS:
        np.testing.assert_allclose(
            np.asarray(getattr(got, field)), np.asarray(getattr(want, field)),
            atol=1e-4, err_msg=f"{backend}.{field} (seed={seed})")
    # gradients agree too (the engine is differentiated in training)
    g_scan = jax.grad(lambda l: jnp.sum(lattice_stats(
        lat, l, 0.8, backend="scan").logZ))(lp)
    g = jax.grad(lambda l: jnp.sum(lattice_stats(
        lat, l, 0.8, backend=backend).logZ))(lp)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_scan),
                               atol=1e-5)


@pytest.mark.parametrize("accumulators", ["full", "loss_only"])
def test_dag_pallas_grad_jvp_fd(accumulators):
    """jax.grad AND jax.jvp through the DAG Pallas custom_jvp == scan
    autodiff, and the grad passes a central finite-difference check —
    both statistics modes (the fused DAG loss-only kernel included)."""
    lat, lp = _dag_batch(7, B=2)

    def f(lp_, be):
        st = lattice_stats(lat, lp_, 0.8, backend=be,
                           accumulators=accumulators)
        return jnp.sum(st.logZ) + jnp.sum(st.c_avg)

    g_scan = jax.grad(lambda l: f(l, "scan"))(lp)
    g_pal = jax.grad(lambda l: f(l, "pallas"))(lp)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_scan),
                               atol=2e-5)
    d = jax.random.normal(jax.random.PRNGKey(31), lp.shape)
    _, jv_scan = jax.jvp(lambda l: f(l, "scan"), (lp,), (d,))
    _, jv_pal = jax.jvp(lambda l: f(l, "pallas"), (lp,), (d,))
    assert abs(float(jv_pal) - float(jv_scan)) < 1e-4
    eps = 1e-2                      # f32 round-off dominates below ~3e-3
    fd = (f(lp + eps * d, "pallas") - f(lp - eps * d, "pallas")) / (2 * eps)
    assert abs(float(fd) - float(jnp.vdot(g_pal, d))) < 1e-3


def test_dag_pallas_no_silent_fallback(monkeypatch):
    """backend="pallas" on a general DAG must run the DAG kernels — not
    raise, and not silently reroute to a scan backend."""
    from repro.lattice_engine import pallas_backend
    lat, lp = _dag_batch(4)
    assert not lattice_is_sausage(lat)
    calls = {"dag": 0}
    real = pallas_backend.dag_forward

    def spy(*a, **kw):
        calls["dag"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(pallas_backend, "dag_forward", spy)
    st = lattice_stats(lat, lp, 1.0, backend="pallas")
    assert calls["dag"] > 0
    np.testing.assert_allclose(
        np.asarray(st.logZ),
        np.asarray(lattice_stats(lat, lp, 1.0, backend="scan").logZ),
        atol=1e-4)


@pytest.mark.parametrize("accumulators", ["full", "loss_only"])
def test_dag_pallas_under_jit(accumulators):
    """Traced lattices route through the DAG kernels (topology cannot be
    inspected inside jit) for sausage AND DAG batches, both modes."""
    for lat, lp in (_dag_batch(2), _uniform_batch(2)):
        want = np.asarray(lattice_stats(lat, lp, 0.8, backend="scan").logZ)
        got = jax.jit(lambda l, lp_: lattice_stats(
            l, lp_, 0.8, backend="pallas",
            accumulators=accumulators).logZ)(lat, lp)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)


def test_dag_kernels_match_refs():
    """The general-DAG Pallas kernel pair and the fused DAG loss-only
    kernel == their pure-jnp oracles on a ragged DAG batch."""
    from repro.losses.lattice import lattice_frontiers
    lat, lp = _dag_batch(9)
    fr = lattice_frontiers(lat)
    am = arc_scores(lat, lp, 0.8) + lat.lm
    own = ref.gather_sausage_ref(am, lat.level_arcs, -1e30)
    corr = ref.gather_sausage_ref(lat.corr, lat.level_arcs, 0.0)
    st = fr.start.astype(jnp.float32)
    ok = fr.ok.astype(jnp.float32)
    fin = fr.final.astype(jnp.float32)
    for got, want in zip(
            ops.dag_forward(own, corr, st, ok, fin, fr.pidx),
            ref.dag_forward_ref(own, corr, st, ok, fin, fr.pidx)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)
    for got, want in zip(
            ops.dag_backward(own, corr, fin, ok, fr.sidx),
            ref.dag_backward_ref(own, corr, fin, ok, fr.sidx)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)
    args = (lp, lat.start_t, lat.end_t, lat.label, lat.lm, lat.corr,
            lat.arc_mask, lat.is_start, lat.is_final, lat.level_arcs,
            fr.pidx)
    got = ops.dag_loss_only(*args, kappa=0.8, use_pallas=True)
    want = ref.dag_loss_only_ref(*args, kappa=0.8)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4)
    full = lattice_stats(lat, lp, 0.8, backend="scan")
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(full.logZ),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(full.c_avg),
                               atol=1e-4)


def test_dag_pallas_padded_arcs_zero_cotangent():
    """Ragged DAG batches: gradients through the DAG Pallas path put
    exactly zero cotangent on padded arc scores (lat.lm), both modes."""
    lat, lp = _dag_batch(6)
    pad = ~np.asarray(lat.arc_mask)
    assert pad.any()
    for acc in ("full", "loss_only"):
        def f(lm):
            st = lattice_stats(lat._replace(lm=lm), lp, 1.0,
                               backend="pallas", accumulators=acc)
            return jnp.sum(st.logZ) + jnp.sum(st.c_avg)

        g = np.asarray(jax.grad(f)(lat.lm))
        assert np.isfinite(g).all(), acc
        assert np.abs(g[pad]).max() == 0.0, acc
        assert np.abs(g[~pad]).max() > 0.0, acc


def test_forward_backward_shim_matches_engine():
    lat, lp = _uniform_batch(4)
    a = forward_backward(lat, lp, kappa=1.0)
    b = lattice_stats(lat, lp, 1.0, backend="scan")
    for field in ARC_FIELDS + UTT_FIELDS:
        np.testing.assert_allclose(np.asarray(getattr(a, field)),
                                   np.asarray(getattr(b, field)), atol=0.0)


def test_sausage_kernels_match_refs():
    """Masked fwd+bwd Pallas kernels == pure-jnp oracles (replaces the
    hypothesis-gated sweep for containers without hypothesis)."""
    key = jax.random.PRNGKey(0)
    B, S, A = 3, 6, 4
    sc = jax.random.normal(key, (B, S, A))
    co = (jax.random.uniform(jax.random.fold_in(key, 1), (B, S, A)) > 0.5
          ).astype(jnp.float32)
    mask = np.ones((B, S, A), np.float32)
    mask[0, 4:, :] = 0             # fully-masked trailing segments
    mask[1, 2, 1:] = 0             # partially-masked segment
    mask = jnp.asarray(mask)
    for got, want in zip(ops.sausage_forward(sc, co, mask),
                         ref.sausage_forward_ref(sc, co, mask)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)
    for got, want in zip(ops.sausage_backward(sc, co, mask),
                         ref.sausage_backward_ref(sc, co, mask)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)
