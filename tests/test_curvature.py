"""Curvature product tests: GNVP/FVP vs explicit matrices (Secs. 3.4, 5.2)."""
import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.curvature import grad_and_loss, make_curvature_ops
from repro.losses.sequence import CELoss


@pytest.fixture()
def tiny_problem(key):
    D, K = 4, 5
    params = {"w": jax.random.normal(key, (D, K)) * 0.3,
              "b": jnp.zeros((K,))}
    batch = {"x": jax.random.normal(jax.random.fold_in(key, 1), (2, 3, D)),
             "labels": jax.random.randint(jax.random.fold_in(key, 2),
                                          (2, 3), 0, K)}

    def fwd(p, b):
        return jnp.tanh(b["x"]) @ p["w"] + p["b"], 0.0

    return params, batch, fwd


def _explicit_matrix(fwd, loss, params, batch, factor_name):
    """Build J^T H^ J explicitly via basis vectors."""
    flat, unravel = jax.flatten_util.ravel_pytree(params)
    D = flat.shape[0]

    def f(theta):
        return fwd(unravel(theta), batch)[0].reshape(-1)

    J = jax.jacfwd(f)(flat)                                  # (BTK, D)
    logits = fwd(params, batch)[0]
    BTK = logits.size
    H = []
    factor = getattr(loss, factor_name)
    for i in range(BTK):
        u = jnp.zeros(BTK).at[i].set(1.0).reshape(logits.shape)
        H.append(factor(logits, batch, u).reshape(-1))
    H = jnp.stack(H, 1)
    return J.T @ H @ J, unravel


@pytest.mark.parametrize("mode", ["linearize", "rematvp"])
@pytest.mark.parametrize("factor", ["gn_vp", "fisher_vp"])
def test_products_match_explicit(tiny_problem, key, mode, factor):
    params, batch, fwd = tiny_problem
    loss = CELoss()
    G, unravel = _explicit_matrix(fwd, loss, params, batch, factor)
    ops = make_curvature_ops(fwd, loss, params, batch, stabilize=False,
                             mode=mode)
    flat, _ = jax.flatten_util.ravel_pytree(params)
    v_flat = jax.random.normal(jax.random.fold_in(key, 9), flat.shape)
    v = unravel(v_flat)
    out = ops.gnvp(v) if factor == "gn_vp" else ops.fvp(v)
    out_flat, _ = jax.flatten_util.ravel_pytree(out)
    np.testing.assert_allclose(np.asarray(out_flat),
                               np.asarray(G @ v_flat), rtol=1e-4, atol=1e-5)


def test_rematvp_equals_linearize(tiny_problem, key):
    params, batch, fwd = tiny_problem
    loss = CELoss()
    v = jax.tree.map(lambda x: jax.random.normal(key, x.shape), params)
    a = make_curvature_ops(fwd, loss, params, batch, mode="linearize").gnvp(v)
    b = make_curvature_ops(fwd, loss, params, batch, mode="rematvp").gnvp(v)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5)


def test_gn_equals_hessian_for_matching_loss(key):
    """For softmax+CE (a matching loss) and a LINEAR model, GN == Hessian."""
    D, K = 3, 4
    params = {"w": jax.random.normal(key, (D, K)) * 0.5}
    batch = {"x": jax.random.normal(jax.random.fold_in(key, 1), (1, 2, D)),
             "labels": jnp.array([[0, 2]])}

    def fwd(p, b):
        return b["x"] @ p["w"], 0.0          # linear => GN exact

    loss = CELoss()
    flat, unravel = jax.flatten_util.ravel_pytree(params)
    obj = lambda t: loss.value(fwd(unravel(t), batch)[0], batch)[0]  # noqa: E731
    H = jax.hessian(obj)(flat)
    ops = make_curvature_ops(fwd, loss, params, batch, stabilize=False)
    v_flat = jax.random.normal(jax.random.fold_in(key, 5), flat.shape)
    gv = ops.gnvp(unravel(v_flat))
    gv_flat, _ = jax.flatten_util.ravel_pytree(gv)
    np.testing.assert_allclose(np.asarray(gv_flat), np.asarray(H @ v_flat),
                               rtol=1e-4, atol=1e-6)


def test_grad_and_loss_matches_autodiff(tiny_problem):
    params, batch, fwd = tiny_problem
    loss = CELoss()
    l, metrics, grads = grad_and_loss(fwd, loss, params, batch)
    ref = jax.grad(lambda p: loss.value(fwd(p, batch)[0], batch)[0])(params)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


def test_eval_loss_includes_aux(tiny_problem):
    """Regression: eval_loss dropped the scaled auxiliary loss (e.g. MoE
    router aux), so Alg. 1 candidate selection / reject_worse compared a
    DIFFERENT objective than the ``loss + aux`` grad_and_loss minimises.
    At Δθ = 0 the candidate objective must equal the training objective."""
    params, batch, fwd0 = tiny_problem
    fwd = lambda p, b: (fwd0(p, b)[0], jnp.float32(0.37))    # noqa: E731
    loss = CELoss()
    obj, _, _ = grad_and_loss(fwd, loss, params, batch)
    ops = make_curvature_ops(fwd, loss, params, batch)
    zero = jax.tree.map(jnp.zeros_like, params)
    np.testing.assert_allclose(float(ops.eval_loss(zero)), float(obj),
                               rtol=1e-6)
    # and the aux really is in there (not cancelled to the plain loss)
    plain = loss.value(fwd(params, batch)[0], batch)[0]
    assert abs(float(ops.eval_loss(zero)) - float(plain) - 0.37) < 1e-6


def test_fisher_psd(tiny_problem, key):
    """F = sum g g^T is PSD: v^T F v >= 0 for random v."""
    params, batch, fwd = tiny_problem
    ops = make_curvature_ops(fwd, CELoss(), params, batch, stabilize=False)
    from repro.core import tree_math as tm
    for i in range(5):
        v = jax.tree.map(
            lambda x: jax.random.normal(jax.random.fold_in(key, i), x.shape),
            params)
        assert float(tm.vdot(v, ops.fvp(v))) >= -1e-6


# --------------------------------------------------------------------------
# the lattice losses' prepared factor: built once per CG stage
# --------------------------------------------------------------------------

def _lattice_problem(K=7, B=3, T=12, seed=0):
    from repro.data.synthetic import asr_batch
    batch = asr_batch(seed, batch=B, num_frames=T, num_states=K, input_dim=5)
    k = jax.random.PRNGKey(seed + 11)
    logits = jax.random.normal(k, (B, T, K)) * 2.0
    u = jax.random.normal(jax.random.fold_in(k, 1), (B, T, K))
    return logits, batch, u


def _per_call_factor(loss, logits, batch, u, kind):
    """The GN / Fisher factor products as one call builds them from
    scratch: the lattice gradient, the masked one-hot reference and the
    weights, all at ``logits``."""
    from repro.losses.lattice import lattice_frame_counts, lattice_frame_mask
    from repro.losses.sequence import MMILoss, MPELoss
    lat = batch["lattice"]
    mmi = MMILoss(loss.kappa)
    y = jax.nn.one_hot(lat.ref_states, logits.shape[-1],
                       dtype=jnp.float32) * lattice_frame_mask(lat)[..., None]
    frames = jnp.maximum(jnp.sum(lattice_frame_counts(lat)), 1.0)
    if kind == "fisher":
        g = mmi.logit_grad(logits, batch)
        return frames * g * jnp.sum(g * u, -1, keepdims=True)
    g = loss.logit_grad(logits, batch)
    yu = jnp.sum(y * u, -1, keepdims=True)
    if isinstance(loss, MPELoss):
        w = (1.0 / (logits.shape[0] * jnp.maximum(lat.num_ref_units, 1.0))
             )[:, None, None]
        return loss.kappa ** 2 * w * (y * u) + loss.kappa * g * yu
    return loss.kappa ** 2 / frames * (y * u) + loss.kappa * g * yu


@pytest.mark.parametrize("kind", ["gn", "fisher"])
@pytest.mark.parametrize("name", ["mpe", "mmi"])
def test_prepared_factor_matches_per_call(name, kind):
    from repro.losses.sequence import get_loss
    loss = get_loss(name, kappa=0.6)
    logits, batch, u = _lattice_problem()
    gn, fisher = loss.curvature_factors(logits, batch)
    apply = gn if kind == "gn" else fisher
    want = _per_call_factor(loss, logits, batch, u, kind)
    assert float(jnp.max(jnp.abs(want))) > 0
    # one prepared factor serves every u
    for i in range(3):
        ui = u * (i + 1.0) - 0.5 * i
        np.testing.assert_allclose(
            np.asarray(apply(ui)),
            np.asarray(_per_call_factor(loss, logits, batch, ui, kind)),
            rtol=1e-5, atol=1e-9)
    wrapper = loss.gn_vp if kind == "gn" else loss.fisher_vp
    np.testing.assert_allclose(np.asarray(wrapper(logits, batch, u)),
                               np.asarray(want), rtol=1e-5, atol=1e-9)


def _acoustic_problem(arch):
    from repro.configs.acoustic import LSTM, TDNN_SIGMOID
    from repro.data.synthetic import asr_batch
    from repro.launch.steps import acoustic_forward_fn
    from repro.models import acoustic
    cfg = (LSTM if arch == "lstm" else TDNN_SIGMOID).smoke().replace(
        hidden_dim=8, num_outputs=9)
    params = acoustic.init_params(cfg, jax.random.PRNGKey(3))
    batch = asr_batch(5, batch=4, num_frames=12, num_states=cfg.num_outputs,
                      input_dim=cfg.input_dim)
    return acoustic_forward_fn(cfg), params, batch


def _per_product(fwd, loss, params, batch, v, kind):
    """G v / F v as one product computes it with the factor built at its
    own primal output: jvp, factor, vjp."""
    f = lambda p: fwd(p, batch)[0]                          # noqa: E731
    out, jv = jax.jvp(f, (params,), (v,))
    hu = _per_call_factor(loss, out, batch, jv, kind)
    return jax.vjp(f, params)[1](hu)[0]


@pytest.mark.parametrize("mode,sample", [("rematvp", 1.0),
                                         ("linearize", 1.0),
                                         ("rematvp", 0.5)])
@pytest.mark.parametrize("arch", ["lstm", "tdnn"])
def test_products_match_per_product_factor(arch, mode, sample):
    from repro.core.curvature import subsample_batch
    from repro.losses.sequence import MPELoss
    fwd, params, batch = _acoustic_problem(arch)
    loss = MPELoss(kappa=0.5)
    ops = make_curvature_ops(fwd, loss, params, batch, stabilize=False,
                             mode=mode, curvature_sample=sample)
    curv = batch if sample >= 1.0 else subsample_batch(batch, sample)
    k = jax.random.PRNGKey(7)
    v = jax.tree.map(lambda x: jax.random.normal(k, x.shape, x.dtype),
                     params)
    for kind, prod in (("gn", ops.gnvp), ("fisher", ops.fvp)):
        got = jax.jit(prod)(v)
        want = jax.jit(lambda vv: _per_product(fwd, loss, params, curv, vv,
                                               kind))(v)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            scale = float(jnp.max(jnp.abs(b)))
            assert scale > 0
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6 * scale)


@pytest.mark.parametrize("ng_iters,cg_iters", [(4, 8), (2, 3)])
def test_cg_stage_runs_the_lattice_gradients_once(monkeypatch, ng_iters,
                                                  cg_iters):
    """Tracing one NGHF MPE update, the CG stage calls the lattice
    statistics twice (G_mpe and G_mmi), both while the factors are built
    before any solve, and no product calls them: a product traced in a
    solve's loop body would run its lattice gradient every iteration."""
    import repro.core.optim.second_order as so
    import repro.losses.sequence as seq
    from repro.configs.acoustic import LSTM
    from repro.core.optim import SecondOrderConfig
    from repro.data.synthetic import asr_batch
    from repro.launch.steps import build_sequence_step
    from repro.models import acoustic

    phase, calls = ["other"], []
    real_stats, real_ops = seq.lattice_stats, so.make_curvature_ops

    def counted_stats(*a, **kw):
        calls.append(phase[0])
        return real_stats(*a, **kw)

    def within(name, fn):
        def run(*a, **kw):
            was, phase[0] = phase[0], name
            try:
                return fn(*a, **kw)
            finally:
                phase[0] = was
        return run

    def counted_ops(*a, **kw):
        ops = within("build", real_ops)(*a, **kw)
        return ops._replace(gnvp=within("product", ops.gnvp),
                            fvp=within("product", ops.fvp),
                            eval_loss=within("eval", ops.eval_loss))

    monkeypatch.setattr(seq, "lattice_stats", counted_stats)
    monkeypatch.setattr(so, "make_curvature_ops", counted_ops)
    cfg = LSTM.smoke().replace(hidden_dim=8, num_outputs=9)
    params = acoustic.init_params(cfg, jax.random.PRNGKey(0))
    fn, opt = build_sequence_step(
        cfg, SecondOrderConfig(method="nghf", cg_iters=cg_iters,
                               ng_iters=ng_iters),
        loss="mpe", kappa=0.5,
        share_counts=acoustic.share_counts(cfg, params))
    kw = dict(num_frames=12, num_states=cfg.num_outputs,
              input_dim=cfg.input_dim)
    jax.make_jaxpr(fn)(params, opt.init(params), asr_batch(0, batch=4, **kw),
                       asr_batch(1, batch=2, **kw))
    assert calls.count("build") == 2
    assert calls.count("product") == 0
    assert calls.count("other") == 1            # the gradient stage
    assert calls.count("eval") >= 1             # the candidates


def test_ce_keeps_the_per_product_factor(tiny_problem, key, monkeypatch):
    """CE (and the chunked LM loss) have no lattice recursion to save:
    their factor runs inside every product, at its primal output."""
    from repro.losses.chunked_lm import ChunkedCELoss
    assert not hasattr(CELoss, "curvature_factors")
    assert not hasattr(ChunkedCELoss, "curvature_factors")
    params, batch, fwd = tiny_problem
    seen = {"gn": 0, "fisher": 0}

    def counting(kind, real):
        def fn(self, logits, b, u):
            seen[kind] += 1
            return real(self, logits, b, u)
        return fn

    monkeypatch.setattr(CELoss, "gn_vp", counting("gn", CELoss.gn_vp))
    monkeypatch.setattr(CELoss, "fisher_vp",
                        counting("fisher", CELoss.fisher_vp))
    ops = make_curvature_ops(fwd, CELoss(), params, batch)
    assert seen == {"gn": 0, "fisher": 0}
    v = jax.tree.map(lambda x: jax.random.normal(key, x.shape), params)
    ops.gnvp(v)
    ops.gnvp(v)
    ops.fvp(v)
    assert seen == {"gn": 2, "fisher": 1}
