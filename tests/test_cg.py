"""CG engine unit + property tests (paper Alg. 1, Secs. 4.2/4.3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given, settings, st  # hypothesis, or skip-shim

from repro.core import tree_math as tm
from repro.core.cg import cg_solve


def _spd(rng, n, cond=10.0):
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    eig = np.linspace(1.0, cond, n)
    return (q * eig) @ q.T


def test_cg_matches_dense_solve(rng):
    n = 24
    A = _spd(rng, n)
    b = rng.standard_normal(n).astype(np.float32)
    res = cg_solve(lambda v: {"x": jnp.asarray(A, jnp.float32) @ v["x"]},
                   {"x": jnp.asarray(b)}, iters=n + 5)
    np.testing.assert_allclose(np.asarray(res.x["x"]),
                               np.linalg.solve(A, b), rtol=1e-3, atol=1e-4)


def test_preconditioned_cg_same_solution(rng):
    n = 16
    A = _spd(rng, n)
    b = rng.standard_normal(n).astype(np.float32)
    counts = {"x": jnp.asarray(rng.uniform(1, 8, n), jnp.float32)}
    res = cg_solve(lambda v: {"x": jnp.asarray(A, jnp.float32) @ v["x"]},
                   {"x": jnp.asarray(b)}, iters=n + 5, precond=counts)
    np.testing.assert_allclose(np.asarray(res.x["x"]),
                               np.linalg.solve(A, b), rtol=1e-3, atol=1e-4)


def test_preconditioner_speeds_ill_conditioned_diag(rng):
    """Diagonal preconditioning with the true diagonal solves a diagonal
    system in one effective step — the Sec. 4.3 mechanism."""
    n = 32
    d = np.geomspace(1, 1e4, n).astype(np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    bv = lambda v: {"x": jnp.asarray(d) * v["x"]}           # noqa: E731
    plain = cg_solve(bv, {"x": jnp.asarray(b)}, iters=4)
    pre = cg_solve(bv, {"x": jnp.asarray(b)}, iters=4,
                   precond={"x": jnp.asarray(d)})
    x_true = b / d
    err_plain = float(jnp.linalg.norm(plain.x["x"] - x_true))
    err_pre = float(jnp.linalg.norm(pre.x["x"] - x_true))
    assert err_pre < err_plain * 0.1


def test_negative_curvature_freezes(rng):
    n = 8
    A = -np.eye(n, dtype=np.float32)                         # negative definite
    b = rng.standard_normal(n).astype(np.float32)
    res = cg_solve(lambda v: {"x": jnp.asarray(A) @ v["x"]},
                   {"x": jnp.asarray(b)}, iters=5)
    # all curvature values non-positive => x stays 0
    assert np.all(np.asarray(res.curv) <= 0)
    np.testing.assert_allclose(np.asarray(res.x["x"]), 0.0)


@pytest.mark.parametrize("tol", [0.0, 1e-9])
def test_evals_stop_where_the_curvature_guard_fires(tol):
    """diag(1, 3, -1) against b = (1, 1, 0.2): v^T B v turns negative at
    iteration 2, so the two iterates before it are the only candidates
    evaluated.  The fixed-budget scan still runs all 5 iterations; the
    adaptive loop stops on the guard after 3."""
    A = jnp.asarray(np.diag([1.0, 3.0, -1.0]), jnp.float32)
    b = jnp.asarray([1.0, 1.0, 0.2], jnp.float32)
    res = cg_solve(lambda v: {"x": A @ v["x"]}, {"x": b}, iters=5,
                   eval_fn=lambda x: 0.5 * x["x"] @ (A @ x["x"])
                   - x["x"] @ b, eval_every=1, tol=tol)
    curv = np.asarray(res.curv)
    assert np.all(curv[:2] > 0) and curv[2] <= 0
    assert int(res.evals) == 2
    assert int(np.isfinite(np.asarray(res.losses)).sum()) == 2
    assert int(res.iters_used) == (5 if tol == 0.0 else 3)


@pytest.mark.parametrize("eval_every,evals", [(1, 6), (2, 4), (None, 0)])
def test_evals_count_a_normal_solve(eval_every, evals):
    """Without the guard every iterate on the stride is evaluated, and the
    final one always: 6 iterations evaluate 6, at stride 2 iterations 0,
    2, 4 and the final 5; without eval_fn none."""
    n = 8
    rng = np.random.default_rng(13)  # the shared rng fixture feeds later tests
    A = jnp.asarray(_spd(rng, n), jnp.float32)
    b = {"x": jnp.asarray(rng.standard_normal(n), jnp.float32)}
    res = cg_solve(lambda v: {"x": A @ v["x"]}, b, iters=6,
                   eval_fn=(lambda x: jnp.sum(x["x"] ** 2))
                   if eval_every else None, eval_every=eval_every or 1)
    assert int(res.iters_used) == 6
    assert int(res.evals) == evals


def test_candidate_selection_picks_best():
    # eval_fn rewards a specific iteration count
    A = np.diag(np.linspace(1, 3, 6)).astype(np.float32)
    b = np.ones(6, np.float32)

    def eval_fn(x):
        # loss minimised when ||x|| close to 0.3
        return jnp.abs(tm.norm(x) - 0.3)

    res = cg_solve(lambda v: {"x": jnp.asarray(A) @ v["x"]},
                   {"x": jnp.asarray(b)}, iters=6, eval_fn=eval_fn)
    losses = np.asarray(res.losses)
    assert np.isclose(float(res.best_loss), np.nanmin(losses), atol=1e-6)
    assert int(res.best_iter) == int(np.nanargmin(losses))


def test_final_iterate_always_evaluated():
    """Regression: with eval_every > 1 and (iters - 1) % eval_every != 0
    the deepest candidate used to be silently skipped — the final iterate
    must ALWAYS be evaluated and win when it is the best."""
    n = 6
    A = np.diag(np.linspace(1, 3, n)).astype(np.float32)
    b = np.ones(n, np.float32)
    res = cg_solve(lambda v: {"x": jnp.asarray(A) @ v["x"]},
                   {"x": jnp.asarray(b)}, iters=4, eval_every=3,
                   eval_fn=lambda x: -tm.norm(x))
    losses = np.asarray(res.losses)
    assert losses.shape == (4,)                   # history shape unchanged
    assert np.isfinite(losses[0])                 # m=0: on the stride
    assert np.isinf(losses[1]) and np.isinf(losses[2])   # strided out
    assert np.isfinite(losses[3])                 # final iterate: evaluated
    # and selection sees it: best == argmin over the evaluated candidates
    assert int(res.best_iter) == int(np.nanargmin(
        np.where(np.isfinite(losses), losses, np.nan)))
    assert np.isclose(float(res.best_loss), np.nanmin(
        np.where(np.isfinite(losses), losses, np.nan)), atol=1e-6)


def test_quadratic_model_monotone(rng):
    """CG decreases the quadratic model monotonically on SPD systems."""
    n = 20
    A = _spd(rng, n, cond=50)
    b = rng.standard_normal(n).astype(np.float32)
    res = cg_solve(lambda v: {"x": jnp.asarray(A, jnp.float32) @ v["x"]},
                   {"x": jnp.asarray(b)}, iters=15)
    quad = np.asarray(res.quad)
    assert np.all(np.diff(quad) <= 1e-4)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 30), cond=st.floats(1.5, 1e3),
       seed=st.integers(0, 1000))
def test_cg_property_solves_spd(n, cond, seed):
    rng = np.random.default_rng(seed)
    A = _spd(rng, n, cond)
    b = rng.standard_normal(n).astype(np.float32)
    res = cg_solve(lambda v: {"x": jnp.asarray(A, jnp.float32) @ v["x"]},
                   {"x": jnp.asarray(b)}, iters=2 * n + 10)
    err = np.linalg.norm(np.asarray(res.x["x"]) - np.linalg.solve(A, b))
    assert err < 1e-2 * max(1.0, np.linalg.norm(b))


# ---------------------------------------------------------------------------
# adaptive iteration budget (tol > 0)
# ---------------------------------------------------------------------------

def _two_leaf_system(rng, n=24, cond=10.0):
    A = _spd(rng, n, cond)
    bvec = rng.standard_normal(n).astype(np.float32)
    k = n // 2
    b = {"a": jnp.asarray(bvec[:k]), "c": jnp.asarray(bvec[k:])}

    def bv(v):
        flat = jnp.concatenate([v["a"], v["c"]])
        out = jnp.asarray(A, jnp.float32) @ flat
        return {"a": out[:k], "c": out[k:]}

    def unflat(res_x):
        return np.concatenate([np.asarray(res_x["a"]), np.asarray(res_x["c"])])

    return A, bvec, b, bv, unflat


def test_adaptive_budget_stops_early_within_ceiling(rng):
    """On an easy system the relative-improvement criterion fires well
    before the ceiling; the solution is still accurate and iters_used
    never exceeds the configured max."""
    A, bvec, b, bv, unflat = _two_leaf_system(rng, n=24, cond=5.0)
    res = cg_solve(bv, b, iters=30, tol=1e-4)
    used = int(res.iters_used)
    assert 1 <= used < 30
    x_star = np.linalg.solve(A, bvec)
    err = np.linalg.norm(unflat(res.x) - x_star)
    assert err <= 0.02 * (1.0 + np.linalg.norm(x_star))
    # unexecuted history rows are inert: NaN quad/curv, inf losses
    assert np.all(np.isnan(np.asarray(res.quad)[used:]))
    assert np.all(np.isinf(np.asarray(res.losses)[used:]))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 500), tol=st.floats(1e-6, 0.5),
       iters=st.integers(1, 20))
def test_adaptive_budget_never_exceeds_max(seed, tol, iters):
    rng = np.random.default_rng(seed)
    A, bvec, b, bv, _ = _two_leaf_system(rng, n=16, cond=50.0)
    res = cg_solve(bv, b, iters=iters, tol=tol)
    assert 1 <= int(res.iters_used) <= iters


def test_adaptive_zero_tol_keeps_fixed_budget(rng):
    """tol=0 is the historical fixed-budget scan: every iteration runs."""
    _, _, b, bv, _ = _two_leaf_system(rng)
    res = cg_solve(bv, b, iters=7, tol=0.0)
    assert int(res.iters_used) == 7
    assert np.isfinite(np.asarray(res.quad)).all()


def test_adaptive_matches_fixed_at_equal_depth(rng):
    """With a tolerance tight enough to never fire, the while_loop path
    produces the same iterates as the scan path."""
    A, bvec, b, bv, unflat = _two_leaf_system(rng, n=20, cond=200.0)
    fixed = cg_solve(bv, b, iters=6)
    adap = cg_solve(bv, b, iters=6, tol=1e-12)
    assert int(adap.iters_used) == 6
    np.testing.assert_allclose(unflat(adap.x), unflat(fixed.x), rtol=1e-6,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(adap.quad), np.asarray(fixed.quad),
                               rtol=1e-6, atol=1e-7)


def test_adaptive_final_iterate_always_evaluated(rng):
    """With eval_every > 1 the adaptively-chosen final iterate still gets
    evaluated (post-loop) and competes for selection."""
    _, _, b, bv, _ = _two_leaf_system(rng, n=12, cond=3.0)
    res = cg_solve(bv, b, iters=20, tol=1e-3, eval_every=5,
                   eval_fn=lambda x: -tm.norm(x))
    used = int(res.iters_used)
    assert used < 20
    losses = np.asarray(res.losses)
    assert np.isfinite(losses[used - 1])      # deepest candidate evaluated
    finite = np.where(np.isfinite(losses), losses, np.nan)
    assert int(res.best_iter) == int(np.nanargmin(finite))


def test_adaptive_stops_on_negative_curvature(rng):
    """The while_loop exits on the curvature guard instead of spinning
    no-op iterations."""
    n = 8
    A = -np.eye(n, dtype=np.float32)
    b = {"x": jnp.asarray(rng.standard_normal(n), jnp.float32)}
    res = cg_solve(lambda v: {"x": jnp.asarray(A) @ v["x"]}, b, iters=9,
                   tol=1e-6)
    assert int(res.iters_used) == 1
    np.testing.assert_allclose(np.asarray(res.x["x"]), 0.0)


def test_adaptive_warm_start_uses_fewer_iterations(rng):
    """The warm-start payoff the fixed budget could never show: starting
    near the solution, the relative-improvement criterion fires earlier
    at an equally good solution."""
    A, bvec, b, bv, unflat = _two_leaf_system(rng, n=24, cond=300.0)
    x_star = np.linalg.solve(A, bvec)
    k = len(bvec) // 2
    x0 = {"a": jnp.asarray(x_star[:k] * 0.99, jnp.float32),
          "c": jnp.asarray(x_star[k:] * 0.99, jnp.float32)}
    cold = cg_solve(bv, b, iters=30, tol=1e-4)
    warm = cg_solve(bv, b, iters=30, tol=1e-4, x0=x0)
    assert int(warm.iters_used) < int(cold.iters_used)
    # the early stop trades a few iterations for a slightly looser solve;
    # the warm answer must still be a good solution in absolute terms
    err_w = np.linalg.norm(unflat(warm.x) - x_star)
    assert err_w <= 0.05 * (1.0 + np.linalg.norm(x_star))


# ---------------------------------------------------------------------------
# fused flat-buffer vector work (fused=True)
# ---------------------------------------------------------------------------

def test_fused_matches_unfused_with_precond_and_eval(rng):
    """Fused mode (flat buffer + cg_fused_update kernel) reproduces the
    pytree path: iterates, preconditioned residuals, candidate selection —
    with a legacy count-tree preconditioner and an eval_fn in play."""
    A, bvec, b, bv, unflat = _two_leaf_system(rng, n=20, cond=40.0)
    counts = {"a": jnp.asarray(rng.uniform(1, 8, 10), jnp.float32),
              "c": jnp.asarray(rng.uniform(1, 8, 10), jnp.float32)}
    evf = lambda x: jnp.abs(tm.norm(x) - 0.3)                # noqa: E731
    plain = cg_solve(bv, b, iters=8, precond=counts, eval_fn=evf)
    fused = cg_solve(bv, b, iters=8, precond=counts, eval_fn=evf,
                     fused=True)
    np.testing.assert_allclose(unflat(fused.x), unflat(plain.x), rtol=2e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(fused.resid),
                               np.asarray(plain.resid), rtol=2e-4)
    assert int(fused.best_iter) == int(plain.best_iter)


def test_fused_identity_precond_matches_plain(rng):
    """Identity-preconditioner fast path: the kernel's exact blockwise
    <r,r> stands in for <r,z> — same solution as the pytree path."""
    A, bvec, b, bv, unflat = _two_leaf_system(rng, n=16, cond=12.0)
    plain = cg_solve(bv, b, iters=10)
    fused = cg_solve(bv, b, iters=10, fused=True)
    np.testing.assert_allclose(unflat(fused.x), unflat(plain.x), rtol=2e-5,
                               atol=1e-6)


def test_fused_adaptive_compose(rng):
    """fused + tol compose: early stop with the flat-buffer vector work,
    result unravelled back to the pytree structure."""
    A, bvec, b, bv, unflat = _two_leaf_system(rng, n=24, cond=5.0)
    res = cg_solve(bv, b, iters=30, tol=1e-4, fused=True)
    assert int(res.iters_used) < 30
    assert set(res.x) == {"a", "c"}               # pytree structure back
    np.testing.assert_allclose(unflat(res.x), np.linalg.solve(A, bvec),
                               rtol=1e-2, atol=1e-3)


def test_fused_with_constrain_matches_plain(rng):
    """fused + constrain is the sharded per-leaf fused path (flat ravel is
    inexpressible for GSPMD over 2d-sharded leaves): same iterates,
    residual history and candidate selection as the pytree path — with a
    legacy count-tree preconditioner, tol and warm start all in play.
    (This used to raise; second-order configs no longer have to choose
    between ``cg_fused`` and a mesh.)"""
    A, bvec, b, bv, unflat = _two_leaf_system(rng, n=20, cond=40.0)
    counts = {"a": jnp.asarray(rng.uniform(1, 8, 10), jnp.float32),
              "c": jnp.asarray(rng.uniform(1, 8, 10), jnp.float32)}
    x0 = {"a": jnp.asarray(rng.standard_normal(10) * 0.1, jnp.float32),
          "c": jnp.asarray(rng.standard_normal(10) * 0.1, jnp.float32)}
    kw = dict(iters=12, tol=1e-4, precond=counts, x0=x0)
    plain = cg_solve(bv, b, **kw)
    tree = cg_solve(bv, b, fused=True, constrain=lambda t: t, **kw)
    assert set(tree.x) == {"a", "c"}              # pytree structure kept
    assert int(tree.iters_used) == int(plain.iters_used)
    assert int(tree.best_iter) == int(plain.best_iter)
    np.testing.assert_allclose(unflat(tree.x), unflat(plain.x), rtol=2e-4,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(tree.resid),
                               np.asarray(plain.resid), rtol=2e-4, atol=1e-7)
    # and the identity-precond fast path (<r,r> doubling as <r,z>)
    plain_id = cg_solve(bv, b, iters=10)
    tree_id = cg_solve(bv, b, iters=10, fused=True, constrain=lambda t: t)
    np.testing.assert_allclose(unflat(tree_id.x), unflat(plain_id.x),
                               rtol=2e-5, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), scale=st.floats(1e-8, 1e8))
def test_stabilize_rescaling_invariance(seed, scale):
    """Sec. 4.2: the ||θ||/||v|| rescaling is algebraically a no-op in f32
    over a huge range of v scales."""
    from repro.core.curvature import make_curvature_ops
    from repro.losses.sequence import CELoss

    key = jax.random.PRNGKey(seed)
    params = {"w": jax.random.normal(key, (5, 7)) * 0.2}
    batch = {"x": jax.random.normal(jax.random.fold_in(key, 1), (2, 3, 5)),
             "labels": jax.random.randint(jax.random.fold_in(key, 2),
                                          (2, 3), 0, 7)}
    fwd = lambda p, b: (jnp.tanh(b["x"]) @ p["w"], 0.0)     # noqa: E731
    ops = make_curvature_ops(fwd, CELoss(), params, batch, stabilize=True)
    v = {"w": jax.random.normal(jax.random.fold_in(key, 3), (5, 7)) * scale}
    gv = ops.gnvp(v)
    gv_unit = ops.gnvp(jax.tree.map(lambda x: x / scale, v))
    np.testing.assert_allclose(np.asarray(gv["w"]) / scale,
                               np.asarray(gv_unit["w"]), rtol=1e-3,
                               atol=1e-6 * scale if scale > 1 else 1e-9)
