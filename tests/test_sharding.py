"""Sharding rule + dry-run plumbing tests (no forced device count — these
verify specs structurally, not on 512 devices; the one exception is the
multi-device sequence-step equivalence test, which runs in a subprocess
with XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from conftest import given, settings, st  # hypothesis, or skip-shim
from repro.configs.base import INPUT_SHAPES, get_config, list_archs
from repro.launch.sharding import (input_shardings, lattice_pspec,
                                   lattice_shardings, param_pspec,
                                   param_shardings,
                                   sequence_input_shardings)
from repro.models.registry import get_model


class FakeMesh:
    """Structural stand-in with the production extents (16 x 16)."""
    axis_names = ("data", "model")
    shape = {"data": 16, "model": 16}


class FakePodMesh:
    """Structural stand-in for the multi-pod mesh (2 x 16 x 16)."""
    axis_names = ("pod", "data", "model")
    shape = {"pod": 2, "data": 16, "model": 16}


MESH = FakeMesh()


def test_divisibility_guard_drops_axes():
    cfg = get_config("qwen2-72b")
    # kv heads 8 % 16 != 0 -> wk output dim replicated
    spec = param_pspec(cfg, MESH, ["periods", "slot0", "attn", "wk"],
                       (80, 8192, 1024))
    assert spec == P(None, "data", None)
    # wq shards heads
    spec = param_pspec(cfg, MESH, ["periods", "slot0", "attn", "wq"],
                       (80, 8192, 8192))
    assert spec == P(None, "data", "model")


def test_vocab_never_data_sharded():
    cfg = get_config("minitron-8b")
    spec = param_pspec(cfg, MESH, ["embed", "table"], (256000, 4096))
    assert spec == P("model", None)
    cfg = get_config("granite-moe-3b-a800m")    # 49155 % 16 != 0
    spec = param_pspec(cfg, MESH, ["embed", "table"], (49155, 1536))
    assert spec == P(None, None)


def test_moe_expert_sharding_by_divisibility():
    mix = get_config("mixtral-8x22b")           # 8 experts: shard d_ff
    spec = param_pspec(mix, MESH, ["periods", "slot0", "moe", "w_in"],
                       (56, 8, 6144, 16384))
    assert spec == P(None, None, "data", "model")
    gran = get_config("granite-moe-3b-a800m")   # 40 experts: shard d_ff too
    spec = param_pspec(gran, MESH, ["periods", "slot0", "moe", "w_in"],
                       (32, 40, 1536, 512))
    assert spec == P(None, None, "data", "model")


def test_replicated_mode_is_fully_replicated():
    cfg = get_config("qwen2-72b").replace(param_sharding="replicated")
    spec = param_pspec(cfg, MESH, ["embed", "table"], (152064, 8192))
    assert spec == P()


def test_unstacked_specs_match_fsdp_gather():
    """fsdp.make_spec_fn must spec the UN-stacked slice shapes."""
    cfg = get_config("qwen2-72b")
    stacked = param_pspec(cfg, MESH, ["periods", "slot0", "mlp", "w_in"],
                          (80, 8192, 29568))
    unstacked = param_pspec(cfg.replace(param_sharding="1d"), MESH,
                            ["periods", "slot0", "mlp", "w_in"],
                            (8192, 29568), stacked=False)
    assert stacked == P(None, "data", "model")
    assert unstacked == P(None, "model")


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("shape", list(INPUT_SHAPES))
def test_input_specs_cover_all_combos(arch, shape):
    """Every (arch x shape) produces well-formed ShapeDtypeStruct stand-ins
    (the 40-combo grid of deliverable f) without touching devices."""
    from repro.launch.dryrun import applicable
    cfg = get_config(arch)
    if not applicable(cfg, shape):
        pytest.skip("inapplicable per DESIGN.md long_500k policy")
    model = get_model(cfg)
    specs = model.input_specs(shape)
    shp = INPUT_SHAPES[shape]
    if shp.mode in ("train", "prefill"):
        assert specs["tokens"].shape == (shp.global_batch, shp.seq_len)
    else:
        assert specs["tokens"].shape == (shp.global_batch, 1)
        assert "cache" in specs
        # long_500k caches must be bounded (sub-quadratic requirement)
        if shape == "long_500k":
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                    specs["cache"])[0]:
                name = str(getattr(path[-1], "key", ""))
                if name in ("k", "v"):
                    assert leaf.shape[-3] <= cfg.long_context_window, \
                        (arch, leaf.shape)


def test_param_shardings_tree_matches(key):
    cfg = get_config("xlstm-125m").smoke()
    model = get_model(cfg)
    shapes = model.param_shapes()
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    shard = param_shardings(cfg, mesh, shapes)
    assert jax.tree.structure(shard) == jax.tree.structure(shapes)


# ---------------------------------------------------------------------------
# Lattice / sequence-training sharding
# ---------------------------------------------------------------------------

def test_lattice_pspec_leading_dim_over_data_axes():
    """(B, A) / (B, A, P) / (B, L, W) lattice fields shard their leading
    batch dim over every data axis; trailing dims always replicate."""
    assert lattice_pspec(MESH, (32, 48)) == P(("data",), None)
    assert lattice_pspec(MESH, (32, 48, 3)) == P(("data",), None, None)
    assert lattice_pspec(MESH, (32, 16, 3)) == P(("data",), None, None)
    # multi-pod: batch over pod x data (the paper's master/worker split)
    pm = FakePodMesh()
    assert lattice_pspec(pm, (64, 48)) == P(("pod", "data"), None)


def test_lattice_pspec_divisibility_guard_matches_batch_pspec():
    """All-or-nothing guard: B that does not divide the FULL data extent
    replicates (no partial-axis fallback)."""
    assert lattice_pspec(MESH, (8, 48)) == P(None, None)        # 8 % 16 != 0
    pm = FakePodMesh()
    # 16 divides pod (2) and data (16) separately but not pod*data (32):
    # the lattice rule must NOT fall back to a partial axis
    assert lattice_pspec(pm, (16, 48)) == P(None, None)
    assert lattice_pspec(pm, (32, 48)) == P(("pod", "data"), None)
    assert lattice_pspec(pm, (64, 48)) == P(("pod", "data"), None)


def test_lattice_shardings_cover_every_field(key):
    from repro.losses.lattice import make_lattice_batch
    lat = make_lattice_batch(0, batch=4, num_frames=16, num_states=8)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    shard = lattice_shardings(mesh, lat)
    assert jax.tree.structure(shard) == jax.tree.structure(lat)
    for s, leaf in zip(jax.tree.leaves(shard), jax.tree.leaves(lat)):
        # B=4 divides data=1; compare as specs, which normalise a
        # one-axis tuple to the bare axis name
        assert P(s.spec[0]) == P(("data",)), s
        assert all(ax is None for ax in s.spec[1:])
        assert len(s.spec) == leaf.ndim


def test_sequence_input_shardings_batch_leading():
    from repro.data.synthetic import asr_batch
    b = asr_batch(0, batch=4, num_frames=16, num_states=8, input_dim=6)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    shard = sequence_input_shardings(mesh, b)
    assert shard["feats"].spec == P(("data",), None, None)
    assert shard["labels"].spec == P(("data",), None)
    assert shard["lattice"].preds.spec == P(("data",), None, None)
    assert shard["lattice"].level_arcs.spec == P(("data",), None, None)
    assert shard["lattice"].num_ref_units.spec == P(("data",))


@pytest.mark.slow
def test_sequence_step_matches_single_device():
    """A jitted build_sequence_step MPE/NGHF update on an 8-device CPU mesh
    (4-way data parallel) must match the single-device update to float
    tolerance.  Runs in a subprocess: the forced device count must be set
    before jax initialises."""
    script = textwrap.dedent("""
        import numpy as np, jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs.acoustic import LSTM
        from repro.core.optim import SecondOrderConfig
        from repro.data.synthetic import asr_batch
        from repro.launch.steps import build_sequence_step
        from repro.launch.sharding import sequence_input_shardings
        from repro.models import acoustic

        assert jax.device_count() >= 8, jax.device_count()
        acfg = LSTM.smoke().replace(hidden_dim=16, num_outputs=12)
        socfg = SecondOrderConfig(method="nghf", cg_iters=2, ng_iters=1)
        params = acoustic.init_params(acfg, jax.random.PRNGKey(0))
        counts = acoustic.share_counts(acfg, params)
        kw = dict(num_frames=16, num_states=12, input_dim=acfg.input_dim)
        gb = asr_batch(0, batch=8, **kw)
        cb = asr_batch(1, batch=4, **kw)

        fn1, opt1 = build_sequence_step(acfg, socfg, loss="mpe",
                                        kappa=0.5, share_counts=counts)
        p1, s1, m1 = jax.jit(fn1)(params, opt1.init(params), gb, cb)

        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2),
                    ("data", "model"))
        pshard = jax.tree.map(lambda _: NamedSharding(mesh, P()), params)
        fn2, opt2 = build_sequence_step(acfg, socfg, loss="mpe",
                                        kappa=0.5, mesh=mesh,
                                        state_sharding=pshard,
                                        share_counts=counts)
        params2 = jax.device_put(params, pshard)
        p2, s2, m2 = jax.jit(fn2)(
            params2, opt2.init(params2, state_sharding=pshard),
            jax.device_put(gb, sequence_input_shardings(mesh, gb)),
            jax.device_put(cb, sequence_input_shardings(mesh, cb)))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-5
        assert int(jax.tree.leaves(s2["step"])[0]) == 1
        print("SEQ_SHARD_OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SEQ_SHARD_OK" in out.stdout


@pytest.mark.slow
def test_lm_fsdp_nghf_step_matches_single_device():
    """The tentpole acceptance test: ONE NGHF update on the qwen smoke LM
    with 2d (FSDP) parameter storage over an 8-device (4 data x 2 model)
    CPU mesh must match the single-device update — same CG candidate
    selection, params allclose (relative-L2; measured headroom ~100x).
    Also pins the fisher_diag regression: the EMA diagonal coming OUT of
    the jitted step must carry the storage sharding (it used to be
    replicated — θ-sized, an OOM at mixtral scale)."""
    script = textwrap.dedent("""
        import jax, numpy as np
        from repro.configs.base import get_config
        from repro.core.optim import config_for
        from repro.data.synthetic import lm_batch
        from repro.data.pipeline import shard_batch
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.sharding import param_shardings
        from repro.launch.steps import build_step, jit_train_step
        from repro.models.registry import get_model

        assert jax.device_count() >= 8, jax.device_count()
        cfg = get_config("qwen2.5-3b").smoke().replace(
            param_sharding="2d", compute_dtype="float32")
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = lm_batch(0, batch=8, seq_len=16, vocab=cfg.vocab_size)
        ocfg = config_for("nghf", cg_iters=2, ng_iters=1,
                          preconditioner="fisher_diag", warm_start=True)

        fn1, opt1 = build_step(cfg, ocfg, cg_frac=2, min_cg=4)
        p1, s1, m1 = jax.jit(fn1)(params, opt1.init(params), batch)
        p1 = jax.device_get(p1)

        mesh = make_debug_mesh(4, 2)
        pshard = param_shardings(cfg, mesh, model.param_shapes())
        pp = jax.tree.map(jax.device_put, params, pshard)
        fn8, opt8 = build_step(cfg, ocfg, cg_frac=2, min_cg=4,
                               state_sharding=pshard, mesh=mesh)
        # jit_train_step donates (params, opt_state) exactly as the train
        # driver does; pp/s8 are dead after the call (never reused below).
        p8, s8, m8 = jit_train_step(fn8)(
            pp, opt8.init(pp, state_sharding=pshard),
            shard_batch(batch, mesh))
        p8 = jax.device_get(p8)

        assert int(m1["cg_best_iter"]) == int(m8["cg_best_iter"])
        assert abs(float(m1["loss"]) - float(m8["loss"])) < 1e-4
        a = np.concatenate([np.ravel(np.asarray(x, np.float64))
                            for x in jax.tree.leaves(p1)])
        c = np.concatenate([np.ravel(np.asarray(x, np.float64))
                            for x in jax.tree.leaves(p8)])
        rel_l2 = np.linalg.norm(a - c) / np.linalg.norm(a)
        assert rel_l2 < 1e-4, rel_l2
        np.testing.assert_allclose(c, a, rtol=1e-3, atol=3e-5)

        # θ-sized state OUT of the step keeps the 2d storage sharding
        # leaf-for-leaf (fisher_diag EMA diagonal + warm-start Δθ; norm
        # scales are legitimately replicated because their PARAM sharding
        # is too) — the fisher_diag regression showed up here as every d
        # leaf replicated.
        for tree in (s8["precond"]["d"], s8["delta"]):
            n_sharded = 0
            for (path, l), sh in zip(
                    jax.tree_util.tree_leaves_with_path(tree),
                    jax.tree.leaves(pshard)):
                assert l.sharding.is_equivalent_to(sh, l.ndim), \
                    (jax.tree_util.keystr(path), l.sharding, sh)
                n_sharded += not l.sharding.is_fully_replicated
            assert n_sharded >= 10, n_sharded
        print("LM_FSDP_OK", rel_l2)
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "LM_FSDP_OK" in out.stdout


@pytest.mark.mesh8
def test_sharded_cg_history_and_tree_math_on_mesh():
    """8-device coverage of the core numerics (fast lane, ``mesh8``):

    * sharded fused cg_solve (fused=True + constrain) on 2d-sharded
      buffers reproduces the unsharded solve's ITERATE HISTORY at equal
      depth — residual trajectory, candidate selection, solution;
    * core.tree_math ops commute with with_sharding_constraint on a
      mixed-dtype tree over a real (4 data x 2 model) mesh: elementwise
      ops bit-equal, reductions to f32 round-off."""
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import tree_math as tm
        from repro.core.cg import cg_solve
        from repro.launch.mesh import make_debug_mesh

        assert jax.device_count() >= 8, jax.device_count()
        mesh = make_debug_mesh(4, 2)
        rng = np.random.default_rng(0)

        # --- sharded-vs-unsharded cg_solve history -----------------------
        def spd(n, cond):
            q, _ = np.linalg.qr(rng.standard_normal((n, n)))
            eig = np.geomspace(1.0, cond, n)
            return ((q * eig) @ q.T).astype(np.float32)

        A1, A2 = spd(16, 30.0), spd(64, 80.0)
        b = {"a": jnp.asarray(rng.standard_normal(16), jnp.float32),
             "c": jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)}
        bv = lambda v: {
            "a": jnp.asarray(A1) @ v["a"],
            "c": (jnp.asarray(A2) @ v["c"].reshape(-1)).reshape(8, 8)}
        shards = {"a": NamedSharding(mesh, P(("data",))),
                  "c": NamedSharding(mesh, P(("data",), "model"))}
        constrain = lambda t: jax.tree.map(
            jax.lax.with_sharding_constraint, t, shards)
        evf = lambda x: jnp.abs(tm.norm(x) - 0.5)

        ref = jax.jit(lambda b: cg_solve(bv, b, iters=8, eval_fn=evf))(b)
        bs = jax.tree.map(jax.device_put, b, shards)
        got = jax.jit(lambda b: cg_solve(
            bv, constrain(b), iters=8, eval_fn=evf, fused=True,
            constrain=constrain))(bs)
        np.testing.assert_allclose(np.asarray(got.resid),
                                   np.asarray(ref.resid), rtol=2e-4)
        np.testing.assert_allclose(np.asarray(got.quad),
                                   np.asarray(ref.quad), rtol=2e-4,
                                   atol=1e-6)
        assert int(got.best_iter) == int(ref.best_iter)
        for k in ("a", "c"):
            np.testing.assert_allclose(np.asarray(got.x[k]),
                                       np.asarray(ref.x[k]), rtol=2e-4,
                                       atol=1e-6)

        # --- tree_math commutes with with_sharding_constraint ------------
        x = {"w": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32),
             "e": jnp.asarray(rng.standard_normal((16, 16)), jnp.bfloat16),
             "s": jnp.asarray(rng.standard_normal(16), jnp.float32)}
        y = jax.tree.map(lambda l: l + l.dtype.type(0.25), x)
        xsh = {"w": NamedSharding(mesh, P(("data",), "model")),
               "e": NamedSharding(mesh, P("model", ("data",))),
               "s": NamedSharding(mesh, P(("data",)))}
        con = lambda t: jax.tree.map(
            jax.lax.with_sharding_constraint, t, xsh)
        for name, op in [("add", tm.add), ("sub", tm.sub),
                         ("mul", tm.mul),
                         ("axpy", lambda a, b: tm.axpy(0.5, a, b))]:
            plain = jax.jit(lambda a, b: op(a, b))(x, y)
            comm = jax.jit(lambda a, b: con(op(con(a), con(b))))(x, y)
            for k in x:
                assert plain[k].dtype == comm[k].dtype, (name, k)
                np.testing.assert_array_equal(
                    np.asarray(plain[k], np.float32),
                    np.asarray(comm[k], np.float32), err_msg=name)
        for name, red in [("vdot", lambda a, b: tm.vdot(a, b)),
                          ("norm", lambda a, b: tm.norm(a))]:
            plain = float(jax.jit(red)(x, y))
            comm = float(jax.jit(lambda a, b: red(con(a), con(b)))(x, y))
            assert abs(plain - comm) <= 1e-5 * (abs(plain) + 1.0), \
                (name, plain, comm)
        print("MESH8_CORE_OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH8_CORE_OK" in out.stdout


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000), alpha=st.floats(-4.0, 4.0))
def test_tree_math_commutes_with_sharding_constraint(seed, alpha):
    """Property (satellite d): every core.tree_math op commutes with
    with_sharding_constraint on mixed-dtype param pytrees — constraining
    inputs and outputs changes neither values nor dtypes.  Runs on the
    session's real devices (the constraint is a layout annotation, not a
    value op); the mesh8 subprocess test covers a genuine 4x2 mesh."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    x = {"w": jax.random.normal(ks[0], (4, 6), jnp.float32),
         "e": jax.random.normal(ks[1], (6, 2)).astype(jnp.bfloat16),
         "s": jax.random.normal(ks[2], (3,), jnp.float32)}
    y = jax.tree.map(lambda l: (l * l.dtype.type(0.5)
                                + l.dtype.type(0.125)), x)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    sh = jax.tree.map(
        lambda l: jax.sharding.NamedSharding(mesh, P(*([None] * l.ndim))),
        x)
    con = lambda t: jax.tree.map(jax.lax.with_sharding_constraint, t, sh)

    from repro.core import tree_math as tm
    ops = [lambda a, b: tm.add(a, b), lambda a, b: tm.sub(a, b),
           lambda a, b: tm.mul(a, b),
           lambda a, b: tm.scale(a, jnp.float32(alpha)),
           lambda a, b: tm.axpy(jnp.float32(alpha), a, b),
           lambda a, b: tm.where(jnp.bool_(seed % 2), a, b),
           lambda a, b: tm.cast_like(a, b),
           lambda a, b: tm.zeros_like(a)]
    for i, op in enumerate(ops):
        plain = jax.jit(op)(x, y)
        comm = jax.jit(lambda a, b: con(op(con(a), con(b))))(x, y)
        for k in x:
            assert plain[k].dtype == comm[k].dtype, (i, k)
            np.testing.assert_array_equal(np.asarray(plain[k], np.float32),
                                          np.asarray(comm[k], np.float32),
                                          err_msg=f"op {i} leaf {k}")
    for red in (lambda a, b: tm.vdot(a, b), lambda a, b: tm.norm(a)):
        plain = jax.jit(red)(x, y)
        comm = jax.jit(lambda a, b: red(con(a), con(b)))(x, y)
        np.testing.assert_array_equal(np.asarray(plain), np.asarray(comm))


def test_hlo_analysis_trip_counts():
    from repro.launch.hlo_analysis import analyze
    W = jnp.zeros((128, 128))
    x = jnp.zeros((8, 128))

    def once(w, x):
        return jnp.tanh(x @ w)

    def scanned(w, x):
        def body(h, _):
            return jnp.tanh(h @ w), None
        return jax.lax.scan(body, x, None, length=6)[0]

    a1 = analyze(jax.jit(once).lower(W, x).compile().as_text())
    a6 = analyze(jax.jit(scanned).lower(W, x).compile().as_text())
    assert abs(a6["flops"] / a1["flops"] - 6.0) < 1e-6
