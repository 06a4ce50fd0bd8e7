"""Training driver.

Runs NGHF / NG / HF / SGD / Adam on any registered architecture with the
synthetic LM pipeline — or, with an ``--arch *-asr`` id, runs the paper's
actual workload: lattice-based discriminative sequence training (MPE/MMI)
of an acoustic model, through the SAME distributed launch layer (mesh +
sharded batches + one jitted uniform step).  Every optimiser goes through
the same ``core.optim`` protocol: ONE driver loop, ONE checkpoint format
(full ``(params, opt_state, step)`` — resume is exact), no per-optimiser
branching.  On CPU use ``--smoke`` (reduced geometry); on a real cluster
the same script runs against the production mesh (``--mesh``).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
      --optimizer nghf --steps 20 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch lstm-asr --smoke \
      --optimizer adam --loss mpe --steps 100 --batch 16
  PYTHONPATH=src python -m repro.launch.train --arch lstm-asr --smoke \
      --optimizer nghf --warm-start --adapt-lam --steps 8 --batch 32
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.io import load_train_state, save_train_state
from repro.configs.acoustic import ASR_ARCHS, get_acoustic_config
from repro.configs.base import get_config, list_archs
from repro.core.optim import config_for, list_optimizers
from repro.data.pipeline import shard_batch
from repro.data.synthetic import EpochPlan, asr_batch, lm_batch
from repro.launch import steps as S
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.launch.sharding import (data_extent, input_shardings,
                                   param_shardings,
                                   sequence_input_shardings)
from repro.models.registry import get_model

# default learning rates when --lr is not given (ignored by second-order
# configs, which have no ``lr`` field)
SEQ_DEFAULT_LR = {"sgd": 0.2, "adam": 2e-3}
LM_DEFAULT_LR = {"sgd": 0.3, "adam": 3e-4}


# ---------------------------------------------------------------------------
# Lattice sequence training (the paper's workload) through the launch layer
# ---------------------------------------------------------------------------

def _resolve_mesh(mesh):
    if mesh is None or mesh == "none":
        return None
    if isinstance(mesh, str):
        if "x" in mesh and mesh.split("x")[0].isdigit():
            # "DxM" debug mesh, e.g. "4x2" = 4-way data x 2-way model —
            # runs the full sharded path on host devices (pair with
            # XLA_FLAGS=--xla_force_host_platform_device_count=8)
            d, m = (int(v) for v in mesh.split("x"))
            return make_debug_mesh(d, m)
        return make_production_mesh(multi_pod=mesh == "multi-pod")
    return mesh                        # an actual jax.sharding.Mesh


def _parse_sample_schedule(sched):
    """"0:1.0,100:0.5,300:0.25" (or a [(step, frac), ...] list) -> sorted
    [(step, frac), ...]: the curvature-sample fraction to use from each
    update index on (Sainath et al.'s shrinking sample across outer
    iterations)."""
    if sched is None:
        return None
    if isinstance(sched, str):
        pairs = [p.split(":") for p in sched.split(",") if p.strip()]
    else:
        pairs = sched
    return sorted((int(s), float(f)) for s, f in pairs)


def train_sequence(*, arch=None, acfg=None, optimizer="nghf", loss="mpe",
                   steps=8, batch=32, cg_batch=8, frames=32, kappa=0.5,
                   cg_iters=6, ng_iters=2, lam=1.0, lr=None, noise=1.2,
                   smoke=False, mesh=None, backend="auto", init_params=None,
                   seed=0, verbose=True, ckpt_dir=None, resume=False,
                   dataset_batches=None, ckpt_every=10, warm_start=False,
                   adapt_lam=False, preconditioner=None,
                   curvature_sample=None, curvature_sample_schedule=None,
                   cg_tol=None, cg_fused=False, profile_dir=None):
    """Lattice MPE/MMI (or frame-CE) training of an acoustic model through
    the distributed launch layer.  Returns ``(params, log)``.

    Any registered optimiser works — NGHF and the paper's first-order
    baselines run the SAME loop, step signature and checkpoint format.

    ``mesh``: None, a ``jax.sharding.Mesh``, or "single-pod"/"multi-pod".
    Under a mesh the acoustic params are replicated (they are small; the
    batch is what scales), every batch — dense features AND the packed
    ``Lattice`` pytree — is placed with ``sequence_input_shardings``, and
    the jitted update runs both Fig. 1 stages GSPMD data-parallel.

    ``dataset_batches``: when set, gradient batches cycle over a FIXED
    pool of that many seeds (a finite training set revisited across
    epochs, the paper's regime); when None every update draws a fresh
    batch.  ``seed`` offsets the whole stream so separate stages (e.g. CE
    pretraining vs MPE) can use disjoint data.

    ``profile_dir``: when set, the updates after the first (which
    compiles) run under ``jax.profiler.trace(profile_dir)``.  The loop's
    host spans (``train.make_batch``, ``train.update``,
    ``train.read_metrics``, ``train.checkpoint``, ``train.rebuild``) land
    in the same trace as the device's operations, so each device-idle gap
    falls under the host span that caused it; the update's own stages are
    named scopes inside the program (``grad_stage``,
    ``curvature_product``, ``candidate_eval``, ``cg_solve``,
    ``lattice_stats``).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import acoustic

    if acfg is None:
        acfg = get_acoustic_config(arch)
        if smoke:
            acfg = acfg.smoke()
    mesh = _resolve_mesh(mesh)

    if init_params is not None:
        # the jitted update donates (params, opt_state) — copy so the
        # CALLER's arrays survive the first step (examples reuse the same
        # init_params across several train_sequence runs)
        params = jax.tree.map(jnp.copy, init_params)
    else:
        params = acoustic.init_params(acfg, jax.random.PRNGKey(seed))
    state_sharding = None
    if mesh is not None:
        state_sharding = jax.tree.map(
            lambda _: NamedSharding(mesh, P()), params)
        params = jax.device_put(params, state_sharding)

    def make_batch(s, n):
        b = asr_batch(s, batch=n, num_frames=frames,
                      num_states=acfg.num_outputs, input_dim=acfg.input_dim,
                      noise=noise)
        if mesh is not None:
            b = jax.device_put(b, sequence_input_shardings(mesh, b))
        return b

    sample_sched = _parse_sample_schedule(curvature_sample_schedule)
    ocfg = config_for(optimizer, cg_iters=cg_iters, ng_iters=ng_iters,
                      lam=lam, warm_start=warm_start, adapt_lam=adapt_lam,
                      preconditioner=preconditioner,
                      curvature_sample=curvature_sample, cg_tol=cg_tol,
                      cg_fused=cg_fused or None,
                      lr=lr if lr is not None
                      else SEQ_DEFAULT_LR.get(optimizer))
    counts = acoustic.share_counts(acfg, params)

    def build(frac=None):
        cfg_u = ocfg if frac is None else ocfg.replace(curvature_sample=frac)
        fn, o = S.build_sequence_step(
            acfg, cfg_u, loss=loss, kappa=kappa, backend=backend, mesh=mesh,
            state_sharding=state_sharding, share_counts=counts)
        # donate (params, opt_state): the loop below rebinds both from the
        # step outputs, and checkpoints copy out post-step values.
        return S.jit_train_step(fn), o

    def sched_frac(u):
        if not sample_sched:
            return None
        frac = getattr(ocfg, "curvature_sample", 1.0)
        for boundary, f in sample_sched:
            if u >= boundary:
                frac = f
        return frac

    step, opt = build()
    opt_state = opt.init(params, state_sharding=state_sharding)

    start = 0
    if resume and ckpt_dir and os.path.exists(ckpt_dir):
        params, opt_state, start = load_train_state(ckpt_dir, params,
                                                    opt_state)
        if verbose:
            print(f"[train] resumed from step {start}")

    plan = EpochPlan(num_updates_per_epoch=max(steps, 1), base_seed=seed)

    def grad_seed(u):
        return plan.grad_seed(0, u % dataset_batches if dataset_batches
                              else u)

    span = jax.profiler.TraceAnnotation
    log = []
    cur_frac = None
    with contextlib.ExitStack() as profiling:
        for u in range(start, steps):
            if profile_dir and u == start + 1:
                profiling.enter_context(jax.profiler.trace(profile_dir))
            t0 = time.time()
            want = sched_frac(u) if opt.uses_cg_batch else None
            if want is not None and want != cur_frac:
                # curvature-sample schedule boundary: the sample is a
                # STATIC slice (jit-friendly), so a new fraction means one
                # rebuild + recompile per phase — a handful over a whole
                # run.  The optimiser state is untouched
                # (curvature_sample does not enter the state template).
                with span("train.rebuild"):
                    step, opt = build(want)
                cur_frac = want
                if verbose:
                    print(f"  [curvature-sample] step {u}: fraction -> "
                          f"{want}")
            with span("train.make_batch"):
                gb = make_batch(grad_seed(u), batch)
                cb = make_batch(plan.cg_seed(0, u), cg_batch) \
                    if opt.uses_cg_batch else None
            with span("train.update"):
                params, opt_state, metrics = step(params, opt_state, gb, cb)
            with span("train.read_metrics"):
                metrics = {k: float(v) for k, v in metrics.items()
                           if getattr(v, "ndim", 0) == 0}
            dt = time.time() - t0
            log.append(dict(step=u, time_s=dt, **metrics))
            if verbose:
                key_metric = metrics.get("mpe_acc", metrics.get(
                    "mmi", metrics.get("ce", metrics.get("loss",
                                                         float("nan")))))
                print(f"  seq step {u:4d} {loss}={key_metric:.4f} "
                      f"({dt:.1f}s)")
            if ckpt_dir and (u + 1) % ckpt_every == 0:
                with span("train.checkpoint"):
                    save_train_state(ckpt_dir, params, opt_state,
                                     step=u + 1)
        if ckpt_dir:
            with span("train.checkpoint"):
                save_train_state(ckpt_dir, params, opt_state, step=steps)
    return params, log


def evaluate_sequence(acfg, params, *, loss="mpe", kappa=0.5, frames=32,
                      batch=32, n=4, noise=1.2, seed0=90_000,
                      backend="auto"):
    """Held-out metric (mpe_acc for MPE, -loss otherwise) over n batches."""
    from repro.losses.sequence import get_loss
    from repro.models import acoustic

    loss_spec = get_loss(loss, kappa=kappa, backend=backend)
    vals = []
    for i in range(n):
        b = asr_batch(seed0 + i, batch=batch, num_frames=frames,
                      num_states=acfg.num_outputs, input_dim=acfg.input_dim,
                      noise=noise)
        logits = acoustic.forward(acfg, params, b["feats"])
        val, metrics = loss_spec.value(logits, b)
        vals.append(float(metrics.get("mpe_acc", -val)))
    return float(np.mean(vals))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b",
                    choices=(list_archs()
                             + ["lm-" + a for a in list_archs()]
                             + sorted(ASR_ARCHS)),
                    help="architecture id; 'lm-<arch>' is an explicit "
                    "alias for the LM path (e.g. 'lm-qwen2.5-3b'), "
                    "'*-asr' ids run lattice sequence training")
    ap.add_argument("--optimizer", default="nghf",
                    choices=list_optimizers())
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--cg-iters", type=int, default=8)
    ap.add_argument("--ng-iters", type=int, default=4)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--warm-start", action="store_true",
                    help="warm-start the outer CG from the previous Δθ")
    ap.add_argument("--adapt-lam", action="store_true",
                    help="Levenberg-Marquardt-style λ adaptation")
    ap.add_argument("--preconditioner", default=None,
                    choices=["identity", "share_counts", "fisher_diag"])
    ap.add_argument("--curvature-sample", type=float, default=None,
                    help="fraction of the CG batch used for GN/Fisher "
                    "curvature products (candidate eval keeps the full "
                    "batch); e.g. 0.5")
    ap.add_argument("--curvature-sample-schedule", default=None,
                    help="shrink the curvature sample across updates, "
                    "e.g. '0:1.0,100:0.5,300:0.25' (ASR archs only)")
    ap.add_argument("--cg-tol", type=float, default=None,
                    help="adaptive CG budget: stop when the quadratic "
                    "model's relative per-iteration gain drops below "
                    "this; --cg-iters becomes the ceiling")
    ap.add_argument("--cg-fused", action="store_true",
                    help="fused flat-buffer CG vector work (one kernel "
                    "launch for x+=av, r-=aBv, <r,r>)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced geometry for CPU")
    ap.add_argument("--mesh", default="none",
                    help="'none' | 'single-pod' | 'multi-pod' | 'DxM' "
                    "(debug mesh: D-way data x M-way model, e.g. '4x2' "
                    "on 8 forced host devices)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-json", default=None)
    ap.add_argument("--profile-dir", default=None,
                    help="trace the updates after the first into this "
                    "directory (jax.profiler; open with TensorBoard or "
                    "Perfetto): the loop's train.* host spans and the "
                    "update's named stages on one clock (ASR archs only)")
    # lattice sequence training (``*-asr`` archs) only:
    ap.add_argument("--loss", default="mpe", choices=["mpe", "mmi", "ce"])
    ap.add_argument("--kappa", type=float, default=0.5)
    ap.add_argument("--frames", type=int, default=32)
    ap.add_argument("--cg-batch", type=int, default=8)
    ap.add_argument("--lattice-backend", default="auto")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.arch in ASR_ARCHS:
        _, log = train_sequence(
            arch=args.arch, optimizer=args.optimizer, loss=args.loss,
            steps=args.steps, batch=args.batch, cg_batch=args.cg_batch,
            frames=args.frames, kappa=args.kappa, cg_iters=args.cg_iters,
            ng_iters=args.ng_iters, lr=args.lr, smoke=args.smoke,
            mesh=args.mesh, backend=args.lattice_backend,
            ckpt_dir=args.ckpt_dir, resume=args.resume,
            warm_start=args.warm_start, adapt_lam=args.adapt_lam,
            preconditioner=args.preconditioner,
            curvature_sample=args.curvature_sample,
            curvature_sample_schedule=args.curvature_sample_schedule,
            cg_tol=args.cg_tol, cg_fused=args.cg_fused,
            profile_dir=args.profile_dir)
        if args.log_json:
            with open(args.log_json, "w") as f:
                json.dump(log, f, indent=1)
        return log

    arch = args.arch
    if arch.startswith("lm-") and arch[3:] in list_archs():
        arch = arch[3:]                # 'lm-qwen2.5-3b' alias
    cfg = get_config(arch)
    if args.smoke:
        cfg = cfg.smoke()
    model = get_model(cfg)

    key = jax.random.PRNGKey(0)
    params = model.init(key)
    print(f"[train] arch={cfg.name} params={model.param_count()/1e6:.1f}M "
          f"optimizer={args.optimizer}")

    mesh = _resolve_mesh(args.mesh)
    pshard = None
    if mesh is not None:
        pshard = param_shardings(cfg, mesh, model.param_shapes())
        params = jax.tree.map(jax.device_put, params, pshard)

    ocfg = config_for(args.optimizer, cg_iters=args.cg_iters,
                      ng_iters=args.ng_iters, warm_start=args.warm_start,
                      adapt_lam=args.adapt_lam,
                      preconditioner=args.preconditioner,
                      curvature_sample=args.curvature_sample,
                      cg_tol=args.cg_tol,
                      cg_fused=args.cg_fused or None,
                      lr=args.lr if args.lr is not None
                      else LM_DEFAULT_LR.get(args.optimizer))
    min_cg = 1
    if mesh is not None:
        min_cg = data_extent(mesh)[1]  # CG sub-batch stays data-sharded
    step_fn, opt = S.build_step(cfg, ocfg, cg_frac=4, min_cg=min_cg,
                                state_sharding=pshard, mesh=mesh)
    step = S.jit_train_step(step_fn)
    opt_state = opt.init(params, state_sharding=pshard)

    start = 0
    if args.resume and args.ckpt_dir and os.path.exists(args.ckpt_dir):
        params, opt_state, start = load_train_state(args.ckpt_dir, params,
                                                    opt_state)
        print(f"[train] resumed from step {start}")

    log = []
    for i in range(start, args.steps):
        batch = lm_batch(i, batch=args.batch, seq_len=args.seq,
                         vocab=cfg.vocab_size)
        if cfg.is_encoder_decoder:
            batch["encoder_input"] = jax.random.normal(
                jax.random.fold_in(key, i),
                (args.batch, cfg.encoder_frames, cfg.d_model)).astype(cfg.cdtype)
        if mesh is not None:
            batch = shard_batch(batch, mesh)
        t0 = time.time()
        params, opt_state, metrics = step(params, opt_state, batch)
        metrics = {k: float(v) for k, v in metrics.items()}
        dt = time.time() - t0
        log.append(dict(step=i, time_s=dt, **metrics))
        print(f"  step {i:4d} loss={metrics.get('ce', metrics.get('loss')):.4f} "
              f"acc={metrics.get('acc', float('nan')):.3f} ({dt:.1f}s)")
        if args.ckpt_dir and (i + 1) % 10 == 0:
            save_train_state(args.ckpt_dir, params, opt_state, step=i + 1)
    if args.ckpt_dir:
        save_train_state(args.ckpt_dir, params, opt_state, step=args.steps)
    if args.log_json:
        with open(args.log_json, "w") as f:
            json.dump(log, f, indent=1)
    return log


if __name__ == "__main__":
    main()
