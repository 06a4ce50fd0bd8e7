"""Per-optimiser update wall-time through the unified ``core.optim`` API.

Times ONE jitted update (post-compile) of each registered optimiser on
the paper's workload — LSTM acoustic model + lattice MPE — through
``launch.steps.build_sequence_step``, i.e. exactly what the training
driver executes per step.  Second-order rows use the same gradient/CG
batch geometry; ``nghf`` is measured cold, warm-started, and with each
CG-stage cost lever engaged:

  * ``nghf_sampled``       — GN/Fisher products on half the CG batch
                             (``curvature_sample=0.5``; candidate eval
                             stays full-batch).
  * ``nghf_fused``         — per-iteration vector work through the fused
                             flat-buffer kernel (``cg_fused=True``).
  * ``nghf_adaptive``      — relative-improvement stopping
                             (``cg_tol``; ``cg_iters`` as ceiling).
  * ``nghf_warm_adaptive`` — warm start + adaptive budget: the warm
                             start now shows up as FEWER iterations
                             (``cg_iters_used`` in the JSON row) instead
                             of the old always-pay-the-ceiling regression.
  * ``nghf_fast``          — all levers together.
  * ``nghf_fsdp4x2``       — the sharded second-order LM path: one NGHF
                             update on the qwen smoke LM with 2d (FSDP)
                             parameter storage over an 8-device host-CPU
                             mesh (4 data x 2 model), timed in a
                             subprocess (the forced device count must
                             precede jax init).

Emits the standard CSV rows plus one JSON row per optimiser:

    {"bench": "optim_update", "optimizer": "nghf_fast", ...,
     "ms_per_update": 61.2, "cg_iters_used": 3, "cg_best_loss": -0.41}

The update's per-stage device time (gradient stage, curvature products,
candidate evaluations, CG vector work, lattice statistics) is read from a
chip trace of the one jitted update by the benchmark's per-stage metrics
(``bench/metrics/``), not timed here.

``--json-out BENCH_lattice.json`` MERGES these rows into the existing
lattice-engine trajectory file (same CI artifact), replacing any previous
``optim_update`` rows.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_call
from repro.configs.acoustic import LSTM
from repro.launch.steps import build_sequence_step, jit_train_step
from repro.data.synthetic import asr_batch
from repro.models import acoustic

FRAMES = 32
BATCH_GRAD = 32
BATCH_CG = 8

# (row label, optimizer spec name, config overrides)
CONFIGS = [
    ("sgd", "sgd", {"lr": 0.2}),
    ("adam", "adam", {"lr": 2e-3}),
    ("hf", "hf", {"cg_iters": 6}),
    ("nghf", "nghf", {"cg_iters": 6, "ng_iters": 3}),
    ("nghf_warm", "nghf", {"cg_iters": 6, "ng_iters": 3,
                           "warm_start": True}),
    ("nghf_sampled", "nghf", {"cg_iters": 6, "ng_iters": 3,
                              "curvature_sample": 0.5}),
    ("nghf_fused", "nghf", {"cg_iters": 6, "ng_iters": 3,
                            "cg_fused": True}),
    ("nghf_adaptive", "nghf", {"cg_iters": 6, "ng_iters": 3,
                               "cg_tol": 0.2}),
    ("nghf_warm_adaptive", "nghf", {"cg_iters": 6, "ng_iters": 3,
                                    "warm_start": True, "cg_tol": 0.2}),
    ("nghf_fast", "nghf", {"cg_iters": 6, "ng_iters": 3,
                           "warm_start": True, "cg_tol": 0.2,
                           "curvature_sample": 0.5, "cg_fused": True}),
]


def donation_row(cfg, params, counts, gb, cb):
    """The ``nghf_donated`` row: the SAME nghf geometry as the ``nghf``
    row, jitted through ``launch.steps.jit_train_step`` (params +
    opt_state donated — what the training driver now runs).

    Donated inputs are invalid after the call, so timing must CHAIN the
    step's outputs back as inputs instead of re-calling on the same
    arrays; the row also records the compiled graphs' memory_analysis so
    the donation's temp/argument-byte effect is part of the artifact.
    """
    step_fn, opt = build_sequence_step(cfg, "nghf", loss="mpe",
                                       share_counts=counts,
                                       cg_iters=6, ng_iters=3)
    state = opt.init(params)
    mem_u = jax.jit(step_fn).lower(params, state, gb, cb) \
        .compile().memory_analysis()
    dstep = jit_train_step(step_fn).lower(params, state, gb, cb).compile()
    mem_d = dstep.memory_analysis()
    # never feed the shared ``params`` into the donating step — later
    # benches reuse it and donation deletes its buffers
    p = jax.tree.map(jnp.copy, params)
    for _ in range(3):                       # settle, post-compile
        p, state, _ = dstep(p, state, gb, cb)
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        p, state, _ = dstep(p, state, gb, cb)
    jax.block_until_ready((p, state))
    us = (time.perf_counter() - t0) / iters * 1e6
    emit("optim_update.nghf_donated", us, f"ms_per_update={us / 1e3:.3f}")
    rec = {"bench": "optim_update", "optimizer": "nghf_donated",
           "donated": True, "B": BATCH_GRAD, "cg_B": BATCH_CG, "T": FRAMES,
           "ms_per_update": round(us / 1e3, 4),
           "temp_bytes": int(mem_d.temp_size_in_bytes),
           "temp_bytes_undonated": int(mem_u.temp_size_in_bytes),
           "arg_bytes": int(mem_d.argument_size_in_bytes)}
    print(json.dumps(rec))
    return rec


def sharded_lm_row():
    """The ``nghf_fsdp4x2`` row: one NGHF LM update with 2d (FSDP)
    parameter storage on a 4 data x 2 model host-CPU mesh — what
    ``--arch lm-* --optimizer nghf`` runs per step, θ-sized CG state
    sharded included.  The child process times the settled (warm-started,
    donating) step and prints the JSON row; the parent re-emits it.  The
    child is forced onto 8 host-CPU devices (the parent may hold the
    accelerator), so the row records the child's own platform: it is a
    CPU number, never a chip one."""
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import json, time
        import jax
        from repro.configs.base import get_config
        from repro.core.optim import config_for
        from repro.data.pipeline import shard_batch
        from repro.data.synthetic import lm_batch
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.sharding import param_shardings
        from repro.launch.steps import build_step, jit_train_step
        from repro.models.registry import get_model

        cfg = get_config("qwen2.5-3b").smoke().replace(param_sharding="2d")
        model = get_model(cfg)
        mesh = make_debug_mesh(4, 2)
        pshard = param_shardings(cfg, mesh, model.param_shapes())
        params = jax.tree.map(jax.device_put,
                              model.init(jax.random.PRNGKey(0)), pshard)
        ocfg = config_for("nghf", cg_iters=6, ng_iters=3,
                          preconditioner="fisher_diag", warm_start=True)
        fn, opt = build_step(cfg, ocfg, cg_frac=2, min_cg=4,
                             state_sharding=pshard, mesh=mesh)
        gb = shard_batch(lm_batch(0, batch=8, seq_len=32,
                                  vocab=cfg.vocab_size), mesh)
        step = jit_train_step(fn)
        state = opt.init(params, state_sharding=pshard)
        p = params                  # donated: always chain the outputs
        for _ in range(2):          # compile + settle the warm start
            p, state, m = step(p, state, gb)
        iters = 3
        t0 = time.perf_counter()
        for _ in range(iters):
            p, state, m = step(p, state, gb)
        jax.block_until_ready((p, state))
        us = (time.perf_counter() - t0) / iters * 1e6
        print(json.dumps({
            "bench": "optim_update", "optimizer": "nghf_fsdp4x2",
            "mesh": "4x2", "device": jax.devices()[0].platform,
            "devices": int(jax.device_count()),
            "param_sharding": "2d", "warm_start": True,
            "B": 8, "cg_B": 4, "T": 32,
            "ms_per_update": round(us / 1e3, 4),
            "cg_iters_used": int(m["cg_iters_used"]),
            "cg_best_loss": round(float(m["cg_best_loss"]), 6)}))
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.abspath(
                   os.path.join(os.path.dirname(__file__), "..", "src")))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"nghf_fsdp4x2 bench failed:\n{out.stderr[-3000:]}")
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    emit("optim_update.nghf_fsdp4x2", rec["ms_per_update"] * 1e3,
         f"ms_per_update={rec['ms_per_update']:.3f}")
    print(json.dumps(rec))
    return rec


def run(budget: str = "small", json_out: str | None = None):
    cfg = LSTM.smoke().replace(hidden_dim=48, num_outputs=30)
    params = acoustic.init_params(cfg, jax.random.PRNGKey(0))
    counts = acoustic.share_counts(cfg, params)
    kw = dict(num_frames=FRAMES, num_states=cfg.num_outputs,
              input_dim=cfg.input_dim, noise=1.2)
    gb = asr_batch(0, batch=BATCH_GRAD, **kw)
    cb = asr_batch(1, batch=BATCH_CG, **kw)

    rows, json_rows = [], []
    for label, name, overrides in CONFIGS:
        step_fn, opt = build_sequence_step(cfg, name, loss="mpe",
                                           share_counts=counts, **overrides)
        step = jax.jit(step_fn)
        state = opt.init(params)
        cg = cb if opt.uses_cg_batch else None
        # warm the state so the warm-start rows time a SETTLED warm start
        # (x0 != 0 and, under cg_tol, the adaptive budget at its
        # steady-state iteration count), not the first cold update
        p = params
        for _ in range(3):
            p, state, _ = step(p, state, gb, cg)
        us = time_call(lambda: step(p, state, gb, cg), warmup=1, iters=3)
        rows.append(emit(f"optim_update.{label}", us,
                         f"ms_per_update={us / 1e3:.3f}"))
        rec = {"bench": "optim_update", "optimizer": label,
               "warm_start": bool(overrides.get("warm_start", False)),
               "B": BATCH_GRAD, "cg_B": BATCH_CG, "T": FRAMES,
               "ms_per_update": round(us / 1e3, 4)}
        for k, val in overrides.items():
            if k in ("curvature_sample", "cg_tol", "cg_fused"):
                rec[k] = val
        if opt.uses_cg_batch:
            # the warm-start satellite's proof: adaptive rows record how
            # many CG iterations the update actually spent and where the
            # candidate selection landed
            _, _, m = step(p, state, gb, cg)
            rec["cg_iters_used"] = int(m["cg_iters_used"])
            rec["cg_best_loss"] = round(float(m["cg_best_loss"]), 6)
        json_rows.append(rec)
        print(json.dumps(rec))

    json_rows.append(donation_row(cfg, params, counts, gb, cb))
    json_rows.append(sharded_lm_row())

    if json_out:
        # merge into the shared trajectory file (one CI artifact for both
        # the lattice-engine and optimiser benches)
        doc = {"bench": "lattice_engine", "budget": budget,
               "device": jax.devices()[0].platform, "rows": []}
        if os.path.exists(json_out):
            with open(json_out) as f:
                doc = json.load(f)
        doc["rows"] = [r for r in doc.get("rows", [])
                       if r.get("bench") != "optim_update"] + json_rows
        with open(json_out, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"# merged {len(json_rows)} optim rows into {json_out}")
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", default="small")
    ap.add_argument("--json-out", default=None,
                    help="merge JSON rows into e.g. BENCH_lattice.json")
    args = ap.parse_args()
    run(args.budget, json_out=args.json_out)
