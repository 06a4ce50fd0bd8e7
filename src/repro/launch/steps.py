"""Step builders: the jitted functions that the trainer, server, and
multi-pod dry-run lower.

  * ``build_step``          — ONE builder for every optimiser on the LM
    archetypes.  ``build_step(cfg, opt_spec, ...)`` returns
    ``(step, opt)`` where ``step(params, opt_state, batch) ->
    (params, opt_state, metrics)`` has the SAME signature whether
    ``opt_spec`` names SGD, Adam, NG, HF or NGHF — second-order
    optimisers slice their CG sub-batch from the gradient batch
    internally (``cg_frac``); first-order ones just take the batch.
    Under pjit the batch means become all-reduces over (pod, data) —
    the paper's Fig. 1 distributed scheme.
  * ``build_sequence_step`` — the same uniform step for the paper's
    actual workload: an acoustic model + lattice MMI/MPE ``LossSpec``.
    ``step(params, opt_state, grad_batch, cg_batch=None)`` takes an
    explicit CG batch (the paper samples it from the WHOLE training set,
    not the gradient batch — Sec. 4.1); first-order optimisers ignore it
    (``opt.uses_cg_batch`` tells the driver whether to build one).
    Under a mesh, threads state sharding + the lattice-engine constraints
    so the statistics stage (``lattice_stats``) is GSPMD data-parallel
    alongside the gradient stage.
  * ``build_prefill_step`` — sequence forward returning last-position
    logits only (never materialises (B, T, V)).
  * ``build_serve_step``   — ONE new token against a seq_len KV cache.

Candidate evaluation inside the CG stage follows the optimiser config's
``eval_accumulators`` ("loss_only" by default: the LossSpec's value-only
fast path — for the lattice losses that is the engine's fused
forward-only statistics).

The CG-stage cost levers are plain ``SecondOrderConfig`` fields and
therefore flow through both builders' ``**opt_overrides`` untouched:
``curvature_sample`` (GN/Fisher products on a deterministic fraction of
the CG batch, candidate eval on the full batch), ``cg_tol`` /
``cg_min_iters`` (adaptive iteration budget, ``cg_iters`` as ceiling)
and ``cg_fused`` (one fused kernel launch per iteration for the vector
work; auto-disabled under a mesh).
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.optim import Optimizer, get_optimizer
from repro.kernels import dispatch
from repro.losses.chunked_lm import ChunkedCELoss
from repro.models.registry import get_model


def _lm_forward(cfg: ArchConfig, model):
    """forward returning (hidden, head) + scaled aux, for ChunkedCELoss."""
    from repro.launch import fsdp

    def fwd(params, batch):
        hidden, aux = model.forward_hidden(params, batch)
        # gather the sequence dim ONCE (bf16) before the chunked loss:
        # its traced dynamic_slice over a T-sharded hidden otherwise makes
        # GSPMD materialise a full f32 copy per chunk (§Perf hillclimb 2).
        hidden = fsdp.unshard_seq(hidden)
        return (hidden, model.head_matrix(params)), cfg.router_aux_coef * aux

    return fwd


def _scalar_metrics(metrics: dict) -> dict:
    """Keep scalar diagnostics only (dry-run outputs stay tiny)."""
    out = {}
    for k, v in metrics.items():
        if hasattr(v, "ndim") and v.ndim == 0:
            out[k] = v
    return out


def cg_sub_batch(batch: dict, frac: int, min_size: int):
    """Static slice of the leading batch dim — the paper's (much smaller)
    CG batch.  Keeps divisibility by the data-parallel extent."""
    ref = batch["tokens"] if "tokens" in batch else batch["feats"]
    B = ref.shape[0]
    nb = max(B // frac, min_size)

    def slc(x):
        if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] == B:
            return x[:nb]
        return x

    return jax.tree.map(slc, batch)


# XLA's TPU compiler places the output-layer VJP fusion of the CG-stage
# curvature product — the (B, T, K) cotangent, its bias reduction and its
# bf16 copy for the weight matmul — in VMEM and then overruns the default
# 16 MiB scoped-VMEM limit (lstm-asr, CG batch 8 x 128 frames x 6000
# outputs: 17.4 MiB; 40.6 MiB at "highest" matmul precision), refusing
# the whole step.  A v5e core has 128 MiB of VMEM; 64 MiB of scoped
# limit compiles the full-width step at either precision.
TPU_COMPILER_OPTIONS = {"xla_tpu_scoped_vmem_limit_kib": "65536"}


def jit_train_step(step: Callable, **jit_kwargs) -> Callable:
    """jit a train step donating ``(params, opt_state)`` — args 0 and 1 of
    every builder here.

    Both θ-sized pytrees are dead the moment the update returns (the
    driver loops rebind them from the step's outputs), so donating lets
    XLA update them in place instead of holding old+new simultaneously —
    for NGHF that is params + CG/optimiser state, the largest buffers in
    the graph.  Donation makes the inputs invalid after the call: never
    reuse a donated ``params``/``opt_state`` value (checkpoint saves must
    use the step's OUTPUTS, which ``checkpoint.io`` copies to host
    eagerly).  The graph auditor (``repro.analysis.graph_audit``) checks
    the resulting ``input_output_alias`` on every train graph.

    On a TPU the step also gets ``TPU_COMPILER_OPTIONS``.
    """
    jit_kwargs.setdefault("donate_argnums", (0, 1))
    if dispatch.compiled_backend():
        jit_kwargs.setdefault("compiler_options", TPU_COMPILER_OPTIONS)
    return jax.jit(step, **jit_kwargs)


def build_step(cfg: ArchConfig, opt_spec, *, cg_frac: int = 8,
               min_cg: int = 1, state_sharding=None, mesh=None,
               **opt_overrides) -> Tuple[Callable, Optimizer]:
    """One uniform LM train step for ANY registered optimiser.

    ``opt_spec``: a registry name ("sgd" | "adam" | "ng" | "hf" | "nghf")
    or an already-built config dataclass; ``opt_overrides`` are forwarded
    to ``optim.get_optimizer``.  Returns ``(step, opt)`` — jit ``step``
    and seed the loop with ``opt.init(params)``.

    The model's per-leaf application counts (MoE expert usage, tied
    embeddings at 2x — ``Model.share_counts``) feed the Sec. 4.3
    share_counts preconditioner; first-order optimisers ignore them.

    ``mesh`` + ``state_sharding`` make this the sharded second-order LM
    path: θ-sized CG/optimiser state is pinned to the (2d) storage
    sharding, and the step body is traced inside ``fsdp.step_context`` so
    a 2d-stored parameter tree is FSDP-gathered to its 1d compute spec at
    the point of use — in the primal forward AND in every GN/Fisher
    JVP/VJP of the CG stage (the context registers contextvars at trace
    time, so it holds no matter who jits: the train driver, the dry-run
    lowering, or a test).  Pass ``min_cg`` = the data-parallel extent so
    the CG sub-batch stays evenly sharded.
    """
    from repro.launch import fsdp

    model = get_model(cfg)
    loss = ChunkedCELoss()
    fwd = _lm_forward(cfg, model)
    counts = model.share_counts(model.param_shapes())
    opt = get_optimizer(opt_spec, fwd, loss, share_counts=counts,
                        state_sharding=state_sharding, **opt_overrides)

    def step(params, opt_state, batch):
        with fsdp.step_context(cfg, mesh):
            lm_batch = dict(batch)
            if "labels" not in lm_batch:
                lm_batch["labels"] = lm_batch["tokens"]
            cg_batch = (cg_sub_batch(lm_batch, cg_frac, min_cg)
                        if opt.uses_cg_batch else None)
            new_params, new_state, metrics = opt.step(params, opt_state,
                                                      lm_batch, cg_batch)
        return new_params, new_state, _scalar_metrics(metrics)

    return step, opt


def acoustic_forward_fn(acfg):
    """forward for the acoustic models: (params, batch) -> (logits, 0 aux)."""
    from repro.models import acoustic

    def fwd(params, batch):
        return acoustic.forward(acfg, params, batch["feats"]), 0.0

    return fwd


def build_sequence_step(acfg, opt_spec, *,
                        loss: str = "mpe", kappa: float = 0.5,
                        backend: str = "auto", mesh=None,
                        state_sharding=None, share_counts=None,
                        **opt_overrides) -> Tuple[Callable, Optimizer]:
    """One uniform update for lattice-based sequence training — any
    optimiser, the paper's actual SGD/Adam-vs-NGHF comparison included.

    Args:
      acfg: acoustic model config (``configs.acoustic``).
      opt_spec: optimiser registry name ("sgd" | "adam" | "ng" | "hf" |
        "nghf") or an already-built config dataclass; ``opt_overrides``
        are forwarded to ``optim.get_optimizer``.
      loss: "mpe" | "mmi" | "ce" (``losses.sequence.get_loss``).
      kappa: acoustic scale of the lattice losses.
      backend: lattice-engine backend for the statistics stage —
        "scan" | "levelized" | "pallas" | "auto".  Any lattice DAG
        topology works on every backend ("pallas" dispatches sausage vs
        general-DAG kernels internally; under jit the lattice is traced,
        so "pallas" always runs the general-DAG frontier kernels while
        "auto" resolves to the levelized scan — see
        ``lattice_engine.api``).
      mesh / state_sharding / share_counts: GSPMD placement — see below.

    Returns ``(step, opt)`` with ``step(params, opt_state, grad_batch,
    cg_batch=None) -> (params, opt_state, metrics)`` where both batches
    come from ``data.synthetic.asr_batch`` (feats + labels + a
    ``Lattice``).  The CG batch is explicit because the paper samples it
    from the entire training set (Sec. 4.1), not as a slice of the
    gradient batch; pass None for first-order optimisers
    (``opt.uses_cg_batch`` is the driver's cue).

    Under ``mesh`` the lattice ``LossSpec`` constrains the engine's (B, A)
    arc tensors to the data axes (``lattice_stats(..., mesh=...)``) and
    ``state_sharding`` pins the θ-sized CG/optimiser state, so jitting
    this function with ``launch.sharding.sequence_input_shardings``-placed
    batches runs both Fig. 1 stages GSPMD data-parallel.
    """
    from repro.losses.sequence import get_loss

    loss_spec = get_loss(loss, kappa=kappa, backend=backend, mesh=mesh)
    fwd = acoustic_forward_fn(acfg)
    opt = get_optimizer(opt_spec, fwd, loss_spec,
                        share_counts=share_counts,
                        state_sharding=state_sharding, **opt_overrides)

    def sequence_step(params, opt_state, grad_batch, cg_batch=None):
        new_params, new_state, metrics = opt.step(params, opt_state,
                                                  grad_batch, cg_batch)
        return new_params, new_state, _scalar_metrics(metrics)

    return sequence_step, opt


def build_prefill_step(cfg: ArchConfig):
    model = get_model(cfg)

    def prefill_step(params, batch):
        hidden, _ = model.forward_hidden(params, batch)
        last = hidden[:, -1:]
        logits = last @ model.head_matrix(params).astype(last.dtype)
        return logits.astype(jnp.float32)

    return prefill_step


def build_serve_step(cfg: ArchConfig, *, long_mode: bool = False):
    model = get_model(cfg)

    def serve_step(params, cache, tokens, pos):
        logits, new_cache = model.decode_step(params, cache, tokens, pos,
                                              long_mode=long_mode)
        return logits, new_cache

    return serve_step
