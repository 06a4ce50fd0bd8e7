"""The system under test: the program's jitted NGHF update, built as
``repro.launch.train.train_sequence`` builds it, fed from the cell's pool.

From the program the benchmark takes the step builder, the model's share
counts, the mesh and sharding rules and the ``Lattice`` type; the weights
and the pool are the benchmark's own, made from the seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import lattices
from bench.reference import model as ref_model

# faults planted under the timed path, for the tests that show the
# comparison catches them (never set by a benchmark run)
FAULTS = ("unchanged", "half_batch", "no_exchange")


class Trainer:
    """One compiled update with its state, and the pool it cycles through.

    ``step()`` is the window's call: take the next pool batch, run the
    update, read the scalar metrics back to the host."""

    def __init__(self, cell, seed, *, pool=None, fault=None):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import AcousticConfig
        from repro.core.optim import config_for
        from repro.launch import steps as S
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.sharding import sequence_input_shardings
        from repro.losses.lattice import Lattice
        from repro.models import acoustic

        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        cfg, traffic = cell.config, cell.traffic
        opt = traffic["optimizer"]
        acfg = AcousticConfig(
            name=cfg["name"], kind=cfg["kind"], input_dim=cfg["input_dim"],
            hidden_dim=cfg["hidden_dim"],
            num_recurrent_layers=cfg.get("num_recurrent_layers", 0),
            num_ff_layers=cfg.get("num_ff_layers", 0),
            unfold=cfg.get("unfold", 1),
            tdnn_contexts=tuple(tuple(c) for c in
                                cfg.get("tdnn_contexts", ())),
            num_outputs=cfg["num_outputs"], activation=cfg["activation"])
        mesh = make_debug_mesh(*traffic["mesh"]) if traffic.get("mesh") \
            else None

        params = ref_model.make_weights(cfg, seed)
        state_sharding = None
        if mesh is not None:
            state_sharding = jax.tree.map(
                lambda _: NamedSharding(mesh, P()), params)
            params = jax.device_put(params, state_sharding)
        self.initial = jax.device_get(params)

        if pool is None:
            pool, _ = lattices.make_pool(cell.generator, seed, traffic, cfg)

        def place(b):
            b = {"feats": jnp.asarray(b["feats"]),
                 "labels": jnp.asarray(b["labels"]),
                 "lattice": Lattice(**{k: jnp.asarray(v)
                                       for k, v in b["lattice"].items()})}
            if mesh is not None:
                b = jax.device_put(b, sequence_input_shardings(mesh, b))
            return b

        self.pool = [(place(g), place(c)) for g, c in pool]
        ocfg = config_for(opt["name"], cg_iters=opt["cg_iters"],
                          ng_iters=opt["ng_iters"], lam=opt["lam"],
                          preconditioner=opt["preconditioner"])
        counts = acoustic.share_counts(acfg, params)
        fn, self.opt = S.build_sequence_step(
            acfg, ocfg, loss=opt["loss"], kappa=opt["kappa"],
            backend=opt["lattice_backend"], mesh=mesh,
            state_sharding=state_sharding, share_counts=counts)
        self._step = _with_fault(S.jit_train_step(fn), fn, fault)
        self.params = params
        self.state = self.opt.init(params, state_sharding=state_sharding)
        self.updates = 0

    def fetch(self):
        return self.pool[self.updates % len(self.pool)]

    def call(self, batches):
        grad_batch, cg_batch = batches
        self.params, self.state, metrics = self._step(
            self.params, self.state, grad_batch, cg_batch)
        self.updates += 1
        return metrics

    def step(self):
        return jax.device_get(self.call(self.fetch()))

    def free(self):
        """Drop the update's state, its pool and its compiled program."""
        self.params = self.state = self.pool = self._step = None


def _rows(batch, keep):
    return jax.tree.map(lambda a: a[:a.shape[0] // keep], batch)


def _with_fault(step, fn, fault):
    if fault is None:
        return step
    if fault == "unchanged":
        # the update's metrics, but parameters and state handed back as
        # they came in
        return jax.jit(lambda p, s, g, c: (p, s, fn(p, s, g, c)[2]))
    keep = 2 if fault == "half_batch" else 4
    # half_batch: the mean over the first half of each batch's rows;
    # no_exchange: each chip's update from its own quarter of the rows
    # alone, which is what a data-parallel update without its reductions
    # computes on the first chip
    return lambda p, s, g, c: step(p, s, _rows(g, keep), _rows(c, keep))
