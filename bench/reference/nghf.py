"""One NGHF update (Secs. 4-6, Alg. 1), plain and in blocks of rows.

  1. gradient stage: loss and gradient of the MPE loss on the gradient
     batch;
  2. NG direction: (lam F + eta I) d = -grad by ``ng_iters`` steps of
     preconditioned CG, F the empirical Fisher of the MMI loss (Sec. 5.2);
  3. outer CG: G x = d by ``cg_iters`` steps, G the Gauss-Newton matrix of
     the MPE loss (Eqn. 11); every iterate is a candidate, scored by the
     MPE loss on the CG batch; the best one is taken if it beats x = 0.

The constants below are the trainer's defaults, which are the paper's
algorithm: the Sec. 4.2 rescaling of product inputs to |theta|, the Sec.
4.3 share-count preconditioner, no Tikhonov damping on G, and eta = 1 on
the inner Fisher solve.  The host drives the loops; each jitted piece
handles one block of rows, and the blocks' parts are summed.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from bench.reference import lattice as L
from bench.reference import model as M

NG_DAMPING = 1.0      # eta of the inner Fisher solve
PRECISION = "highest"  # f32 matmuls in full precision on the TPU
# XLA's TPU compiler keeps the output layer's (rows, T, 6000) product
# cotangent fusion in VMEM and overruns the default 16 MiB scoped limit
# (17.9 MiB for the Fisher product at 16 x 512 frames); a v5e core has
# 128 MiB of VMEM.
TPU_OPTIONS = {"xla_tpu_scoped_vmem_limit_kib": "65536"}


def _jit(fn):
    if jax.default_backend() == "tpu":
        return jax.jit(fn, compiler_options=TPU_OPTIONS)
    return jax.jit(fn)


def _tmap(f, *trees):
    return jax.tree.map(f, *trees)


def vdot(a, b):
    return sum(float(jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def norm(a):
    return math.sqrt(vdot(a, a))


def axpy(alpha, x, y):
    return _tmap(lambda u, w: alpha * u + w, x, y)


def blocks(batch, rows):
    """Split a batch dict (feats, lattice fields) into blocks of ``rows``."""
    n = batch["feats"].shape[0]
    for lo in range(0, n, rows):
        yield jax.tree.map(lambda a: a[lo:lo + rows], batch)


class Reference:
    """The plain update for one model configuration and traffic mix.

    ``dtype`` is the model's compute type: float32 for the reference,
    bfloat16 for the lower-precision control."""

    def __init__(self, cfg, traffic, *, dtype=jnp.float32, rows=16,
                 devices=None):
        opt = traffic["optimizer"]
        self.cfg, self.dtype, self.rows = cfg, dtype, rows
        # blocks of rows go round-robin to these devices; their parts are
        # summed on the first
        self.devices = list(devices) if devices else [jax.devices()[0]]
        self.kappa = float(opt["kappa"])
        self.ng_iters, self.cg_iters = int(opt["ng_iters"]), int(opt["cg_iters"])
        self.lam = float(opt["lam"])
        self.counts = M.share_counts(cfg)
        fwd = partial(M.forward, cfg, dtype=dtype)
        kappa = self.kappa

        @_jit
        def grad_block(params, blk, n):
            def loss(p):
                return L.mpe_loss(fwd(p, blk["feats"]), blk["lattice"],
                                  kappa, n)
            return jax.value_and_grad(loss)(params)

        @_jit
        def factors(params, blk, n, frames):
            logits = fwd(params, blk["feats"])
            gm = jax.grad(L.mpe_loss)(logits, blk["lattice"], kappa, n)
            gf = jax.grad(L.mmi_loss)(logits, blk["lattice"], kappa, frames)
            return gm, gf

        def product(kind, params, v, blk, g, n, frames):
            f = lambda p: fwd(p, blk["feats"])          # noqa: E731
            _, u = jax.jvp(f, (params,), (v,))
            lat = blk["lattice"]
            if kind == "gn":        # kappa^2 w (y . u) + kappa G (y^T u)
                t = jnp.arange(u.shape[1])[None, :]
                real = (t < L.frames(lat)[:, None]).astype(jnp.float32)
                y = jax.nn.one_hot(lat["ref_states"], u.shape[-1]) \
                    * real[..., None]
                w = 1.0 / (n * jnp.maximum(lat["num_ref_units"], 1.0))
                yu = jnp.sum(y * u, -1, keepdims=True)
                hu = kappa ** 2 * w[:, None, None] * y * u + kappa * g * yu
            else:                   # frames * G (G^T u), G of the MMI loss
                hu = frames * g * jnp.sum(g * u, -1, keepdims=True)
            out, pull = jax.vjp(f, params)
            return pull(hu.astype(out.dtype))[0]

        @_jit
        def eval_block(params, x, blk, n):
            p = _tmap(jnp.add, params, x)
            return L.mpe_loss(fwd(p, blk["feats"]), blk["lattice"], kappa, n)

        self._grad_block = grad_block
        self._factors = factors
        self._gn = _jit(partial(product, "gn"))
        self._fisher = _jit(partial(product, "fisher"))
        self._eval_block = eval_block

    def _minv(self, r):
        return {k: _tmap(lambda x: x / self.counts[k], v) for k, v in r.items()}

    def _cg(self, bv, b, iters, eval_fn=None, damping=0.0):
        """Preconditioned CG from x = 0 with the negative-curvature freeze
        and candidate selection of Alg. 1 (``cg_solve``'s fixed budget)."""
        x = _tmap(jnp.zeros_like, b)
        r = b
        z = self._minv(r)
        v = z
        rz = vdot(r, z)
        dead = False
        best = (x, math.inf, -1)
        for m in range(iters):
            bvv = bv(v)
            if damping:
                bvv = axpy(damping, v, bvv)
            vbv = vdot(v, bvv)
            bad = vbv <= 0.0 or dead
            alpha = 0.0 if bad else rz / max(vbv, 1e-30)
            x = axpy(alpha, v, x)
            r = axpy(-alpha, bvv, r)
            z = self._minv(r)
            rz_new = vdot(r, z)
            beta = 0.0 if bad else rz_new / max(rz, 1e-30)
            v = axpy(beta, v, z)
            rz, dead = rz_new, bad
            if eval_fn is not None and not bad:
                loss = eval_fn(x)
                if loss < best[1]:
                    best = (x, loss, m)
        if eval_fn is None or not math.isfinite(best[1]):
            return x, math.inf, iters - 1
        return best

    def update(self, params, grad_batch, cg_batch):
        """One update; returns (new params, readings)."""
        with jax.default_matmul_precision(PRECISION):
            return self._update(params, grad_batch, cg_batch)

    def _spread(self, fn, params, blks, *args):
        """fn(params, block, *args) on each block, the blocks round-robin
        over the devices (dispatched before any is waited for); the
        results, on the first device, in block order."""
        devs = self.devices
        ps = [jax.device_put(params, d) for d in devs]
        outs = [fn(ps[i % len(devs)], jax.device_put(b, devs[i % len(devs)]),
                   *args) for i, b in enumerate(blks)]
        return [jax.device_put(o, devs[0]) for o in outs]

    def _update(self, params, gb, cb):
        n_g = gb["feats"].shape[0]
        parts = self._spread(self._grad_block, params,
                             list(blocks(gb, self.rows)), n_g)
        loss = sum(float(l) for l, _ in parts)
        grads = parts[0][1]
        for _, g in parts[1:]:
            grads = _tmap(jnp.add, grads, g)
        b = _tmap(jnp.negative, grads)
        theta_norm = norm(params)

        n_c = cb["feats"].shape[0]
        cblocks = list(blocks(cb, self.rows))
        frames = float(jnp.maximum(jnp.sum(L.frames(cb["lattice"])), 1.0))
        devs = self.devices
        placed = [jax.device_put(blk, devs[i % len(devs)])
                  for i, blk in enumerate(cblocks)]
        ps = [jax.device_put(params, d) for d in devs]
        facs = [self._factors(ps[i % len(devs)], blk, n_c, frames)
                for i, blk in enumerate(placed)]

        def product(kind, v):
            s = theta_norm / max(norm(v), 1e-30)
            v_in = _tmap(lambda a: a * s, v)
            fn, j = (self._gn, 0) if kind == "gn" else (self._fisher, 1)
            vs = [jax.device_put(v_in, d) for d in devs]
            outs = [fn(ps[i % len(devs)], vs[i % len(devs)], blk, fac[j],
                       n_c, frames)
                    for i, (blk, fac) in enumerate(zip(placed, facs))]
            out = jax.device_put(outs[0], devs[0])
            for o in outs[1:]:
                out = _tmap(jnp.add, out, jax.device_put(o, devs[0]))
            return _tmap(lambda a: a / s, out)

        def eval_fn(x):
            xs = [jax.device_put(x, d) for d in devs]
            parts = [self._eval_block(ps[i % len(devs)], xs[i % len(devs)],
                                      blk, n_c)
                     for i, blk in enumerate(placed)]
            return sum(float(p) for p in parts)

        d, _, _ = self._cg(lambda v: _tmap(lambda a: self.lam * a,
                                           product("fisher", v)),
                           b, self.ng_iters, damping=NG_DAMPING)
        x, best_loss, best_iter = self._cg(lambda v: product("gn", v), d,
                                           self.cg_iters, eval_fn=eval_fn)
        base = eval_fn(_tmap(jnp.zeros_like, x))
        accepted = best_loss < base
        new = _tmap(jnp.add, params, x) if accepted else params
        leaf_norms = {f"{k}.{j}": norm(v) for k, sub in grads.items()
                      for j, v in sub.items()}
        return new, {"loss": loss, "grad_norm": norm(grads),
                     "grad_leaf_norms": leaf_norms,
                     "cg_best_iter": best_iter, "cg_best_loss": best_loss,
                     "cg_base_loss": base, "accepted": accepted}
