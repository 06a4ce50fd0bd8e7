"""Bring-up check of the NGHF system on a TPU, through its own entry points.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four chips: the data-parallel update

With no option it runs three phases on one chip, in this one process:

  1. lattice statistics: ``lattice_stats`` on a seeded batch of general-DAG
     lattices against K=6000 log-probs, on the Pallas kernels and on the
     levelized scan, ``full`` and ``loss_only``; logZ, c_avg and
     d(sum logZ)/d(log-probs) must agree;
  2. trainer: ``repro.launch.train.main`` at the paper's full LSTM width
     (``lstm-asr``: 80 -> 2x1000 LSTM -> 1000 FF -> 6000 outputs, random
     weights from seed 0), three NGHF MPE updates, once with the defaults
     and once with ``--lattice-backend pallas --cg-fused``; every metric
     must be finite and the first update's loss must agree;
  3. service: ``repro.serving.service.main`` answers 12 requests on the
     Pallas backend with no retrace, and its streaming resume is
     bit-exact.

``--chips 4`` runs only three full-width NGHF MPE updates data-parallel
on a 4x1 mesh and the same updates on one device, and compares the
gradient norms, the selected CG candidates and the parameters.

It fails — non-zero exit, no result line — when JAX finds no TPU, and
when any check fails.  The last line of its output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times printed on earlier lines are informational: one cold run, compile
included, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
KAPPA = 0.5
# lattice statistics: pallas vs levelized, both in f32.  The engine has no
# matmul; the phase still runs under "highest" matmul precision so that no
# gather XLA turns into a one-hot dot drops to bf16 passes.  The two
# backends sum the same terms in different orders, and exp/log differ by a
# few ulp between the kernels and XLA.
STATS_RTOL = 1e-5        # logZ, relative to max(1, |logZ|)
STATS_ATOL = 1e-4        # c_avg (expected correct frames, O(10))
# d logZ / d log-probs is kappa * occupancy, in [0, 1]; the Pallas
# backend forms occupancies as exp(alpha + beta - logZ), whose exponent
# carries f32 rounding of |logZ| (~600 here), so its error is a few ulp
# of |logZ| (5.2e-5 against the levelized scan on CPU at these shapes)
GRAD_RTOL = 1e-6         # times max(1, max |logZ|)
# trainer: the first update's loss is evaluated at the same seeded weights
# by both runs; only the lattice backend differs (default matmul precision
# in both, so the logits are the same program's)
LOSS_ATOL = 1e-4         # MPE loss = -(expected phone accuracy), in [-1, 0]
# four chips: the data-parallel update reduces gradients and curvature
# products across chips, in another order than one device does
PARAM_RTOL = 1e-4


def info(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok, what):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def max_rel(a, b):
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(1.0, np.abs(b))))


def max_abs(a, b):
    import numpy as np
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def dag_batch(seed, *, batch, frames, num_states):
    """Seeded general-DAG lattices padded to one arc count, plus
    log-softmax frame log-probs made on the device."""
    import jax
    import numpy as np
    from repro.losses.lattice import batch_lattices, make_random_dag_lattice

    def draw(max_arcs=None):
        rng = np.random.default_rng(seed)
        return [make_random_dag_lattice(rng, num_frames=frames,
                                        num_states=num_states,
                                        max_arcs=max_arcs)
                for _ in range(batch)]

    arcs = max(d["start_t"].shape[0] for d in draw())
    lat = batch_lattices(draw(arcs))
    logits = 2.0 * jax.random.normal(jax.random.PRNGKey(seed),
                                     (batch, frames, num_states))
    return lat, jax.nn.log_softmax(logits, axis=-1)


def lattice_phase(*, batch=8, frames=128, num_states=6000):
    import jax
    import jax.numpy as jnp
    from repro.lattice_engine import lattice_stats

    lat, lp = dag_batch(SEED, batch=batch, frames=frames,
                        num_states=num_states)
    info(f"lattice stats: B={batch} T={frames} K={num_states} "
         f"A={lat.num_arcs} L,W={tuple(lat.level_arcs.shape[1:])}")

    def value(backend, acc):
        def f(lat_, lp_):
            st = lattice_stats(lat_, lp_, KAPPA, backend=backend,
                               accumulators=acc)
            return st.logZ, st.c_avg
        return f

    def grad(backend, acc):
        def g(lat_, lp_):
            return jax.grad(lambda l: jnp.sum(value(backend, acc)(
                lat_, l)[0]))(lp_)
        return g

    out = {}
    with jax.default_matmul_precision("highest"):
        for acc in ("full", "loss_only"):
            for backend in ("pallas", "levelized"):
                res = ()
                for kind, fn in (("value", value), ("grad", grad)):
                    t0 = time.perf_counter()
                    compiled = jax.jit(fn(backend, acc)).lower(
                        lat, lp).compile()
                    t1 = time.perf_counter()
                    r = jax.block_until_ready(compiled(lat, lp))
                    t2 = time.perf_counter()
                    res += r if kind == "value" else (r,)
                    info(f"  {backend:9s} {acc:9s} {kind:5s} compile "
                         f"{t1 - t0:.2f} s, run {t2 - t1:.4f} s "
                         "(informational)")
                out[backend, acc] = res
    for acc in ("full", "loss_only"):
        (zp, cp, gp), (zl, cl, gl) = out["pallas", acc], out["levelized", acc]
        for name, v in (("logZ", zp), ("c_avg", cp), ("grad", gp)):
            check(bool(jnp.all(jnp.isfinite(v))), f"{acc} pallas {name} "
                  "not finite")
        dz, dc, dg = max_rel(zp, zl), max_abs(cp, cl), max_abs(gp, gl)
        grad_tol = GRAD_RTOL * max(1.0, float(jnp.max(jnp.abs(zl))))
        info(f"  {acc}: pallas vs levelized  logZ rel {dz:.3e} "
             f"(tol {STATS_RTOL}), c_avg abs {dc:.3e} (tol {STATS_ATOL}), "
             f"grad abs {dg:.3e} (tol {grad_tol:.3e})")
        check(dz <= STATS_RTOL and dc <= STATS_ATOL and dg <= grad_tol,
              f"{acc} statistics: pallas and levelized disagree")


def trainer_phase():
    from repro.launch import train

    argv = ["--arch", "lstm-asr", "--optimizer", "nghf", "--loss", "mpe",
            "--steps", "3", "--batch", "32", "--cg-batch", "8",
            "--frames", "128"]
    runs = {}
    for label, flags in (("default", []),
                         ("pallas+cg-fused",
                          ["--lattice-backend", "pallas", "--cg-fused"])):
        info(f"trainer ({label}): train.main {' '.join(argv + flags)}")
        log = train.main(argv + flags)
        check(len(log) == 3, f"trainer ({label}) ran {len(log)} of 3 updates")
        for entry in log:
            bad = [k for k, v in entry.items() if not math.isfinite(v)]
            check(not bad, f"trainer ({label}) step {entry['step']}: "
                  f"non-finite {bad}")
        info(f"  step wall time {[round(e['time_s'], 3) for e in log]} s "
             "(first includes compile; informational)")
        runs[label] = log
    a, b = runs["default"][0]["loss"], runs["pallas+cg-fused"][0]["loss"]
    info(f"  first-update mpe loss: default {a:.7f}, pallas {b:.7f}, "
         f"|diff| {abs(a - b):.3e} (tol {LOSS_ATOL})")
    check(abs(a - b) <= LOSS_ATOL, "first-update loss differs between the "
          "default and the pallas+cg-fused trainer")


def service_phase():
    from repro.serving import service

    t0 = time.perf_counter()
    metrics = service.main(["--requests", "12", "--backend", "pallas"])
    info(f"service: {metrics['completed']}/12 answered in "
         f"{time.perf_counter() - t0:.2f} s incl. compile (informational)")
    check(metrics["completed"] == 12, "service did not answer all 12 "
          "requests")


def four_chip_phase():
    import jax
    import numpy as np
    from repro.configs.acoustic import get_acoustic_config
    from repro.launch.train import train_sequence
    from repro.models import acoustic

    # three updates: with random weights the first candidate is often
    # rejected (parameters unchanged), and the parameter comparison needs
    # an accepted one
    kw = dict(arch="lstm-asr", optimizer="nghf", loss="mpe", steps=3,
              batch=32, cg_batch=8, frames=128, cg_iters=8, ng_iters=4,
              seed=SEED, verbose=False)
    info("4 chips: three lstm-asr NGHF MPE updates on a 4x1 data mesh")
    t0 = time.perf_counter()
    p4, log4 = train_sequence(mesh="4x1", **kw)
    p4 = jax.block_until_ready(p4)
    info(f"  sharded update {time.perf_counter() - t0:.2f} s incl. compile "
         "(informational)")
    placed = {d for leaf in jax.tree.leaves(p4)
              for d in leaf.sharding.device_set}
    check(len(placed) == 4, f"sharded params live on {len(placed)} devices")
    in_use = [d.memory_stats()["bytes_in_use"] for d in jax.devices()[:4]]
    info(f"  bytes in use per device {in_use}")
    check(min(in_use) > 0, "a device of the mesh holds nothing")
    p4 = jax.device_get(p4)

    info("  the same updates on jax.devices()[0]")
    p1, log1 = train_sequence(mesh=None, **kw)
    p1 = jax.device_get(jax.block_until_ready(p1))
    p0 = jax.device_get(acoustic.init_params(
        get_acoustic_config("lstm-asr"), jax.random.PRNGKey(SEED)))

    def l2(tree):
        return math.sqrt(sum(float(np.sum(np.square(
            np.asarray(x, np.float64)))) for x in jax.tree.leaves(tree)))

    diff = jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b, p4, p1)
    upd = jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b, p1, p0)
    rel = l2(diff) / l2(p1)
    for m4, m1 in zip(log4, log1):
        for name, m in (("4-chip", m4), ("1-device", m1)):
            info(f"  step {m['step']} {name:8s}: loss {m['loss']:.7f}, "
                 f"grad_norm {m['grad_norm']:.7e}, CG candidate "
                 f"{int(m['cg_best_iter'])} (loss {m['cg_best_loss']:.7f}, "
                 f"accepted {bool(m['cg_accepted'])}), update_norm "
                 f"{m['update_norm']:.7e}")
        check(int(m4["cg_best_iter"]) == int(m1["cg_best_iter"])
              and bool(m4["cg_accepted"]) == bool(m1["cg_accepted"]),
              f"step {m1['step']}: the sharded update selected another CG "
              "candidate")
        check(abs(m4["grad_norm"] - m1["grad_norm"])
              <= PARAM_RTOL * abs(m1["grad_norm"])
              and abs(m4["cg_best_loss"] - m1["cg_best_loss"]) <= LOSS_ATOL,
              f"step {m1['step']}: sharded gradient or candidate loss "
              "differs from one device")
    info(f"  params rel-L2 4-chip vs 1-device {rel:.3e} (tol {PARAM_RTOL}) "
         f"after {sum(bool(m['cg_accepted']) for m in log1)} accepted "
         f"updates; |params - init| {l2(upd):.7e}, rel-L2 of the change "
         f"{l2(diff) / max(l2(upd), 1e-30):.3e}")
    check(rel <= PARAM_RTOL, "sharded params differ from the single-device "
          "updates")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel NGHF update and "
                    "its single-device comparison")
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{devices[0].platform!r}); nothing was run")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} TPU devices, JAX has "
          f"{len(devices)}")
    info(f"compile cache: {enable_compile_cache()}")
    info(f"device: {devices[0].device_kind} x {len(devices)}")
    if args.chips == 4:
        four_chip_phase()
    else:
        for phase in (lattice_phase, trainer_phase, service_phase):
            t0 = time.perf_counter()
            phase()
            info(f"{phase.__name__} done in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
