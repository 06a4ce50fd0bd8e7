"""Chip benchmark of the NGHF trainer: one cell, one seed, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's weights and pool from the seed, builds the
program's jitted NGHF update as its trainer does, and drives it through its
first updates (compiling or loading it from the cache); those updates are
compared with the plain reference once the window has closed.  With
``--trace 0`` the window runs updates for ``--seconds`` and reports the
cell's end-to-end metrics; with ``--trace 1`` it traces a few updates and
reports the per-layer metrics read from the trace.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown`` too, and ``checks`` last).  Without a TPU, or with fewer
chips than the cell needs, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.time()

import argparse                                        # noqa: E402
import json                                            # noqa: E402
import math                                            # noqa: E402
import os                                              # noqa: E402
import shutil                                          # noqa: E402
import sys                                             # noqa: E402
import tempfile                                        # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the benchmark's own host spans, read back from the trace
SPANS = ("bench.fetch_batch", "bench.step_call", "bench.read_metrics")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def enable_compile_cache(jax):
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache`` (a fixed path: the path is
    part of a cache entry's key).  Every program is cached, so a second
    run of a cell compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def tpu_devices(jax, chips):
    """The first ``chips`` TPU devices, or SystemExit."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX platform "
                         f"{devices[0].platform!r}); nothing was run")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX has "
                         f"{len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Counts program compiles and cache loads while ``active``; one per
    process (JAX's listeners cannot be removed)."""

    _instance = None

    def __init__(self, jax):
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @classmethod
    def get(cls, jax):
        if cls._instance is None:
            cls._instance = cls(jax)
        cls._instance.count = 0
        return cls._instance

    def _on(self, event, duration, **kw):
        if self.active and event == COMPILE_EVENT:
            self.count += 1


def peak_bytes(devices):
    """The most device memory held on the fullest chip: the peak of the
    arrays in use plus the peak reserved apart, where the TPU runtime
    keeps a program's scratch."""
    def held(d):
        stats = d.memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0) + \
            stats.get("peak_bytes_reserved", 0)
    return int(max(held(d) for d in devices))


def _finite(metrics):
    return all(math.isfinite(float(metrics[k])) for k in ("loss", "grad_norm"))


def timed_window(trainer, seconds, setup_s):
    """Updates for ``seconds``: (attempted, failed, end-to-end metrics)."""
    attempted = failed = 0
    t = time.perf_counter()
    while True:
        m = trainer.step()
        attempted += 1
        failed += not _finite(m)
        if time.perf_counter() - t >= seconds:
            break
    window = time.perf_counter() - t
    log(f"window: {attempted} updates in {window:.3f} s")
    return attempted, failed, {
        "update_ms": {"value": window / attempted * 1e3, "unit": "ms"},
        "setup_s": {"value": setup_s, "unit": "s"}}


def traced_window(trainer, cell, devices):
    """``trace_updates`` updates under the profiler, each step inside the
    benchmark's host spans: (attempted, failed, per-layer metrics, the
    trace's device times, breakdown)."""
    import jax

    from bench import trace as tr

    n, failed = cell.traffic["trace_updates"], 0
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tdir)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(n):
                with jax.profiler.TraceAnnotation("bench.fetch_batch"):
                    batches = trainer.fetch()
                with jax.profiler.TraceAnnotation("bench.step_call"):
                    m = trainer.call(batches)
                with jax.profiler.TraceAnnotation("bench.read_metrics"):
                    m = jax.device_get(m)
                failed += not _finite(m)
        jax.profiler.stop_trace()
        events = tr.load(tdir, devices=len(devices))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    run = tr.Run(events, updates=n, chips=len(devices),
                 update_flops=cell.update_flops(),
                 device_kind=devices[0].device_kind)
    metrics = {}
    for spec in cell.per_layer:
        v = cell.metric_reader(spec["name"])(run)
        if v is not None:        # a reader that finds nothing is left out
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    log(f"traced {n} updates: window {run.window_s():.3f} s")
    return n, failed, metrics, {"busy_s": run.busy_s() or 0.0,
                                "window_s": run.window_s()}, \
        run.breakdown(SPANS)


def execute(cell, seed, seconds, trace, *, devices, fault=None):
    """One run of ``cell`` on ``devices``; returns the result dict.

    ``fault`` plants a fault under the timed path (tests only)."""
    import jax

    from bench import compare
    from bench.trainer import Trainer

    counter = CompileCounter.get(jax)
    traffic = cell.traffic
    log(f"cell {cell.name} seed {seed}: {cell.config['name']} "
        f"grad {traffic['grad_batch']}x{traffic['frames']}, CG "
        f"{traffic['cg_batch']}x{traffic['frames']}, mesh "
        f"{traffic.get('mesh')}")
    trainer = Trainer(cell, seed, fault=fault)
    initial = trainer.initial
    prog = compared_updates(trainer, traffic["compared_updates"])
    setup_s = time.time() - T0

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    counter.active = True
    if trace:
        attempted, failed, metrics, times, breakdown = traced_window(
            trainer, cell, devices)
    else:
        attempted, failed, metrics = timed_window(trainer, seconds, setup_s)
    counter.active = False
    log(f"compiles in the window: {counter.count}")
    device["memory_peak_bytes"] = peak_bytes(devices)
    trainer.free()
    del trainer
    jax.clear_caches()

    t = time.perf_counter()
    ref = reference_updates(cell, seed, devices)
    log(f"reference: {len(ref['loss'])} updates in "
        f"{time.perf_counter() - t:.1f} s")
    values = compare.readings(prog, ref, initial)
    log("readings: " + ", ".join(f"{k} {v:.6e}" for k, v in values.items()))
    correct, checks = compare.verdict(values, cell.limits)
    for k, c in checks.items():
        log(f"check {k}: {c['value']:.6e} (limit {c['limit']:.6e})")
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device.update(times)
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def compared_updates(trainer, n):
    """Drive ``trainer`` through its first ``n`` updates with the window's
    own call; return their losses, gradient norms, chosen candidates and
    the parameters after them (copied to the host)."""
    import jax

    prog = {"loss": [], "grad_norm": [], "candidate": [], "accepted": []}
    for i in range(n):
        t = time.perf_counter()
        m = trainer.step()
        prog["loss"].append(float(m["loss"]))
        prog["grad_norm"].append(float(m["grad_norm"]))
        prog["candidate"].append(int(m["cg_best_iter"]))
        prog["accepted"].append(bool(m["cg_accepted"]))
        log(f"set-up update {i}: {time.perf_counter() - t:.3f} s, loss "
            f"{prog['loss'][-1]:.7f}, grad_norm {prog['grad_norm'][-1]:.7e}, "
            f"candidate {prog['candidate'][-1]}, accepted "
            f"{prog['accepted'][-1]}")
    prog["params"] = jax.device_get(trainer.params)
    return prog


def reference_updates(cell, seed, devices, *, dtype=None, pool=None):
    """The reference's readings over the compared updates, its blocks of
    rows spread over ``devices``; ``dtype`` "int8" or bfloat16 gives a
    lower-precision control."""
    import jax
    import jax.numpy as jnp

    from bench import lattices
    from bench.reference import model
    from bench.reference.nghf import Reference

    traffic = cell.traffic
    if pool is None:
        pool, _ = lattices.make_pool(cell.generator, seed, traffic,
                                     cell.config)
    with jax.default_device(devices[0]):
        ref = Reference(cell.config, traffic, devices=devices,
                        dtype=dtype if dtype is not None else jnp.float32)
        params = model.make_weights(cell.config, seed)
        out = {"loss": [], "grad_norm": [], "candidate": [], "accepted": []}
        for i in range(traffic["compared_updates"]):
            gb, cb = (jax.tree.map(jnp.asarray, b) for b in pool[i % len(pool)])
            params, r = ref.update(params, gb, cb)
            out["loss"].append(r["loss"])
            out["grad_norm"].append(r["grad_norm"])
            out["candidate"].append(int(r["cg_best_iter"]))
            out["accepted"].append(bool(r["accepted"]))
            if i == 0:
                out["grad_leaf_norms"] = r["grad_leaf_norms"]
            log(f"reference update {i}: loss {r['loss']:.7f}, grad_norm "
                f"{r['grad_norm']:.7e}, candidate {r['cg_best_iter']}, "
                f"accepted {r['accepted']}")
        out["params"] = jax.device_get(params)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("bench: --seed must be non-negative")

    from bench.cell import load_cell
    cell = load_cell(args.workload)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise SystemExit(f"bench: the program is not in this checkout "
                         f"({e}); nothing was run")

    import jax
    devices = tpu_devices(jax, cell.chips)
    log(f"compile cache: {enable_compile_cache(jax)}")
    out = execute(cell, args.seed, args.seconds, args.trace,
                  devices=devices)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
