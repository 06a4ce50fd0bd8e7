"""Readings from which the comparison's limits are set: the program, the
lower-precision control and the planted faults, each against the plain
reference, on many seeds in one process (set-up is paid once).

    python bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        [--controls int8,bfloat16] [--faults half_batch,no_exchange] \
        [--precisions highest] [--memory 1] [--out FILE]

Prints one JSON line per (seed, what) with the compared numbers; the
benchmark's own runs never run this.  ``--precisions`` runs the program
again under other default matmul precisions (the look behind a number
that swings); ``--memory 1`` reads, on the first seed, the compiled
update's memory analysis and the device's bytes in use while it runs.
The limits live in ``bench/limits/<cell>.json``, and PERF.md gives the
readings behind them; a cell is calibrated before it has any.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="",
                    help="comma-separated lower precisions of the reference "
                    "to put in the program's place: int8, bfloat16")
    ap.add_argument("--faults", default="",
                    help="comma-separated faults of bench.trainer.FAULTS")
    ap.add_argument("--program", type=int, default=1,
                    help="0: skip the program's own readings")
    ap.add_argument("--precisions", default="",
                    help="comma-separated default matmul precisions to run "
                    "the program under as well: high, highest")
    ap.add_argument("--memory", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bench import compare, lattices, run
    from bench.cell import load_cell
    from bench.reference import model
    from bench.trainer import Trainer

    cell = load_cell(args.workload, limits=False)
    devices = run.tpu_devices(jax, cell.chips)
    run.enable_compile_cache(jax)
    n = cell.traffic["compared_updates"]
    faults = [f for f in args.faults.split(",") if f]

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        pool, env = lattices.make_pool(cell.generator, seed, cell.traffic,
                                       cell.config)
        run.log(f"seed {seed}: pool in {time.perf_counter() - t:.1f} s, "
                f"envelope (A, P, L, W) = {env}")
        if args.memory:       # first, so that the peak is the program's
            trainer = Trainer(cell, seed, pool=pool)
            emit({"cell": cell.name, "seed": seed, "what": "memory",
                  **memory(trainer, devices)})
            trainer.free()
            del trainer
            args.memory = 0
        ref = run.reference_updates(cell, seed, devices, pool=pool)
        subjects = ([(None, None)] if args.program else []) \
            + [(f, None) for f in faults] \
            + [(None, p) for p in args.precisions.split(",") if p]
        for fault, precision in subjects:
            with jax.default_matmul_precision(precision):
                trainer = Trainer(cell, seed, pool=pool, fault=fault)
                initial = trainer.initial
                prog = run.compared_updates(trainer, n)
            peak = run.peak_bytes(devices)
            trainer.free()
            del trainer
            what = fault or (f"program@{precision}" if precision
                             else "program")
            emit({"cell": cell.name, "seed": seed, "what": what,
                  "peak_bytes": peak,
                  "loss": prog["loss"], "grad_norm": prog["grad_norm"],
                  "candidate": prog["candidate"],
                  "accepted": prog["accepted"],
                  "ref_loss": ref["loss"], "ref_grad_norm": ref["grad_norm"],
                  "ref_candidate": ref["candidate"],
                  "ref_accepted": ref["accepted"],
                  **compare.readings(prog, ref, initial)})
        for control in (c for c in args.controls.split(",") if c):
            dtype = jnp.bfloat16 if control == "bfloat16" else control
            ctl = run.reference_updates(cell, seed, devices, dtype=dtype,
                                        pool=pool)
            initial = jax.device_get(model.make_weights(cell.config, seed))
            emit({"cell": cell.name, "seed": seed,
                  "what": f"control_{control}", "loss": ctl["loss"],
                  "grad_norm": ctl["grad_norm"],
                  "candidate": ctl["candidate"],
                  "accepted": ctl["accepted"],
                  **compare.readings(ctl, ref, initial)})


def memory(trainer, devices):
    """The compiled update's memory analysis, and the most bytes in use on
    the first device, polled while one update runs."""
    import threading

    g, c = trainer.fetch()
    stats = trainer._step.lower(trainer.params, trainer.state, g,
                                c).compile().memory_analysis()
    analysis = {k: getattr(stats, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes") if hasattr(stats, k)}
    seen, done = [], threading.Event()

    def poll():
        while not done.is_set():
            seen.append((devices[0].memory_stats() or {}).get(
                "bytes_in_use", 0))
            time.sleep(0.001)

    before = devices[0].memory_stats() or {}
    thread = threading.Thread(target=poll)
    thread.start()
    try:
        trainer.step()
    finally:
        done.set()
        thread.join()
    return {"analysis": analysis, "stats_before": before,
            "stats_after": devices[0].memory_stats() or {},
            "in_use_during_update_max": max(seen, default=0),
            "polls": len(seen)}


if __name__ == "__main__":
    main()
