"""Which of the TDNN's dots need more than one bf16 pass: the program's
update run with ``models/acoustic.py``'s ``TDNN_PRECISION`` table set to
each variant, against the plain reference, on the same seeds in one
process (each seed's pool and reference are made once).

    python bench/bisect_precision.py --workload tdnn-asr.nghf-mpe.dag \
        --seeds 11,12,13 --variants default,hidden_high,all_high \
        [--against all_high] [--timed 5] [--timed-seeds 2] [--out FILE]

Prints one JSON line per (seed, variant) with the compared numbers and,
on the first ``--timed-seeds`` seeds, the mean wall time of ``--timed``
updates after the compared ones; then one line per variant with its
verdict, and one with the limits the rule below gives.

``default`` states no precision: every dot runs at the ambient default
in its (B, T) form, the program as it was before the TDNN stated one.

Verdict: a variant agrees as closely as ``--against`` when, on every
seed, it picks the candidates and acceptances that ``--against`` picks
there, and its largest reading of each check over the seeds is at most
``AS_CLOSE`` times the largest of ``--against``.  A variant not cheaper
than ``--against`` is not judged.

Limit rule, for each check: P = the largest reading of ``--against``
over the seeds; C = the smallest reading of ``default`` that is at
least 3 P; the limit is sqrt(P C) rounded up to one significant digit
(fresh seeds read higher than the calibration's, so the room goes
above P).  Where no reading of ``default`` reaches 3 P the check gets
no limit from here.
The benchmark's own runs never run this."""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

LAYERS = ("hidden", "output")
AS_CLOSE = 2.0


def variants():
    from jax.lax import Precision as P
    return {"default": dict.fromkeys(LAYERS),
            "out_high": {"hidden": None, "output": P.HIGH},
            "out_highest": {"hidden": None, "output": P.HIGHEST},
            "hidden_high": {"hidden": P.HIGH, "output": None},
            "all_high": dict.fromkeys(LAYERS, P.HIGH),
            "all_highest": dict.fromkeys(LAYERS, P.HIGHEST)}


# passes of bf16 per dot, for "cheaper than": one, three or six
PASSES = {None: 1, "HIGH": 3, "HIGHEST": 6}


def cost(table):
    return tuple(PASSES[None if p is None else p.name] for p in
                 (table["hidden"], table["output"]))


def verdict(recs, against):
    """{variant: {"agrees": bool, "max": {check: largest reading},
    "departs_on": [seeds]}}; ``recs`` the emitted per-seed lines."""
    from bench.compare import NAMES

    by = {}
    for r in recs:
        by.setdefault(r["what"], {})[r["seed"]] = r
    ref = by.get(against, {})
    out = {}
    for what, seeds in by.items():
        top = {k: max(r[k] for r in seeds.values()) for k in NAMES}
        departs = [s for s, r in seeds.items() if s not in ref or (
            r["candidate"], r["accepted"]) != (ref[s]["candidate"],
                                               ref[s]["accepted"])]
        out[what] = {"max": top, "departs_on": sorted(departs),
                     "agrees": bool(ref) and not departs and all(
                         top[k] <= AS_CLOSE * max(
                             r[k] for r in ref.values()) for k in NAMES)}
    return out


def limit_rule(sound, control):
    """{check: {"P", "C", "limit"}} from the readings of the sound
    program and of the one-pass control (lists of {check: value})."""
    from bench.compare import NAMES

    out = {}
    for k in NAMES:
        p = max(r[k] for r in sound)
        above = [r[k] for r in control if r[k] >= 3 * p]
        c = min(above) if above else None
        lim = None
        if c is not None and p > 0:
            g = math.sqrt(p * c)
            unit = 10.0 ** math.floor(math.log10(g))
            lim = float(f"{math.ceil(g / unit - 1e-9) * unit:.1g}")
        out[k] = {"P": p, "C": c, "limit": lim}
    return out


def main(argv=None, devices=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", required=True,
                    help="comma-separated, of: " + ", ".join(
                        ("default", "out_high", "out_highest", "hidden_high",
                         "all_high", "all_highest")))
    ap.add_argument("--against", default="all_high")
    ap.add_argument("--timed", type=int, default=5)
    ap.add_argument("--timed-seeds", type=int, default=2)
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import compare, lattices, run
    from bench.cell import load_cell
    from bench.trainer import Trainer
    from repro.launch import steps
    from repro.models import acoustic

    table = variants()
    names = [v for v in args.variants.split(",") if v]
    unknown = sorted(set(names) - set(table))
    if unknown:
        raise SystemExit(f"bisect: unknown variants {unknown}")
    cell = load_cell(args.workload, root=args.root, limits=False)
    if devices is None:
        devices = run.tpu_devices(jax, cell.chips)
        run.enable_compile_cache(jax)
    n = cell.traffic["compared_updates"]
    stated, jit_step = acoustic.TDNN_PRECISION, steps.jit_train_step
    # one jitted update per variant, for every seed: the step is a
    # function of its arguments alone, so a later seed skips its trace
    jitted, failed, recs = {}, set(), []

    def jit_once(fn, **kw):
        if name not in jitted:
            jitted[name] = jit_step(fn, **kw)
        return jitted[name]

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        pool, env = lattices.make_pool(cell.generator, seed, cell.traffic,
                                       cell.config)
        ref = run.reference_updates(cell, seed, devices, pool=pool)
        run.log(f"seed {seed}: pool and reference in "
                f"{time.perf_counter() - t:.1f} s, envelope {env}")
        for name in (v for v in names if v not in failed):
            acoustic.TDNN_PRECISION = table[name]
            steps.jit_train_step = jit_once
            try:
                t = time.perf_counter()
                trainer = Trainer(cell, seed, pool=pool)
                initial = trainer.initial
                prog = run.compared_updates(trainer, n)
                first_s = time.perf_counter() - t
                times = []
                for _ in range(args.timed if i < args.timed_seeds else 0):
                    t = time.perf_counter()
                    trainer.step()
                    times.append(time.perf_counter() - t)
                peak = run.peak_bytes(devices)
                trainer.free()
                del trainer
            except Exception as e:     # a compile the chip refuses
                failed.add(name)
                emit({"cell": cell.name, "seed": seed, "variant": name,
                      "error": str(e)[:2000]})
                continue
            finally:
                acoustic.TDNN_PRECISION = stated
                steps.jit_train_step = jit_step
            rec = {"cell": cell.name, "seed": seed, "what": name,
                   "first_s": first_s,
                   "update_ms": (1e3 * sum(times) / len(times)
                                 if times else None),
                   "peak_bytes": peak,
                   "loss": prog["loss"], "grad_norm": prog["grad_norm"],
                   "candidate": prog["candidate"],
                   "accepted": prog["accepted"],
                   "ref_candidate": ref["candidate"],
                   "ref_accepted": ref["accepted"],
                   **compare.readings(prog, ref, initial)}
            recs.append(rec)
            emit(rec)

    judged = verdict(recs, args.against)
    against = cost(table[args.against])
    for name in (v for v in names if v in judged):
        cheaper = name != args.against and all(
            a <= b for a, b in zip(cost(table[name]), against))
        emit({"variant": name, "judged": cheaper, **judged[name]})
    sound = [r for r in recs if r["what"] == args.against]
    control = [r for r in recs if r["what"] == "default"]
    if sound and control:
        emit({"limit_rule": limit_rule(sound, control)})


if __name__ == "__main__":
    main()
