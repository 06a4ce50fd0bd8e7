"""Benchmark suite entry point: one benchmark per paper table/figure plus
the roofline report (deliverables d and g).

Prints ``name,us_per_call,derived`` CSV rows (one per measured artifact).

  table2_optimisers   — paper Tables 2/3 + Fig. 2 (optimiser comparison)
  table45_activations — paper Tables 4/5 (ReLU vs sigmoid, RNN/TDNN)
  cg_stability        — Sec. 4.2 (‖θ‖/‖v‖ rescaling) ablation
  precond_ablation    — Sec. 4.3 (shared-parameter preconditioning)
  kernel_bench        — Pallas kernel reference micro-benchmarks
  lattice_engine_bench — per-backend statistics-stage timings (also emits
                        machine-readable JSON rows: backend, B/S/A,
                        ms_per_update)
  optim_bench         — per-optimiser update wall time through the
                        unified core.optim API (sgd/adam/hf/nghf, CG
                        warm start on/off)
  roofline            — per (arch x shape x mesh) roofline terms from the
                        multi-pod dry-run artifacts (results/dryrun/)
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    t0 = time.time()
    print("name,us_per_call,derived")
    from benchmarks import (cg_stability, kernel_bench, lattice_engine_bench,
                            optim_bench, precond_ablation, table2_optimisers,
                            table45_activations)
    table2_optimisers.run()
    table45_activations.run()
    cg_stability.run()
    precond_ablation.run()
    kernel_bench.run()
    lattice_engine_bench.run()
    optim_bench.run()

    from benchmarks import roofline
    rows = roofline.load_all()
    if rows:
        for r in rows:
            print(f"roofline.{r.arch}.{r.shape}.{r.mesh},0.0,"
                  f"compute_s={r.compute_s:.3e};memory_s={r.memory_s:.3e};"
                  f"collective_s={r.collective_s:.3e};"
                  f"bottleneck={r.bottleneck};useful={r.useful_ratio:.3f};"
                  f"temp_gib={r.temp_gib:.2f};fits={r.fits}")
    else:
        print("roofline.missing,0.0,run scripts/run_dryrun_all.sh first")
    print(f"# total benchmark wall time: {time.time() - t0:.0f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
