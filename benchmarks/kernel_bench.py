"""Kernel micro-benchmarks.

Off the TPU the Pallas kernels run in interpret mode (Python per-block
execution — correctness, not speed), so the timed artifact there is the
pure-jnp reference path plus an analytic bytes/FLOPs model per kernel;
on a TPU backend the kernels compile and the same harness times them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_call
from repro.kernels import ref


def run(budget: str = "small"):
    rows = []
    key = jax.random.PRNGKey(0)

    # --- SWA attention -----------------------------------------------------
    B, T, H, hd, W = 2, 1024, 4, 128, 256
    q = jax.random.normal(key, (B, T, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, H, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, H, hd))
    f = jax.jit(lambda q, k, v: ref.swa_attention_ref(q, k, v, W))
    us = time_call(f, q, k, v)
    flops = 4.0 * B * H * T * (W + 1) * hd          # windowed qk + av
    rows.append(emit("kernel.swa_attention_ref", us,
                     f"gflops={flops/1e9:.2f};window={W};T={T}"))

    # --- lattice sausage forward --------------------------------------------
    Bs, S, A = 64, 64, 8
    sc = jax.random.normal(key, (Bs, S, A))
    co = jnp.ones((Bs, S, A))
    f = jax.jit(lambda s, c: ref.sausage_forward_ref(s, c))
    us = time_call(f, sc, co)
    rows.append(emit("kernel.lattice_fb_ref", us,
                     f"arcs={Bs*S*A};segments={S}"))

    # --- fused CG vector update ----------------------------------------------
    N = 4_000_000
    x, vv, r, bv = (jax.random.normal(jax.random.fold_in(key, i), (N,))
                    for i in range(4))
    f = jax.jit(lambda x, vv, r, bv: ref.cg_fused_update_ref(0.3, x, vv, r, bv))
    us = time_call(f, x, vv, r, bv)
    bytes_moved = N * 4 * 5                        # 3 reads + 2 writes f32
    rows.append(emit("kernel.cg_fused_ref", us,
                     f"GBps={bytes_moved/us/1e3:.2f};N={N}"))
    return rows


if __name__ == "__main__":
    run()
