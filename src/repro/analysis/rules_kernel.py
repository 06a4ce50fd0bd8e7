"""Kernel-sanitizer rules: pure checks over captured Pallas launches.

Everything here takes either a ``kernels.instrument.KernelCall`` record
(the kernel name, grid, BlockSpecs and the *concrete* operands of one
launch) or plain arrays, and returns a list of failure strings — so
every rule is unit-testable against hand-built records without running
a kernel.  ``sanitize_kernels`` is the driver that runs the real
kernels over the adversarial corpus and applies these rules.

Rule IDs (catalog + rationale: docs/static_analysis.md):

  KS001  grid/BlockSpec structure: positive grid, block shapes divide
         the padded dims, every index_map stays in range over the whole
         grid
  KS002  frontier-tensor invariants: ``arc_pos``/``pidx``/``sidx`` stay
         inside the (L*W+1,) buffer (dump slot included), masked/padded
         arcs map to the dump slot, ``level_arcs`` entries are unique
         valid arc ids
  KS003  gather bounds: every index operand a kernel gathers with is
         within the bounds of the buffer it indexes (interpret mode
         clamps out-of-bounds reads silently; compiled TPU/GPU returns
         garbage — this is the rule that catches it on CPU)
  KS004  oracle agreement + finiteness: kernel outputs match the _ref
         oracle and contain no NaN/+inf (the -1e30 masked sentinel is
         legal)
  KS005  precision flow: lse/cumsum/rr accumulations stay f32 even
         under bf16 inputs (checked via jax.eval_shape on the wrappers)
"""
from __future__ import annotations

import itertools
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

import jax

NEG = -1e30
# full index_map sweeps are bounded; past this many grid points only the
# corner points (min/max per axis) are evaluated
_MAX_GRID_POINTS = 4096


# ---------------------------------------------------------------------------
# KS001: grid / BlockSpec / index-map structure
# ---------------------------------------------------------------------------

def _iter_grid_points(grid: Tuple[int, ...]):
    total = 1
    for d in grid:
        total *= d
    if total <= _MAX_GRID_POINTS:
        yield from itertools.product(*(range(d) for d in grid))
    else:
        yield from itertools.product(*(sorted({0, d - 1}) for d in grid))


def _check_one_spec(name: str, what: str, spec, shape: Tuple[int, ...],
                    grid: Tuple[int, ...]) -> List[str]:
    out: List[str] = []
    if spec.block_shape is None:                   # whole operand, e.g. SMEM
        return out
    bs = tuple(spec.block_shape)
    if len(bs) != len(shape):
        return [f"KS001: {name} {what}: block_shape {bs} rank "
                f"{len(bs)} != operand rank {len(shape)} {shape}"]
    for d, (blk, dim) in enumerate(zip(bs, shape)):
        if blk is None:
            continue
        if blk <= 0 or dim % blk:
            out.append(f"KS001: {name} {what}: block dim {d} = {blk} "
                       f"does not divide padded dim {dim} (shape {shape})")
    if out:
        return out
    for point in _iter_grid_points(grid):
        try:
            idx = spec.index_map(*point)
        except Exception as e:                     # index map must be total
            return out + [f"KS001: {name} {what}: index_map raised at "
                          f"grid point {point}: {e!r}"]
        idx = tuple(int(i) for i in (idx if isinstance(idx, tuple)
                                     else (idx,)))
        if len(idx) != len(shape):
            return out + [f"KS001: {name} {what}: index_map returned "
                          f"{len(idx)} indices for rank-{len(shape)} "
                          f"operand at grid point {point}"]
        for d, (i, blk, dim) in enumerate(zip(idx, bs, shape)):
            # None block dims are indexed per element, blocked dims per
            # block — either way the index must stay inside the operand
            bound = dim if blk is None else dim // blk
            if not 0 <= i < bound:
                out.append(f"KS001: {name} {what}: index_map{point} dim "
                           f"{d} -> {i}, outside [0, {bound}) "
                           f"(shape {shape}, block {bs})")
                break
        if out:
            return out
    return out


def check_call_structure(call) -> List[str]:
    """KS001 over one captured launch: every operand/output BlockSpec is
    structurally sound and its index map stays in range on every grid
    point.  Calls without a grid (batch-blocked kernels) are trivially
    clean."""
    out: List[str] = []
    if call.grid is None:
        return out
    if any(d <= 0 for d in call.grid):
        return [f"KS001: {call.name}: non-positive grid {call.grid}"]
    if call.in_specs is not None:
        if len(call.in_specs) != len(call.operand_shapes):
            out.append(f"KS001: {call.name}: {len(call.in_specs)} "
                       f"in_specs for {len(call.operand_shapes)} operands")
        for spec, shape in zip(call.in_specs, call.operand_shapes):
            out.extend(_check_one_spec(call.name, f"in_spec{shape}", spec,
                                       shape, call.grid))
    if call.out_specs is not None and call.out_shape is not None:
        shapes = [tuple(s.shape) for s in jax.tree.leaves(call.out_shape)]
        for spec, shape in zip(call.out_specs, shapes):
            out.extend(_check_one_spec(call.name, f"out_spec{shape}", spec,
                                       shape, call.grid))
    return out


# ---------------------------------------------------------------------------
# KS002: frontier-tensor invariants (losses.lattice.lattice_frontiers)
# ---------------------------------------------------------------------------

def check_frontier_invariants(lat, fr) -> List[str]:
    """KS002 over one batched lattice + its ``Frontiers``: every position
    tensor stays inside the (L*W+1,) level-major buffer (dump slot L*W
    included), masked/padded arcs land on the dump slot, and every valid
    ``level_arcs`` entry is a unique in-range arc id."""
    out: List[str] = []
    la = np.asarray(lat.level_arcs)
    B, L, W = la.shape
    A = int(np.asarray(lat.arc_mask).shape[1])
    dump = L * W
    for name, t in (("arc_pos", fr.arc_pos), ("pidx", fr.pidx),
                    ("sidx", fr.sidx)):
        t = np.asarray(t)
        lo, hi = int(t.min()), int(t.max())
        if lo < 0 or hi > dump:
            out.append(f"KS002: {name} range [{lo}, {hi}] outside the "
                       f"(L*W+1,) buffer [0, {dump}] (dump slot {dump})")
    if la.min() < -1 or la.max() >= A:
        out.append(f"KS002: level_arcs range [{la.min()}, {la.max()}] "
                   f"outside [-1, {A})")
    arc_pos = np.asarray(fr.arc_pos)
    mask = np.asarray(lat.arc_mask)
    for b in range(B):
        valid = la[b][la[b] >= 0]
        if len(valid) != len(np.unique(valid)):
            out.append(f"KS002: batch row {b}: duplicate arc ids in "
                       f"level_arcs")
        # masked arcs never appear in level_arcs, so their position is
        # the dump slot — a compiled gather through a stale position
        # would read live alpha values for dead arcs
        dead = ~mask[b]
        if dead.any() and (arc_pos[b, :A][dead] != dump).any():
            bad = np.where(dead & (arc_pos[b, :A] != dump))[0][:3]
            out.append(f"KS002: batch row {b}: masked arcs {bad.tolist()} "
                       f"map to live frontier slots, expected dump {dump}")
    return out


# ---------------------------------------------------------------------------
# KS003: gather bounds of captured index operands
# ---------------------------------------------------------------------------

# kernel name -> [(operand position, operand name, bounds fn)] where the
# bounds fn maps the launch's operand shape list to (lo, hi_exclusive):
# the half-open range every element of that index operand must lie in.
# The frontier position tensors use the dump slot L*W as their largest
# legal value; the bound comes from the (B, L, W) tile at operand 0.  The
# arc-layout gathers (level_arcs, frame endpoints) run in XLA, outside
# every kernel.
GATHER_SPECS: Dict[str, List[Tuple[int, str, Callable]]] = {
    "_dag_fwd_kernel": [
        (5, "pidx", lambda shp: (0, shp[0][1] * shp[0][2] + 1)),
    ],
    "_dag_bwd_kernel": [
        (4, "sidx", lambda shp: (0, shp[0][1] * shp[0][2] + 1)),
    ],
    "_dag_loss_only_kernel": [
        (5, "pidx", lambda shp: (0, shp[0][1] * shp[0][2] + 1)),
    ],
}


def check_gather_bounds(call) -> List[str]:
    """KS003 over one captured launch: every registered index operand is
    inside the bounds of the buffer it gathers from.  Launches whose
    operands were tracers (captured under jit) are skipped — the
    sanitizer runs kernels eagerly precisely so this check sees values."""
    specs = GATHER_SPECS.get(call.name)
    if not specs or not call.operands:
        return []
    out: List[str] = []
    for pos, name, bounds in specs:
        arr = np.asarray(call.operands[pos])
        lo, hi = bounds(call.operand_shapes)
        amin, amax = int(arr.min()), int(arr.max())
        if amin < lo or amax >= hi:
            out.append(
                f"KS003: {call.name} operand {pos} ({name}): values in "
                f"[{amin}, {amax}] escape the legal gather range "
                f"[{lo}, {hi}) — interpret mode clamps this read, "
                f"compiled TPU/GPU returns garbage")
    return out


# ---------------------------------------------------------------------------
# KS004: oracle agreement + finiteness
# ---------------------------------------------------------------------------

def check_finite(name: str, outputs: Sequence, labels=None) -> List[str]:
    """KS004a: no NaN and no +inf anywhere (the -1e30 masked sentinel and
    large negative values are legal)."""
    out: List[str] = []
    labels = labels or [f"out{i}" for i in range(len(outputs))]
    for lbl, arr in zip(labels, outputs):
        # host-side comparison precision, never traced
        a = np.asarray(arr, dtype=np.float64)  # reprolint: disable=RL007
        if np.isnan(a).any():
            out.append(f"KS004: {name} {lbl}: NaN at "
                       f"{np.argwhere(np.isnan(a))[:3].tolist()}")
        if np.isposinf(a).any():
            out.append(f"KS004: {name} {lbl}: +inf at "
                       f"{np.argwhere(np.isposinf(a))[:3].tolist()}")
    return out


def diff_outputs(name: str, got: Sequence, want: Sequence, *,
                 atol: float = 1e-4, rtol: float = 1e-4,
                 labels=None) -> List[str]:
    """KS004b: kernel outputs vs the _ref oracle.  Masked sentinel slots
    (<= NEG/2 on both sides) compare equal regardless of magnitude."""
    out: List[str] = []
    labels = labels or [f"out{i}" for i in range(len(got))]
    for lbl, g, w in zip(labels, got, want):
        # host-side comparison precision, never traced
        g = np.asarray(g, dtype=np.float64)  # reprolint: disable=RL007
        w = np.asarray(w, dtype=np.float64)  # reprolint: disable=RL007
        if g.shape != w.shape:
            out.append(f"KS004: {name} {lbl}: shape {g.shape} != oracle "
                       f"{w.shape}")
            continue
        both_masked = (g <= NEG / 2) & (w <= NEG / 2)
        err = np.abs(g - w) - (atol + rtol * np.abs(w))
        bad = (err > 0) & ~both_masked & ~(np.isnan(g) & np.isnan(w))
        if bad.any():
            i = tuple(np.argwhere(bad)[0])
            out.append(f"KS004: {name} {lbl}: differs from oracle at "
                       f"{list(i)}: kernel {g[i]:.6g} vs ref {w[i]:.6g} "
                       f"({int(bad.sum())} mismatched elements)")
    return out


# ---------------------------------------------------------------------------
# KS005: precision flow under bf16 inputs
# ---------------------------------------------------------------------------

def check_output_dtypes(name: str, fn, args, expected) -> List[str]:
    """KS005: abstract-evaluate ``fn(*args)`` and compare the flattened
    output dtypes against ``expected`` (a list of (label, dtype)).
    Accumulating an lse/cumsum/<r,r> in bf16 loses the paper's few-
    trusted-CG-iterations premise ~8 bits at a time."""
    out: List[str] = []
    try:
        res = jax.eval_shape(fn, *args)
    except Exception as e:
        return [f"KS005: {name}: eval_shape failed: {e!r}"]
    leaves = jax.tree.leaves(res)
    if len(leaves) != len(expected):
        return [f"KS005: {name}: {len(leaves)} outputs, expected "
                f"{len(expected)}"]
    for leaf, (lbl, dt) in zip(leaves, expected):
        if leaf.dtype != dt:
            out.append(f"KS005: {name} {lbl}: accumulates/returns "
                       f"{leaf.dtype}, expected {np.dtype(dt).name} — "
                       f"bf16 inputs must not degrade the accumulator")
    return out
