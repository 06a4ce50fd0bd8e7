"""General-DAG lattices with variable fan-in and skip arcs.

Nodes sit at random frame boundaries; consecutive nodes are always
connected (every arc lies on a start-to-final path) and arcs over 2-3
boundaries are added with ``skip_prob``; each connected pair of nodes
carries 1..``max_alt`` parallel arcs with distinct labels.

A copy of ``repro.losses.lattice.make_random_dag_lattice`` (less its
padding option: ``bench.lattices`` pads every pool to one envelope), with
one change: the reference is a path of the lattice — the first arc drawn
between each pair of consecutive nodes — instead of a random state per
frame.  With the program's random reference no arc label matches it, every
arc's correctness is about 0, and the MPE loss and its gradient are about
0 too (|loss| ~ 1e-4): candidate selection then compares losses that
differ by less than their rounding.  A decoding lattice holds the
reference path or one close to it.
"""
from __future__ import annotations

import numpy as np


def generate(rng, *, frames, num_states, skip_prob=0.4, max_alt=3):
    """One lattice as a dict of numpy arrays (unbatched)."""
    n_inner = int(rng.integers(2, max(3, frames // 4)))
    inner = rng.choice(np.arange(1, frames), size=min(n_inner, frames - 1),
                       replace=False)
    times = np.array(sorted({0, frames} | set(int(t) for t in inner)))
    N = len(times)
    ref = np.zeros(frames, np.int32)

    raw = []                            # (start node, end node, label)
    for i in range(N - 1):
        targets = [i + 1]
        for j in range(i + 2, min(i + 4, N)):
            if rng.random() < skip_prob:
                targets.append(j)
        for j in targets:
            labels = rng.choice(num_states, size=int(rng.integers(
                1, max_alt + 1)), replace=False)
            if j == i + 1:              # the reference path's arc
                ref[times[i]:times[j]] = labels[0]
            for lab in labels:
                raw.append((i, j, int(lab)))
    raw.sort()                          # (start, end) order is topological
    A = len(raw)

    start_t = np.array([times[i] for i, _, _ in raw], np.int32)
    end_t = np.array([times[j] for _, j, _ in raw], np.int32)
    label = np.array([l for _, _, l in raw], np.int32)
    lm = rng.normal(0.0, 0.3, size=A).astype(np.float32)
    corr = np.array([float(np.sum(ref[s:e] == l)) / max(e - s, 1)
                     for (s, e, l) in zip(start_t, end_t, label)],
                    np.float32)
    by_end, by_start = {}, {}
    for a, (i, j, _) in enumerate(raw):
        by_end.setdefault(j, []).append(a)
        by_start.setdefault(i, []).append(a)
    P = max(max((len(v) for v in by_end.values()), default=1),
            max((len(v) for v in by_start.values()), default=1))
    preds = -np.ones((A, P), np.int32)
    succs = -np.ones((A, P), np.int32)
    for a, (i, j, _) in enumerate(raw):
        for k, p in enumerate(by_end.get(i, [])):
            preds[a, k] = p
        for k, s in enumerate(by_start.get(j, [])):
            succs[a, k] = s
    is_start = np.array([i == 0 for i, _, _ in raw])
    is_final = np.array([j == N - 1 for _, j, _ in raw])
    return dict(start_t=start_t, end_t=end_t, label=label, lm=lm, corr=corr,
                preds=preds, succs=succs, is_start=is_start,
                is_final=is_final, arc_mask=np.ones(A, bool), ref_states=ref,
                num_ref_units=np.float32(N - 1))
