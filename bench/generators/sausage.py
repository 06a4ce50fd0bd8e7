"""Sausage (confusion-network) lattices, as ``repro.data.synthetic.
asr_batch`` builds them: the utterance is cut into ``seg_len``-frame
segments, each with ``n_alt`` competing arcs (the first one carries the
reference label), and consecutive segments are fully connected.

A copy of ``repro.losses.lattice.make_sausage_lattice`` (less its padding
option: ``bench.lattices`` pads every pool to one envelope).
"""
from __future__ import annotations

import numpy as np


def generate(rng, *, frames, num_states, seg_len=4, n_alt=3):
    """One lattice as a dict of numpy arrays (unbatched)."""
    n_seg = frames // seg_len
    ref = rng.integers(0, num_states, size=n_seg)
    A = n_seg * n_alt
    start_t = np.zeros(A, np.int32)
    end_t = np.zeros(A, np.int32)
    label = np.zeros(A, np.int32)
    lm = rng.normal(0.0, 0.3, size=A).astype(np.float32)
    corr = np.zeros(A, np.float32)
    preds = -np.ones((A, n_alt), np.int32)
    succs = -np.ones((A, n_alt), np.int32)
    is_start = np.zeros(A, bool)
    is_final = np.zeros(A, bool)
    for s in range(n_seg):
        for j in range(n_alt):
            a = s * n_alt + j
            start_t[a] = s * seg_len
            end_t[a] = (s + 1) * seg_len
            label[a] = ref[s] if j == 0 else rng.integers(0, num_states)
            corr[a] = 1.0 if label[a] == ref[s] else 0.0
            if s == 0:
                is_start[a] = True
            else:
                preds[a] = np.arange((s - 1) * n_alt, s * n_alt)
            if s == n_seg - 1:
                is_final[a] = True
            else:
                succs[a] = np.arange((s + 1) * n_alt, (s + 2) * n_alt)
    ref_states = np.repeat(ref, seg_len).astype(np.int32)
    if len(ref_states) < frames:
        ref_states = np.pad(ref_states, (0, frames - len(ref_states)),
                            mode="edge")
    return dict(start_t=start_t, end_t=end_t, label=label, lm=lm, corr=corr,
                preds=preds, succs=succs, is_start=is_start,
                is_final=is_final, arc_mask=np.ones(A, bool),
                ref_states=ref_states, num_ref_units=np.float32(n_seg))
