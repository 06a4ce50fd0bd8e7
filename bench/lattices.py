"""Lattice pools for the benchmark's traffic: levelization, padding to one
envelope, stacking, and the features that go with the lattices.

The lattice generators (``bench/generators/<name>.py``) and
``levelize_arcs`` here are copies of ``repro.losses.lattice``'s, kept with
the benchmark so that a change to the program cannot move the yardstick.
Everything here is numpy on the host, seeded, and deterministic.
"""
from __future__ import annotations

import numpy as np

# per-arc fields of one lattice, and the ones padded along a second axis
ARC_FIELDS = ("start_t", "end_t", "label", "lm", "corr", "is_start",
              "is_final", "arc_mask")
INDEX_FIELDS = ("preds", "succs")
EMB_STREAM = 1_000_003          # seed stream of the state embeddings
MAX_DRAWS = 1000                # draws per lattice before the envelope
                                # is taken to be too small for the mix


def levelize_arcs(preds, is_start, arc_mask):
    """(L, W) int32 arc ids by topological level, -1 padded.

    level(a) = 0 for start arcs and arcs with no valid predecessor, else
    1 + max(level(pred)).  Arcs must be sorted topologically by id.
    """
    A = preds.shape[0]
    level = np.full(A, -1, np.int64)
    for a in range(A):
        if not arc_mask[a]:
            continue
        ps = preds[a]
        ps = ps[ps >= 0]
        ps = ps[arc_mask[ps]] if ps.size else ps
        if is_start[a] or ps.size == 0:
            level[a] = 0
        else:
            lp = level[ps]
            if (lp < 0).any():
                raise ValueError(f"arcs not topologically sorted at arc {a}")
            level[a] = lp.max() + 1
    n_levels = int(level.max()) + 1 if (level >= 0).any() else 0
    groups = [np.where(level == lv)[0] for lv in range(n_levels)]
    width = max((len(g) for g in groups), default=0)
    out = -np.ones((max(n_levels, 1), max(width, 1)), np.int32)
    for lv, g in enumerate(groups):
        out[lv, :len(g)] = g
    return out


def envelope(lattices):
    """(arcs, fan, levels, width): the smallest shape every lattice of
    ``lattices`` pads into."""
    return (max(l["start_t"].shape[0] for l in lattices),
            max(l[k].shape[1] for l in lattices for k in INDEX_FIELDS),
            max(l["level_arcs"].shape[0] for l in lattices),
            max(l["level_arcs"].shape[1] for l in lattices))


def pad_lattice(lat, env):
    """Pad one lattice to ``env``: masked arcs, -1 predecessor, successor
    and level slots.  Padding changes no statistic of the lattice."""
    A, P, L, W = env
    out = dict(lat)
    pad = A - lat["start_t"].shape[0]
    for k in ARC_FIELDS:
        out[k] = np.pad(lat[k], (0, pad))
    for k in INDEX_FIELDS:
        out[k] = np.pad(lat[k], ((0, pad), (0, P - lat[k].shape[1])),
                        constant_values=-1)
    la = lat["level_arcs"]
    out["level_arcs"] = np.pad(la, ((0, L - la.shape[0]),
                                    (0, W - la.shape[1])),
                               constant_values=-1)
    return out


def stack(lattices):
    """Stack padded lattices into a dict of (B, ...) numpy arrays."""
    return {k: np.stack([l[k] for l in lattices]) for k in lattices[0]}


def features(rng, ref_states, emb, *, noise):
    """(B, T, input_dim) f32 features correlated with the reference state
    sequence: the state's embedding ``emb[state]`` plus Gaussian noise, so
    the sequence loss has signal (as ``repro.data.synthetic.asr_batch``)."""
    noise_arr = rng.normal(scale=noise, size=ref_states.shape + emb.shape[1:])
    return emb[ref_states] + noise_arr.astype(np.float32)


def fits(lat, env):
    A, P, L, W = env
    la = lat["level_arcs"]
    return (lat["start_t"].shape[0] <= A and lat["preds"].shape[1] <= P
            and lat["succs"].shape[1] <= P and la.shape[0] <= L
            and la.shape[1] <= W)


def make_pool(generator, seed, traffic, config):
    """The cell's pool: ``traffic["pool"]`` gradient batches and as many CG
    batches, every lattice drawn from ``seed`` by ``generator`` and padded
    to the traffic's fixed envelope ``[arcs, fan, levels, width]``, so
    every seed gives the jitted update the same shapes.  A lattice that
    does not fit the envelope is drawn again from the same stream.

    Returns a list of ``(grad_batch, cg_batch)``, each a dict with
    ``feats`` (B, T, D) f32, ``labels`` (B, T) int32 and ``lattice`` (a
    dict of numpy lattice fields), and the largest shape drawn."""
    frames = traffic["frames"]
    K, D = config["num_outputs"], config["input_dim"]
    env = tuple(traffic["envelope"])
    emb = np.random.default_rng((seed, EMB_STREAM)).normal(
        size=(K, D)).astype(np.float32)
    pool, drawn = [], []
    for i in range(traffic["pool"]):
        pair = []
        for role, n in ((0, traffic["grad_batch"]), (1, traffic["cg_batch"])):
            rng = np.random.default_rng((seed, i, role))
            lats, draws = [], 0
            while len(lats) < n:
                lat = generator(rng, frames=frames, num_states=K,
                                **traffic["lattice"])
                lat["level_arcs"] = levelize_arcs(
                    lat["preds"], lat["is_start"], lat["arc_mask"])
                if fits(lat, env):
                    lats.append(lat)
                    draws = 0
                elif (draws := draws + 1) >= MAX_DRAWS:
                    raise ValueError(f"no lattice in {MAX_DRAWS} draws fits "
                                     f"the envelope {env}")
            drawn += lats
            lat = stack([pad_lattice(l, env) for l in lats])
            feats = features(rng, lat["ref_states"], emb,
                             noise=traffic["noise"])
            pair.append({"feats": feats, "labels": lat["ref_states"],
                         "lattice": lat})
        pool.append(tuple(pair))
    return pool, envelope(drawn)
