"""Per-stage device time of the NGHF update: the leaf device operations of
a traced window (``bench/trace.py``), grouped by the named scopes the
program traced them under (``grad_stage``, ``curvature_product``,
``candidate_eval``, ``cg_solve``, ``lattice_stats``).

A device event names its HLO instruction and nothing more.  The scope
path of an instruction is its HLO ``op_name`` metadata (e.g.
``jit(sequence_step)/cg_solve/while/body/curvature_product/jvp(...)``),
which the program's optimised HLO holds.  While the readers run, the
process still holds the program it traced, so ``scopes`` takes its HLO
from the live executable whose module ran in the window and whose
instructions include every operation of the window, and reads each
instruction's name once.  A recorded trace carries the same map with its
events, under ``"scopes"``:

    {device: {hlo_name: op_name}}
"""
from __future__ import annotations

import re

from bench.trace import leaves

# Protobuf field numbers of HloModuleProto.computations 3;
# HloComputationProto.instructions 2, .id 5; HloInstructionProto.name 1,
# .metadata 7, .id 35, .operand_ids 36, .called_computation_ids 38;
# OpMetadata.op_name 2.
_WRAPPED = re.compile(r"[\w.-]+\((.*)\)")


def _varint(buf, i):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def _fields(buf, want):
    """The fields numbered ``want`` of the protobuf message ``buf`` (a
    memoryview), as (number, value) pairs: an int for a varint, a
    memoryview for a length-delimited field; other fields are skipped
    unread."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            if key >> 3 in want:
                yield key >> 3, v
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        elif wire == 2:
            size, i = _varint(buf, i)
            if key >> 3 in want:
                yield key >> 3, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")


def _first(buf, number):
    return next((v for _, v in _fields(buf, (number,))), None)


def _ids(values):
    """A repeated int64 field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
            continue
        i = 0
        while i < len(v):
            x, i = _varint(v, i)
            out.append(x)
    return out


def op_names(module):
    """{instruction name: op_name} of every instruction of the serialised
    HloModuleProto ``module``, "" where none can be given.

    XLA leaves the instructions it makes without metadata (the loops it
    lowers a large relayout to, a loop's dynamic-update-slices and
    copies), and an operation JAX lowers through a fresh name stack (a
    cumulative sum's ``reduce_window_sum``) has an op_name that does not
    start at the program's root.  Such an instruction takes the op_name
    of the first instruction downstream of it in its computation that has
    a whole one (a relayout loop feeds the reshape it implements), else
    that of the instruction calling its computation (a loop body's
    bookkeeping counts with the loop)."""
    comps, where = {}, {}      # computation id -> {instruction id: fields}
    for _, comp in _fields(memoryview(module), (3,)):
        insts = {}
        for _, inst in _fields(comp, (2,)):
            got = {1: None, 7: None, 35: None, 36: [], 38: []}
            for k, v in _fields(inst, (1, 7, 35, 36, 38)):
                if k in (36, 38):
                    got[k].append(v)
                else:
                    got[k] = v
            op = got[7] is not None and _first(got[7], 2)
            insts[got[35]] = [bytes(got[1]).decode(),
                              bytes(op).decode() if op else "",
                              _ids(got[36]), _ids(got[38])]
        cid = _first(comp, 5)
        comps[cid] = insts
        for iid, (_, _, _, called) in insts.items():
            for c in called:
                where.setdefault(c, (cid, iid))
    roots = {}                 # the program's root, e.g. jit(sequence_step)
    for insts in comps.values():
        for fields in insts.values():
            if fields[1]:
                root = fields[1].split("/", 1)[0]
                roots[root] = roots.get(root, 0) + 1
    root = max(roots, key=roots.get, default="") + "/"
    for insts in comps.values():
        for fields in insts.values():
            if not fields[1].startswith(root):
                fields[1] = ""
    users = {cid: {} for cid in comps}
    for cid, insts in comps.items():
        for iid, (_, _, operands, _) in insts.items():
            for o in operands:
                users[cid].setdefault(o, []).append(iid)
    memo = {}

    def op_name(cid, iid):
        key = (cid, iid)
        if key not in memo:
            memo[key] = ""          # a cycle reads as no name
            memo[key] = comps[cid][iid][1] or downstream(cid, iid) or (
                op_name(*where[cid]) if cid in where else "")
        return memo[key]

    def downstream(cid, iid):
        queue, seen = list(users[cid].get(iid, ())), {iid}
        for u in queue:
            if u in seen:
                continue
            seen.add(u)
            if comps[cid][u][1]:
                return comps[cid][u][1]
            queue += users[cid].get(u, ())
        return ""

    return {fields[0]: op_name(cid, iid)
            for cid, insts in comps.items() for iid, fields in insts.items()}


def scope_components(path):
    """The scope names in an op_name path, each unwrapped from the
    transforms JAX wraps it in (``transpose(jvp(curvature_product))`` ->
    ``curvature_product``)."""
    out = set()
    for part in path.split("/"):
        m = _WRAPPED.fullmatch(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.fullmatch(part)
        out.add(part)
    return out


def scopes(run, window_ops):
    """{device: {hlo_name: op_name}} for ``window_ops`` ({device: leaf ops
    in the window}): the recorded map where ``run``'s events carry one,
    else the one read from the live program that ran them ({} where the
    process holds none)."""
    if "scopes" in run.events:
        return run.events["scopes"]
    import jax

    held = [m for exe in jax.devices()[0].client.live_executables()
            for m in exe.hlo_modules()]
    parsed, out = {}, {}
    for dev, ops in window_ops.items():
        ran = {o[2] for o in ops}
        programs = {p.split("(", 1)[0]
                    for _, _, p in run.events["modules"].get(dev, ())}
        for i, m in enumerate(held):
            if not ran or m.name not in programs:
                continue
            if i not in parsed:
                parsed[i] = op_names(m.as_serialized_hlo_module_proto())
            if ran <= parsed[i].keys():
                out[dev] = {n: parsed[i][n] for n in ran if parsed[i][n]}
                break
    return out


def stage_s(run, within, besides=()):
    """Seconds per update, averaged over the devices, of the leaf
    operations in ``run``'s window whose scope path holds the scope
    ``within`` and none of ``besides``; None where no operation of the
    window carries ``within`` (a program without the scopes)."""
    if run.window is None:
        return None
    totals = run.__dict__.get("_stage_totals")
    if totals is None:              # {device: {scope path: ns}}, once a run
        lo, hi = run.window
        window_ops = {dev: [o for o in leaves(ops) if o[1] > lo and o[0] < hi]
                      for dev, ops in run.events["ops"].items() if ops}
        names, totals = scopes(run, window_ops), {}
        for dev, ops in window_ops.items():
            t = totals[dev] = {}
            for s, e, n in ops:
                path = names.get(dev, {}).get(n, "")
                t[path] = t.get(path, 0) + min(e, hi) - max(s, lo)
        run._stage_totals = totals
    seen, ns = False, 0
    for t in totals.values():
        for path, d in t.items():
            comps = scope_components(path)
            if within in comps:
                seen = True
                if not comps.intersection(besides):
                    ns += d
    if not seen:
        return None
    return ns / max(len(totals), 1) / run.updates * 1e-9
