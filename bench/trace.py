"""From a profiler trace to the per-layer numbers: device busy and idle
time, gaps between updates, exposed collective time, and the breakdown.
Busy time is that of the operations that hold no other: an XLA loop's
own event, which spans its body, does not count.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote into a plain
structure, which ``Run`` reduces; the tests reduce a small recorded
structure of the same form (``tests/bench/data/``).

    {"ops":     {device: [[start_ns, end_ns, hlo_name], ...]},
     "modules": {device: [[start_ns, end_ns, program_name], ...]},
     "spans":   [[start_ns, end_ns, name], ...]}        # bench.* host spans

All times are on the profiler's one clock.
"""
from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def op_name(event_name):
    """``fusion.12`` from a device event named by its HLO text
    (``%fusion.12 = f32[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(trace_dir, devices):
    """The structure above from the trace under ``trace_dir``, for the
    first ``devices`` TPU devices."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    out = {"ops": {}, "modules": {}, "spans": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = plane.name[len(DEVICE_PREFIX):]
            if not dev.isdigit() or int(dev) >= devices:
                continue
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    out[key][dev] = [[e.start_ns, e.start_ns + e.duration_ns,
                                      op_name(e.name)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"] += [[e.start_ns, e.start_ns + e.duration_ns,
                                  e.name] for e in line.events
                                 if e.name.startswith("bench.")]
    return out


def union(intervals):
    """Sorted, merged [start, end] intervals."""
    merged = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def length(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def subtract(a, b):
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append([cur, bs])
            cur = max(cur, be)
        if cur < e:
            out.append([cur, e])
    return out


def self_times(ops):
    """(name, self time) of nested events: an event's duration less that
    of the events it contains (an XLA loop holds its body's operations)."""
    out, stack = [], []             # stack of [end, name, self time]
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            _, n, t = stack.pop()
            out.append((n, t))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    out += [(n, t) for _, n, t in stack]
    return out


def leaves(ops):
    """The events that contain no other event.  An XLA loop's event spans
    its body's operations; the time between them is the loop's own
    bookkeeping, in which no operation runs."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    parent, stack = set(), []         # stack of indices of open events
    for i in order:
        s, e = ops[i][0], ops[i][1]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            parent.add(stack[-1])
        stack.append(i)
    return [o for i, o in enumerate(ops) if i not in parent]


def is_collective(name):
    return any(c in name for c in COLLECTIVES)


class Run:
    """A traced window of ``updates`` updates on ``chips`` chips."""

    def __init__(self, events, *, updates, chips, update_flops,
                 device_kind):
        self.events, self.updates, self.chips = events, updates, chips
        self.update_flops, self.device_kind = update_flops, device_kind
        windows = [s for s in events["spans"] if s[2] == "bench.window"]
        self.window = (windows[0][0], windows[0][1]) if windows else None

    def window_s(self):
        return (self.window[1] - self.window[0]) * 1e-9

    def _device_ops(self):
        lo, hi = self.window
        return {d: clip(union(leaves(ops)), lo, hi)
                for d, ops in self.events["ops"].items() if ops}

    def busy_s(self):
        """Seconds in which an operation ran, averaged over the devices;
        a loop's own time, outside its body's operations, is idle."""
        ops = self._device_ops()
        if not ops:
            return None
        return sum(length(v) for v in ops.values()) / len(ops) * 1e-9

    def idle_share(self):
        busy = self.busy_s()
        return None if busy is None else 1.0 - busy / self.window_s()

    def host_gaps_s(self):
        """Gaps on each device between one update's program and the
        next's, where the gap lies within the window."""
        lo, hi = self.window
        gaps = []
        for mods in self.events["modules"].values():
            runs = sorted((s, e) for s, e, _ in mods)
            gaps += [(b[0] - a[1]) * 1e-9 for a, b in zip(runs, runs[1:])
                     if a[1] >= lo and b[0] <= hi]
        return gaps

    def collective_exposed_s(self):
        """Per device, the time of collective operations during which no
        other operation runs; the mean over the devices, or None where no
        collective ran."""
        lo, hi = self.window
        exposed, seen = [], False
        for ops in self.events["ops"].values():
            coll = [o for o in ops if is_collective(o[2])]
            seen |= bool(coll)
            rest = union([o for o in leaves(ops)
                          if not is_collective(o[2])])
            exposed.append(length(subtract(clip(union(coll), lo, hi), rest)))
        if not seen:
            return None
        return sum(exposed) / len(exposed) * 1e-9

    def breakdown(self, span_names, top=10):
        """The device operations that took most self time (seconds per
        device; a loop's time less the operations inside it) and the
        longest idle gaps of the first device, each labelled by the host
        span it falls in."""
        lo, hi = self.window
        per_name = {}
        n_dev = max(len(self.events["ops"]), 1)
        for ops in self.events["ops"].values():
            inside = [[max(s, lo), min(e, hi), n] for s, e, n in ops
                      if e > lo and s < hi]
            for name, t in self_times(inside):
                per_name[name] = per_name.get(name, 0.0) + t * 1e-9 / n_dev
        device_ops = sorted(per_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        ops = self._device_ops()
        if ops:
            first = ops[sorted(ops, key=int)[0]]
            idle = subtract([[lo, hi]], first)
            spans = [s for s in self.events["spans"] if s[2] in span_names]
            for s, e in idle:
                mid = (s + e) / 2
                label = next((n for a, b, n in spans if a <= mid < b),
                             "outside the bench's spans")
                gaps.append([label, (e - s) * 1e-9])
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": [list(kv) for kv in device_ops],
                "idle_gaps": gaps[:top]}
