"""lattice_stats_ms (ms): per update, the device time of the operations
traced under the ``lattice_stats`` scope (``lattice_engine/api.py``, the
lattice forward-backward wherever it runs), averaged over the devices.
It cuts across the gradient stage, the products and the evaluations:
its time is part of theirs."""

from bench.stages import stage_s


def read(run):
    t = stage_s(run, "lattice_stats")
    return None if t is None else 1e3 * t
