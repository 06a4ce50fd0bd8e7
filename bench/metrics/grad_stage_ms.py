"""grad_stage_ms (ms): per update, the device time of the operations
traced under the ``grad_stage`` scope (``core/curvature.py``
``grad_and_loss``: the gradient batch's forward, lattice statistics and
backward), averaged over the devices."""

from bench.stages import stage_s


def read(run):
    t = stage_s(run, "grad_stage")
    return None if t is None else 1e3 * t
